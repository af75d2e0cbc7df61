"""The four-chip compact cell: its configuration, the sharded plan
against the plain reference, and the readers of its sharded sweep.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_four_chip.py

The plan runs in a subprocess over four virtual CPU devices, a small
pass of the cell over the four this process has (``bench/conftest.py``);
the readers read a synthetic four-chip trace.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import common, run as R, trace as tr  # noqa: E402

SPEC = R.load_spec()
CELL = "sensor-x6.compact-4chip"


def test_sensor_x6_resolves_to_four_chips_and_the_sharded_backend():
    wl, cfg, traffic = R.resolve(SPEC, CELL)
    assert wl["config"] == cfg["name"] == "sensor-x6"
    assert wl["chips"] == 4 and traffic["driver"] == "compact"
    assert cfg["backend"] == "sharded" and cfg["detector"] == "gfsp"
    assert cfg["n_observations"] == 1_200_000
    assert cfg["published"]["n_observations"] == 19_200_000
    assert list(cfg["reduced"]) == ["n_observations"]
    # the one-chip deployment's schema and draws, but for the scale and
    # the backend
    base = R.resolve(SPEC, "sensor-d1d2d3.compact")[1]
    for key in ("n_sensors", "n_timestamps", "n_values", "zipf_a",
                "triples_per_observation", "assumed", "guarantees",
                "source"):
        assert cfg[key] == base[key], key


PLANS = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import common, reference, run as R
from bench.gen import SensorGraph
from repro.api import Compactor

_, cfg, _ = R.resolve(R.load_spec(), {cell!r})
common.DEVICES[:] = jax.devices()
out = []
for seed, scale in {cases!r}:
    g = SensorGraph(dict(cfg, **scale), seed)
    type_id, inst = g.id("rdf:type"), g.id("repro:instanceOf")

    def plan(comp):
        store = common.load_store(g)
        found = [comp.detect(store, g.id(c))
                 for c in ("ssn:Observation", "ssn:Measurement")]
        return (sorted([e.class_id, sorted(e.props), e.detection.ami]
                       for e in comp.plan(store)),
                [[sorted(d.props), d.ami, d.edges] for d in found])

    want = reference.greedy_fsp(g.spo, type_id, inst)
    sharded, host = (plan(common.compactor(cfg)),
                     plan(Compactor(detector="gfsp", backend="host")))
    out.append({{"sharded": sharded[0], "host": host[0],
                "reference": sorted([c, list(sp), a]
                                    for c, (sp, a) in want.items()),
                "sharded_detect": sharded[1], "host_detect": host[1],
                "mesh": common.compactor(cfg).backend.mesh.devices.size}})
print(json.dumps(out))
"""


def test_sharded_plan_equals_the_reference_and_the_host_plan():
    """On seeded graphs at a small size, G.FSP over the four-way sharded
    sweep plans every class as plain G.FSP and the host backend do, and
    finds each class's star pattern, AMI and edges as the host does."""
    cases = [(2**31 + 5, {"n_observations": 4000}),
             (7, {"n_observations": 2500}),
             (2**32 + 3, {"n_observations": 3000, "n_sensors": 20,
                          "n_timestamps": 60, "n_values": 40})]
    code = textwrap.dedent(PLANS.format(
        root=ROOT, src=os.path.join(ROOT, "src"), cell=CELL, cases=cases))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    for case in json.loads(r.stdout.strip().splitlines()[-1]):
        assert case["mesh"] == 4
        assert case["reference"], case
        assert case["sharded"] == case["reference"] == case["host"]
        assert case["sharded_detect"] == case["host_detect"]


def test_sensor_x6_passes_check_to_zero_over_four_shards(monkeypatch):
    """The cell's configuration as it is but for ``n_observations``
    compacts over a ``(4, 1)`` mesh of the four virtual devices, every
    batch over four shards and none dropping a signature, and each pass
    checks to 0; its control is not correct."""
    import jax
    from bench.controls import CONTROLS
    from repro.core import sweep
    meshes = set()
    plain = sweep.ShardedSweepWorkspace._run_batch

    def spy(self, stack, n_sp):
        meshes.add((0 if self.mesh is None else self.mesh.devices.size,
                    tuple(self.dp_axes)))
        return plain(self, stack, n_sp)

    monkeypatch.setattr(sweep.ShardedSweepWorkspace, "_run_batch", spy)
    wl, cfg, traffic = R.resolve(SPEC, CELL)
    cfg = dict(cfg, n_observations=3000)

    def run():
        return R.execute(SPEC, wl, cfg, traffic, seed=2**31 + 23,
                         seconds=2.0, trace=False, devices=jax.devices())[0]

    sweep.reset_trace_stats()
    res = run()
    stats = dict(sweep.SHARD_STATS)
    assert res["correct"], res["checks"]
    assert {c: v["value"] for c, v in res["checks"].items()} == \
        {"classes_differ": 0, "rows_differ": 0}
    assert meshes == {(4, ("data",))}
    assert stats["batches"] > 0 and stats["shards"] == 4 * stats["batches"]
    assert stats["dropped"] == 0 and stats["exchange_bytes"] > 0
    with CONTROLS[traffic["driver"]]():
        bad = run()
    assert not bad["correct"], bad["checks"]


# a synthetic traced window on four chips: two passes, each chip running
# the sharded sweep and its all-to-all at its own pace
WINDOW = tr.Event(tr.WINDOW_SPAN, 0.0, 10.0)
PASSES = [tr.Event("bench.pass", 0.5, 4.0), tr.Event("bench.pass", 5.0, 4.5)]
# the exchange as the TPU compiler writes it (``v5e:2x2``), and by name alone
A2A = ("%all_to_all.3 = u32[4,8,16392,2]{2,1,3,0:T(8,128)S(1)} "
       "all-to-all(%copy.32), channel_id=1, replica_groups={{0,1,2,3}}, "
       "dimensions={0}")
A2A_NAME = "%all-to-all.5"
FUSION = "%fusion.7 = u32[8,256,2]{2,1,0} fusion(s32[256,8] %y)"


def four_chips(module="jit_sharded_sweep", exchange=A2A):
    device = {}
    for chip in range(4):
        mods = [tr.Event(f"{module}(81726354)", 1.0 + 0.01 * chip,
                         0.020 + 0.001 * chip),
                tr.Event(f"{module}(81726354)", 6.0, 0.030)]
        ops = [tr.Event(FUSION, 1.0 + 0.01 * chip, 0.005),
               tr.Event(exchange, 1.01 + 0.01 * chip, 0.001 * (chip + 1)),
               tr.Event(exchange, 6.01, 0.002)]
        device[chip] = (ops, mods)
    red = tr.reduce_events(device, [WINDOW] + PASSES)
    return R.Reading(trace=red, counters={}, peaks={}, window_s=10.0)


def read(metric, reading):
    return R.load_module(os.path.join(BENCH, "metrics",
                                      metric + ".py")).read(reading)


@pytest.fixture
def four_devices(monkeypatch):
    monkeypatch.setattr(common, "DEVICES", ["tpu"] * 4)


def test_sharded_sweep_device_ms_is_per_chip_and_per_pass(four_devices):
    # four chips' programs: (0.020 + 0.021 + 0.022 + 0.023) + 4 * 0.030 s
    want = (0.086 + 0.120) * 1e3 / 4 / 2
    assert read("sharded_sweep.device_ms", four_chips()) == \
        pytest.approx(want)


@pytest.mark.parametrize("exchange", [A2A, A2A_NAME])
def test_all_to_all_device_ms_is_per_chip_and_per_pass(four_devices,
                                                       exchange):
    # (0.001 + 0.002 + 0.003 + 0.004) + 4 * 0.002 s over 4 chips, 2 passes
    want = (0.010 + 0.008) * 1e3 / 4 / 2
    assert read("all_to_all.device_ms", four_chips(exchange=exchange)) == \
        pytest.approx(want)


@pytest.mark.parametrize("module", ["jit_sweep", "jit_batch"])
def test_sharded_sweep_device_ms_reads_nothing_without_its_program(
        four_devices, module):
    """The one-chip sweep, and the sharded sweep under its former name
    ``jit_batch``, are not read."""
    assert read("sharded_sweep.device_ms", four_chips(module=module)) is None


def test_all_to_all_device_ms_reads_nothing_without_an_exchange(
        four_devices):
    assert read("all_to_all.device_ms", four_chips(exchange=FUSION)) is None


@pytest.mark.parametrize("metric", ["sharded_sweep.device_ms",
                                    "all_to_all.device_ms"])
def test_four_chip_readers_read_nothing_without_passes_or_chips(
        monkeypatch, metric):
    reading = four_chips()
    monkeypatch.setattr(common, "DEVICES", [])
    assert read(metric, reading) is None
    monkeypatch.setattr(common, "DEVICES", ["tpu"] * 4)
    reading.trace.spans = [s for s in reading.trace.spans
                           if s.name != "bench.pass"]
    assert read(metric, reading) is None
