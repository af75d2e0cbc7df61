"""Tests of the benchmark itself, on the CPU at a size a test run holds.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Every workload resolves its files by name; each cell's run comes out
correct, and its control (``bench/controls.py``: the answer altered
where the program produces it) and each fault its timed path can have
come out not correct; the trace reduction and the kernel byte count
hold on a recorded trace; a sharded cell on four chips runs the sweep
over a four-device mesh, and a cell with more chips than the run has
is refused.
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

from bench import run as R  # noqa: E402

SPEC = R.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
# a graph a test holds: the configuration's schema at 3,000 observations
SMALL = {"n_observations": 3000, "n_sensors": 20, "n_timestamps": 60,
         "n_values": 40}
RATES = {"ingest": 60.0}


@pytest.mark.parametrize("cell", CELLS)
def test_workload_resolves_by_name(cell):
    wl, cfg, traffic = R.resolve(SPEC, cell)
    assert os.path.isfile(os.path.join(BENCH, "drivers",
                                       traffic["driver"] + ".py"))
    for m in SPEC["per_layer"] + SPEC["end_to_end"]:
        if R.applies(m, cell) and m in SPEC["per_layer"]:
            assert os.path.isfile(os.path.join(BENCH, "metrics",
                                               m["name"] + ".py"))
    entry = {c["name"]: c for c in SPEC["configs"]}[wl["config"]]
    for key in entry["reduced"]:
        assert key in cfg["reduced"]
    reports = [m["name"] for m in SPEC["end_to_end"] if R.applies(m, cell)]
    assert "setup_s" in reports and len(reports) >= 2
    assert any(R.applies(m, cell) for m in SPEC["per_layer"])


def test_benchmark_names_only_its_own_cells():
    spec = R.load_spec()
    cells = {w["name"] for w in spec["workloads"]}
    assert {w["config"] for w in spec["workloads"]} == \
        {c["name"] for c in spec["configs"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]


def _small(cell):
    wl, cfg, traffic = R.resolve(SPEC, cell)
    cfg = dict(cfg, **SMALL)
    rate = RATES.get(traffic["driver"])
    if rate is not None:
        traffic = dict(traffic, rate_per_s=rate)
    return wl, cfg, traffic


def _execute(cell, seed=2**31 + 11):
    import jax
    wl, cfg, traffic = _small(cell)
    return R.execute(SPEC, wl, cfg, traffic, seed=seed, seconds=2.0,
                     trace=False, devices=jax.devices())[0]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_correct_and_control_not(cell):
    from bench.controls import CONTROLS
    res = _execute(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    names = {m["name"] for m in SPEC["end_to_end"] if R.applies(m, cell)}
    assert set(res["metrics"]) == names
    assert list(res)[-1] == "checks"
    _, _, traffic = R.resolve(SPEC, cell)
    with CONTROLS[traffic["driver"]]():
        bad = _execute(cell)
    assert not bad["correct"], bad["checks"]


FAULT_CASES = [(cell, fault) for cell in CELLS
               for fault in sorted(__import__("bench.controls", fromlist=[
                   "FAULTS"]).FAULTS[R.resolve(SPEC, cell)[2]["driver"]])]


@pytest.mark.parametrize("cell,fault", FAULT_CASES)
def test_fault_comes_out_not_correct(cell, fault):
    from bench.controls import FAULTS
    _, _, traffic = R.resolve(SPEC, cell)
    with FAULTS[traffic["driver"]][fault]():
        bad = _execute(cell)
    assert not bad["correct"], bad["checks"]


def test_trace_reduction_on_recorded_trace():
    from bench import trace as tr
    with open(os.path.join(BENCH, "tests", "data",
                           "trace_compact.json")) as f:
        rec = json.load(f)
    ev = lambda rows: [tr.Event(*r) for r in rows]  # noqa: E731
    red = tr.reduce_events({0: (ev(rec["ops"]), ev(rec["modules"]))},
                           ev(rec["spans"]))
    assert red.window_s == pytest.approx(rec["window_s"])
    assert 0 < red.busy_s <= red.window_s
    assert red.busy_s == pytest.approx(rec["busy_s"], rel=1e-9)
    names = dict(red.top_ops(10))
    assert "sig_hash" in names and "sort" in names
    gaps = red.idle_gaps(10)
    assert gaps and all(g[0].startswith(("bench.", "host.")) for g in gaps)
    assert sum(g for _, g in red.named_gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)


def test_union_and_gaps():
    from bench import trace as tr
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert tr.union_length(iv) == 3.0
    assert tr.gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]


def test_kernel_bytes_from_event_shapes():
    from bench.kernels import call_hbm_bytes
    hbm = ("%sig_hash.1 = u32[8,262144,2]{2,1,0:T(8,128)} custom-call("
           "s32[8,262144,8]{2,1,0:T(8,128)} %x), custom_call_target=")
    assert call_hbm_bytes(hbm) == 8 * 262144 * (2 + 8) * 4
    vmem = ("%seg_boundaries.1 = s32[8,1,262144]{2,1,0:T(1,128)S(1)} "
            "custom-call(s32[8,2,262144]{2,1,0:T(2,128)S(1)} %y)")
    assert call_hbm_bytes(vmem) is None


def test_unknown_device_has_no_peaks():
    from bench import peaks
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_compact_readers_on_recorded_trace():
    from bench import peaks, trace as tr
    with open(os.path.join(BENCH, "tests", "data",
                           "trace_compact.json")) as f:
        rec = json.load(f)
    ev = lambda rows: [tr.Event(*r) for r in rows]  # noqa: E731
    red = tr.reduce_events({0: (ev(rec["ops"]), ev(rec["modules"]))},
                           ev(rec["spans"]))
    reading = R.Reading(trace=red, counters={},
                        peaks=peaks.peaks("TPU v5 lite"),
                        window_s=red.window_s)
    cell = "sensor-d1d2d3.compact"
    for m in SPEC["per_layer"]:
        if not R.applies(m, cell):
            continue
        v = R.load_module(os.path.join(BENCH, "metrics",
                                       m["name"] + ".py")).read(reading)
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100.0, m["name"]


MESH_RUN = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import controls, run as R
from repro.core import sweep

meshes = set()
plain = sweep.ShardedSweepWorkspace._run_batch


def spy(self, stack, n_sp):
    meshes.add((0 if self.mesh is None else self.mesh.devices.size,
                tuple(self.dp_axes)))
    return plain(self, stack, n_sp)


sweep.ShardedSweepWorkspace._run_batch = spy
spec = R.load_spec()
wl, cfg, traffic = R.resolve(spec, {cell!r})
wl = dict(wl, chips=4)
cfg = dict(cfg, **{small!r}, backend="sharded")


def go():
    return R.execute(spec, wl, cfg, traffic, seed=2**31 + 23, seconds=2.0,
                     trace=False, devices=jax.devices())[0]


ok = go()
with controls.CONTROLS["compact"]():
    bad = go()
print(json.dumps({{"correct": ok["correct"], "checks": ok["checks"],
                  "control_correct": bad["correct"],
                  "meshes": sorted(meshes)}}))
"""
COMPACT_CELL = next(c for c in CELLS
                    if R.resolve(SPEC, c)[2]["driver"] == "compact")


def test_sharded_cell_on_four_chips_runs_over_a_four_device_mesh():
    """A cell on four chips whose configuration names the sharded
    backend compacts over a ``(4, 1)`` mesh of four (virtual) devices,
    correct, and its control is not."""
    code = textwrap.dedent(MESH_RUN.format(
        root=ROOT, src=os.path.join(ROOT, "src"), cell=COMPACT_CELL,
        small=SMALL))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert not out["control_correct"]
    assert out["meshes"] == [[4, ["data"]]]


def test_cell_with_more_chips_than_the_run_fails_at_once():
    import jax
    wl, cfg, traffic = _small(COMPACT_CELL)
    wl = dict(wl, chips=len(jax.devices()) + 1)
    cfg = dict(cfg, backend="sharded")
    with pytest.raises(ValueError, match="needs .* chips; the run has"):
        R.execute(SPEC, wl, cfg, traffic, seed=2**31 + 11, seconds=2.0,
                  trace=False, devices=jax.devices())
