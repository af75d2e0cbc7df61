"""Benchmark driver: one module per paper table/figure + kernel micro +
the distributed-FSP roofline cell + the detector x backend perf snapshot
+ the star-query latency matrix (raw vs factorized x host/device)
+ the multi-star BGP matrix (cost-based planner vs fixed strategies)
+ the online-compaction drift matrix (soak via ``launch/serve.py``).

    python -m benchmarks.run [--fast]        # full paper suite
    python -m benchmarks.run --snapshot      # BENCH_fsp.json only (CI smoke)
"""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time

SNAPSHOT_PATH = os.path.join(os.path.dirname(__file__), "..",
                             "BENCH_fsp.json")

# detector x backend cells of the unified pipeline; efsp is now
# backend-parametric (level-batched through the sweep engine); gspan is
# the honest enumeration baseline and stays host-only
SNAPSHOT_CELLS = [("gfsp", "host"), ("gfsp", "device"), ("gfsp", "sharded"),
                  ("efsp", "host"), ("efsp", "device"), ("efsp", "sharded"),
                  ("gspan", "host")]


# (scale x shape) grid cells: every cell runs on BOTH substrate tiers
# in its own subprocess (per-process ru_maxrss); the sensor shape rides
# the device backend so the grid carries a real jit path (zero warm
# retraces) at every scale.  The 1M tail drops the two shapes whose
# information content doesn't change with scale (hierarchy depth and
# the adversarial no-op are fully exercised at 100k).
SCALE_GRID = [
    (10_000, ("sensor", "skewed", "hierarchy", "reified", "adversarial")),
    (100_000, ("sensor", "skewed", "hierarchy", "reified", "adversarial")),
    (1_000_000, ("sensor", "skewed", "reified")),
]
SCALE_SMOKE = [(10_000, ("sensor", "skewed"))]

# (devices x graph size) shard matrix: every cell partitions the sensor
# graph into `devices` shards, detects shard-local (fork-parallel on
# multi-device cells) against an in-process replicated baseline, and
# fans the star workload out through the ShardedQueryEngine.  The
# subprocess gets a forced N-device jax host platform so the cross-
# shard AMI collective runs over a real mesh.
SHARD_GRID = [(d, n) for n in (100_000, 1_000_000) for d in (1, 2, 4, 8)]
SHARD_SMOKE = [(2, 100_000)]


def _check_chip_free() -> None:
    """Refuse to start a jax child while this process holds a chip.

    An accelerator belongs to one process at a time: a child started
    after the parent initialised a TPU backend fails or hangs."""
    if "jax" not in sys.modules:
        return
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"this process holds the {jax.default_backend()} backend; "
            "run device cells and child cells in separate invocations")


def _run_scale_cell(shape: str, n: int, tier: str, *,
                    twin: int = 0, timeout: int = 900) -> dict:
    _check_chip_free()
    backend = "device" if shape == "sensor" else "host"
    cmd = [sys.executable, "-m", "benchmarks.scale_cell",
           "--shape", shape, "--n", str(n), "--tier", tier,
           "--backend", backend]
    if twin:
        cmd += ["--twin", str(twin)]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    r = subprocess.run(cmd, capture_output=True, text=True,
                       timeout=timeout, env=env)
    if r.returncode != 0:
        raise RuntimeError(
            f"scale cell {shape}@{n}/{tier} failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _run_shard_cell(devices: int, n: int, *, timeout: int = 1200) -> dict:
    _check_chip_free()
    cmd = [sys.executable, "-m", "benchmarks.shard_cell",
           "--devices", str(devices), "--n", str(n)]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    r = subprocess.run(cmd, capture_output=True, text=True,
                       timeout=timeout, env=env)
    if r.returncode != 0:
        raise RuntimeError(
            f"shard cell {devices}dev@{n} failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def shard_matrix(grid=None) -> dict:
    """The (devices x graph size) shard matrix: detect + query wall-
    clock, per-shard resident bytes, and cross-shard traffic per cell,
    each cell in its own subprocess with a forced `devices`-device host
    platform.  Digest parity (sharded == replicated, per cell AND
    across device counts at the same scale) is asserted here at bench
    time; the committed numbers are re-gated by
    ``benchmarks.check_snapshot``."""
    cells = []
    digests: dict[int, str] = {}
    for devices, n in (grid or SHARD_GRID):
        c = _run_shard_cell(devices, n)
        assert c["detect_parity"], (devices, n, "sharded digest != "
                                    "replicated digest")
        assert c["query_parity"], (devices, n, "sharded query digest != "
                                   "replicated query digest")
        ref = digests.setdefault(n, c["detect_digest"])
        assert c["detect_digest"] == ref, \
            (devices, n, "digest moved across device counts")
        cells.append(c)
        frac = c["max_shard_resident_bytes"] / max(
            c["repl_resident_bytes"], 1)
        print(f"shard d={devices} n={n:>9,} "
              f"detect {c['detect_ms']:8.1f} ms "
              f"(crit {c['detect_critical_path_ms']:7.1f} ms)  "
              f"query warm {c['query_warm_ms']:7.1f} ms  "
              f"shard bytes {frac:.0%} of repl  "
              f"xfer {c['traffic']['detect_bytes'] + c['traffic']['query_bytes']:>9,} B  "
              f"parity ok")
    return {"cells": cells}


def shard_smoke() -> None:
    """CI smoke: the smallest multi-device shard cell, live, with the
    shard gates asserted in-process (digest parity both ways, zero warm
    retraces, a real collective over the forced 2-device mesh)."""
    res = shard_matrix(grid=SHARD_SMOKE)
    for c in res["cells"]:
        assert c["trace_count_warm"] == 0, c
        assert c["devices"] == 1 or c["traffic"]["collective_calls"] > 0, \
            "multi-device cell never ran the cross-shard collective"
        assert c["max_shard_resident_bytes"] < c["repl_resident_bytes"], \
            "a shard holds no fewer bytes than the replicated graph"
    print(f"shard-smoke OK ({len(res['cells'])} cells)")


def scale_matrix(grid=None) -> dict:
    """The (scale x shape) substrate grid: each cell = one workload
    shape at one scale, measured on the plain AND compressed tiers in
    separate subprocesses.  The plain cell also runs the per-cell
    no-recompaction-twin soak (edge advantage of online re-detection).
    Cross-tier digest parity is asserted here at bench time; the
    committed numbers are re-gated by ``benchmarks.check_snapshot``."""
    cells = []
    for n, shapes in (grid or SCALE_GRID):
        for shape in shapes:
            plain = _run_scale_cell(shape, n, "plain", twin=3)
            comp = _run_scale_cell(shape, n, "compressed")
            assert comp["detect_digest"] == plain["detect_digest"], \
                (shape, n, "detect digest diverged across tiers")
            assert comp["query_digest"] == plain["query_digest"], \
                (shape, n, "query digest diverged across tiers")
            ratio = comp["substrate_bytes"] / max(plain["substrate_bytes"],
                                                  1)
            for c in (plain, comp):
                c["compression_ratio"] = round(ratio, 4)
                cells.append(c)
            print(f"scale {shape:12s} n={n:>9,} "
                  f"B/triple {plain['bytes_per_triple']:6.1f} -> "
                  f"{comp['bytes_per_triple']:5.1f} ({ratio:.1%})  "
                  f"detect warm {plain['detect_warm_ms']:8.1f} / "
                  f"{comp['detect_warm_ms']:8.1f} ms  "
                  f"rss {plain['rss_peak_kb'] // 1024:4d} / "
                  f"{comp['rss_peak_kb'] // 1024:4d} MB  "
                  f"twin+{plain.get('twin', {}).get('edge_advantage', 0)}")
    return {"cells": cells}


def snapshot(fast: bool = True, scale: str | None = None,
             shard: str | None = None) -> dict:
    """FSP perf snapshot on the synthetic sensor graph.

    Each detector x backend cell runs TWICE: the cold pass pays jit
    tracing for the shape-bucketed sweep (one trace per power-of-two
    bucket -- recorded as ``trace_count_cold``), the warm pass must be
    pure cache hits (``trace_count_warm`` is asserted 0 for the jax
    backends by ``benchmarks.check_snapshot``).  Trace/exec counters
    reset between cells, so every count is per-cell (the jit cache
    itself is NOT dropped: later cells legitimately reuse earlier
    buckets); ``lowerings_per_descent`` must be exactly 1 on the
    batched paths.  Written to BENCH_fsp.json so the bench trajectory is
    tracked in CI."""
    from repro.api import Compactor
    from repro.core import sweep as core_sweep
    from repro.data.synthetic import SensorGraphSpec, generate

    n_obs = 800 if fast else 4_000
    store = generate(SensorGraphSpec(n_observations=n_obs, seed=42))
    cells = []
    reference = None
    bucket_shapes: dict[tuple, int] = {}

    def _lpd(lowerings: int, descents: int) -> float:
        return round(lowerings / descents, 4) if descents else 0.0

    for det, be in SNAPSHOT_CELLS:
        comp = Compactor(detector=det, backend=be)
        core_sweep.reset_trace_stats()     # per-cell counters, shared cache
        t0 = time.perf_counter()
        rep = comp.run(store)
        cold_ms = (time.perf_counter() - t0) * 1e3
        cold_detect = sum(d.exec_time_ms for d in rep.detections.values())
        traces_cold = core_sweep.trace_count()
        exec_cold = dict(core_sweep.EXEC_STATS)
        t0 = time.perf_counter()
        rep_warm = comp.run(store)
        warm_ms = (time.perf_counter() - t0) * 1e3
        warm_detect = sum(d.exec_time_ms
                          for d in rep_warm.detections.values())
        traces_warm = core_sweep.trace_count() - traces_cold
        warm_lowerings = core_sweep.EXEC_STATS["lowerings"] \
            - exec_cold["lowerings"]
        warm_descents = core_sweep.EXEC_STATS["descents"] \
            - exec_cold["descents"]
        for k, v in core_sweep.TRACE_COUNTS.items():
            bucket_shapes[k] = bucket_shapes.get(k, 0) + v
        dets = rep.detections
        cell = {
            "detector": det, "backend": be,
            "exec_time_ms": round(cold_ms, 2),
            "exec_time_ms_warm": round(warm_ms, 2),
            "detect_time_ms": round(cold_detect, 2),
            "detect_time_ms_warm": round(warm_detect, 2),
            "trace_count_cold": traces_cold,
            "trace_count_warm": traces_warm,
            "lowerings_per_descent": _lpd(exec_cold["lowerings"],
                                          exec_cold["descents"]),
            "lowerings_per_descent_warm": _lpd(warm_lowerings,
                                               warm_descents),
            "evaluations": int(sum(d.evaluations for d in dets.values())),
            "n_classes": len(rep.plan),
            "edges": {store.dict.term(c): d.edges for c, d in dets.items()},
            "pct_savings_triples": round(rep.pct_savings_triples, 2),
        }
        cells.append(cell)
        # every cell (and both passes) must compact to the identical graph
        if reference is None:
            reference = (cell["edges"], rep.n_triples_after)
        assert (cell["edges"], rep.n_triples_after) == reference, \
            (det, be, cell["edges"], reference)
        assert rep_warm.n_triples_after == rep.n_triples_after, (det, be)
    out = {
        "graph": {"n_observations": n_obs, "n_triples": store.n_triples,
                  "n_nodes": store.n_nodes, "seed": 42},
        "bucket_shapes": {
            "/".join(str(x) for x in k): v
            for k, v in sorted(bucket_shapes.items())},
        "cells": cells,
        "query": query_matrix(fast=fast),
        "bgp": bgp_matrix(fast=fast),
        "drift": drift_matrix(fast=fast),
        "recovery": recovery_matrix(fast=fast),
    }
    # the scale and shard grids are minutes of subprocesses: refresh
    # only when asked ("full"), otherwise carry the committed sections
    # forward so `--snapshot` (CI bench-smoke) keeps gating them
    if scale == "full":
        out["scale"] = scale_matrix()
    if shard == "full":
        out["shard_matrix"] = shard_matrix()
    if scale != "full" or shard != "full":
        try:
            with open(SNAPSHOT_PATH) as f:
                prev = json.load(f)
        except (OSError, ValueError):
            prev = {}
        for key, fresh in (("scale", scale), ("shard_matrix", shard)):
            if fresh != "full" and key in prev:
                out[key] = prev[key]
    with open(SNAPSHOT_PATH, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(f"\n== BENCH_fsp snapshot ({os.path.abspath(SNAPSHOT_PATH)}) ==")
    for c in cells:
        print(f"{c['detector']:6s} x {c['backend']:8s} "
              f"cold {c['exec_time_ms']:9.1f} ms  "
              f"warm {c['exec_time_ms_warm']:8.1f} ms  "
              f"traces={c['trace_count_cold']}/{c['trace_count_warm']}  "
              f"low/desc={c['lowerings_per_descent_warm']:.1f}  "
              f"evals={c['evaluations']:<6d} "
              f"savings={c['pct_savings_triples']:.2f}%")
    return out


def recovery_matrix(fast: bool = True) -> dict:
    """Crash-point recovery sweep: durability as a gated number.

    A durable service (WAL + sync checkpoints every 3 applies) ingests
    a deterministic drift-heavy workload -- typed complete entities
    with novel object tuples, so re-detection genuinely runs -- while a
    seeded raise-mode :class:`~repro.dist.fault.FaultPlan` crashes it
    at ONE injection site.  The driver then :func:`~repro.online.recover`\\ s
    from disk and resubmits the interrupted batch (idempotent: RDF
    set semantics) and the run continues.  Every site x occurrence
    cell must (a) actually crash and (b) finish digest-identical to an
    uninterrupted plain-service reference over the same term-level
    batches -- zero lost or duplicated writes.  Per-cell recovery
    costs (checkpoint bytes, WAL replay ms, batches/mints replayed)
    are recorded; ``benchmarks.check_snapshot`` gates all of it."""
    import shutil
    import tempfile

    import numpy as np

    from repro.data.synthetic import SensorGraphSpec, generate
    from repro.dist.fault import SITES, FaultPlan, InjectedFault
    from repro.online import OnlineCompactionService, recover

    def build_store():
        return generate(SensorGraphSpec(n_observations=60, seed=5))

    def batches(store, n):
        """Deterministic term-level batches: complete typed entities
        with pairwise-novel object tuples (support-1 surrogates feed
        the drift tracker), every third batch deleting an earlier
        insert."""
        term = store.dict.term
        cid = int(store.classes()[0])
        props = np.asarray(store.class_properties(cid))
        cterm, tterm = term(cid), term(store.TYPE)
        pterms = [term(int(p)) for p in props]
        out = []
        for i in range(n):
            ins = []
            for j in range(3):
                s = f"e:n/b{i}/{j}"
                ins.append((s, tterm, cterm))
                ins += [(s, p, f"o:novel/b{i}/{j}/{k}")
                        for k, p in enumerate(pterms)]
            dels = [f"e:n/b{i - 2}/0"] if i % 3 == 2 else None
            out.append((ins, dels))
        return out

    kw = dict(detector="gfsp", backend="host", raw_residue_threshold=4,
              support_drift_threshold=3, retry_sleep=lambda _: None)
    n_batches = 10 if fast else 20
    seq = batches(build_store(), n_batches)

    ref = OnlineCompactionService(build_store(), **kw)
    for ins, dels in seq:
        ref.submit(inserts=ins, delete_entities=dels)
        ref.drain()
    ref_digest = ref.snapshot.digest()

    cells = []
    for site in SITES:
        for occ in (0, 1):
            root = tempfile.mkdtemp(prefix="fsp_recovery_")
            svc = OnlineCompactionService.durable(
                root, build_store(),
                fault_plan=FaultPlan(site, occurrence=occ),
                checkpoint_every=3, checkpoint_async=False, **kw)
            crashed, recoveries = False, 0
            for ins, dels in seq:
                for _ in range(2):
                    try:
                        svc.submit(inserts=ins, delete_entities=dels)
                        svc.drain()
                        break
                    except InjectedFault:
                        crashed = True
                        recoveries += 1
                        svc = recover(root, **kw)
                else:
                    raise AssertionError(f"{site} kept crashing")
            svc.close()
            rec = svc.last_recovery
            cells.append({
                "site": site, "occurrence": occ,
                "crashed": crashed,
                "parity": svc.snapshot.digest() == ref_digest,
                "drained": svc.queue.depth == 0,
                "n_recoveries": recoveries,
                "checkpoint_bytes": rec.checkpoint_bytes if rec else 0,
                "replay_ms": round(rec.replay_ms, 3) if rec else 0.0,
                "batches_replayed": rec.batches_pending if rec else 0,
                "mints_replayed": rec.mints_replayed if rec else 0,
            })
            shutil.rmtree(root, ignore_errors=True)
            c = cells[-1]
            print(f"recovery {site:18s} occ={occ} "
                  f"crashed={c['crashed']} parity={c['parity']} "
                  f"ckpt={c['checkpoint_bytes']}B "
                  f"replay={c['replay_ms']:.1f}ms "
                  f"batches={c['batches_replayed']}")
    return {"n_batches": n_batches, "ref_digest": ref_digest,
            "sites": list(SITES), "cells": cells}


def drift_matrix(fast: bool = True) -> dict:
    """Online-compaction soak: the per-batch drift matrix (recompaction
    latency, queue depth, dirty-class count, edge counts vs the
    no-recompaction twin) plus the service's metrics-channel summaries.
    Recorded with ``assert_gates=False`` so a gate regression shows up
    as a ``check_snapshot`` FAIL over the committed numbers rather than
    an opaque bench crash."""
    from repro.launch.serve import serve_online

    return serve_online(20 if fast else 40, assert_gates=False)


def query_matrix(fast: bool = True) -> dict:
    """Star-query latency matrix: raw vs factorized x host/device.

    The paper's claim is that frequent star patterns hurt *query
    processing*, not only size -- this makes it a gated number.  A
    frequent-pattern-heavy sensor graph (AM >> AMI) is compacted once;
    the workload is every molecule of each class looked up as a
    class-constrained all-ground star query (the shape the compaction
    targets), plus a variable-arm workload recorded for transparency
    (selective lookups favor G', whole-class scans favor the raw
    slices).  Every cell must produce identical binding sets (digest);
    ``factorized x host`` must be no slower than ``raw x host`` on the
    frequent-pattern-heavy class, and the batched device path must not
    retrace warm -- all gated in ``benchmarks.check_snapshot``.
    """
    from repro.api import Compactor
    from repro.core import sweep as core_sweep
    from repro.data.synthetic import SensorGraphSpec, generate
    from repro.query import QueryEngine, StarQuery

    n_obs = 4_000 if fast else 20_000
    store = generate(SensorGraphSpec(n_observations=n_obs, seed=42))
    comp = Compactor(detector="gfsp", backend="host")
    comp.run(store)
    fg = comp.fgraph
    eng = QueryEngine(fg)
    eng.raw_store         # build the expanded baseline outside the timers

    # frequent-pattern-heavy class = largest AM / AMI ratio
    def _ratio(cid):
        t = fg.tables[cid]
        return fg.support(cid).sum() / max(t.n_molecules, 1)
    heavy = max(fg.tables, key=_ratio)

    lookups: list[StarQuery] = []
    for cid, t in sorted(fg.tables.items()):
        for row in t.objects:
            lookups.append(StarQuery(
                arms=tuple((p, int(o)) for p, o in zip(t.props, row)),
                class_id=cid))
    heavy_lookups = [q for q in lookups if q.class_id == heavy]
    var_queries = [
        StarQuery(arms=((t.props[0], int(row[0])), (t.props[-1], None)),
                  class_id=cid)
        for cid, t in sorted(fg.tables.items()) for row in t.objects[:32]]

    def _digest(bindings) -> str:
        h = hashlib.sha1()
        for b in bindings:
            h.update(b.canonical().tobytes())
        return h.hexdigest()[:16]

    def _cell(workload, strategy, backend):
        core_sweep.reset_trace_stats()
        t0 = time.perf_counter()
        res = eng.query_batch(workload, strategy=strategy, backend=backend)
        cold = (time.perf_counter() - t0) * 1e3
        traces_cold = core_sweep.trace_count()
        t0 = time.perf_counter()
        res = eng.query_batch(workload, strategy=strategy, backend=backend)
        warm = (time.perf_counter() - t0) * 1e3
        return res, {
            "strategy": strategy, "backend": backend,
            "exec_time_ms": round(cold, 3),
            "exec_time_ms_warm": round(warm, 3),
            "trace_count_cold": traces_cold,
            "trace_count_warm": core_sweep.trace_count() - traces_cold,
            "n_queries": len(workload),
            "n_rows": int(sum(b.n_rows for b in res)),
            "digest": _digest(res),
        }

    out: dict = {
        "graph": {"n_observations": n_obs, "n_triples": store.n_triples,
                  "seed": 42},
        "heavy_class": store.dict.term(heavy),
        "workloads": {},
    }
    for wname, workload in (("lookup", lookups),
                            ("lookup_heavy", heavy_lookups),
                            ("var_arm", var_queries)):
        cells = []
        for strategy, backend in (("raw", "host"), ("factorized", "host"),
                                  ("factorized", "device")):
            _, cell = _cell(workload, strategy, backend)
            cells.append(cell)
        out["workloads"][wname] = cells
        base = cells[0]["exec_time_ms_warm"]
        for c in cells:
            tag = f"{c['strategy']}x{c['backend']}"
            print(f"query {wname:13s} {tag:18s} "
                  f"cold {c['exec_time_ms']:8.1f} ms  "
                  f"warm {c['exec_time_ms_warm']:8.1f} ms  "
                  f"({base / max(c['exec_time_ms_warm'], 1e-9):4.2f}x raw) "
                  f"rows={c['n_rows']} digest={c['digest']}")
    return out


def bgp_matrix(fast: bool = True) -> dict:
    """Multi-star BGP matrix: planner vs fixed strategies x host/device.

    Six workloads over the sensor graph WITH ssn:Sensor metadata stars
    (so cross-star joins have a factorizable class on both sides):
    molecule ``lookup``s, ``var_arm`` scans, pushed-down value
    ``filter``s (plus a post-hoc cell over the identical queries),
    molecule-to-molecule ``2star`` joins, ``3star`` chains, and a
    ``mixed`` bag spanning all shapes.  Gated invariants
    (``benchmarks.check_snapshot``): every cell of a workload returns
    the identical binding-set digest; the batched device join path does
    not retrace warm; the factorized ``2star`` intermediate is bounded
    by molecule counts (AMI x AMI) strictly below raw's entity-level
    frontier; pushed-down filtering beats post-hoc; the cost-based
    planner's warm latency on ``mixed`` is no worse than EITHER fixed
    strategy -- the per-star choice must pay for itself -- and on
    ``filter``/``3star`` stays within 15% of the best fixed strategy
    (the mixed-slot re-pricing closing ROADMAP item 1').  The matrix
    also re-runs the cost-model calibration
    (``repro.query.bgp.calibrate``) and records the fitted constants
    next to the committed defaults so drift is visible per commit.
    """
    from repro.api import Compactor
    from repro.core import sweep as core_sweep
    from repro.data.synthetic import (MEASUREMENT, OBSERVATION, P_MODEL,
                                      P_PROCEDURE, P_RESULT, P_VALUE,
                                      SENSOR, SensorGraphSpec, generate)
    from repro.query import BGPQuery, Filter, QueryEngine, StarPattern

    n_obs = 4_000 if fast else 20_000
    store = generate(SensorGraphSpec(n_observations=n_obs, seed=42,
                                     include_sensor_metadata=True))
    comp = Compactor(detector="gfsp", backend="host")
    comp.run(store)
    fg = comp.fgraph
    eng = QueryEngine(fg)
    eng.raw_store         # build the expanded baseline outside the timers
    d = store.dict
    obs, meas, sen = (d.lookup(t) for t in (OBSERVATION, MEASUREMENT,
                                            SENSOR))
    p_proc, p_model, p_res, p_val = (
        d.lookup(t) for t in (P_PROCEDURE, P_MODEL, P_RESULT, P_VALUE))

    lookups = [
        BGPQuery(stars=(StarPattern(
            "?s", tuple((int(p), int(o)) for p, o in zip(t.props, row)),
            class_id=cid),))
        for cid, t in sorted(fg.tables.items()) for row in t.objects[:48]]
    var_arm = [
        BGPQuery(stars=(StarPattern(
            "?s", ((int(t.props[0]), int(row[0])),
                   (int(t.props[-1]), "?v")), class_id=cid),))
        for cid, t in sorted(fg.tables.items()) for row in t.objects[:16]]
    # raw's home turf: var arms over the residual (off-SP) property --
    # distinct var labels keep the queries (and their cache entries)
    # separate while probing the same shape
    residual = [BGPQuery(stars=(StarPattern(
        f"?o{i}", ((p_res, f"?m{i}"),), class_id=obs),))
        for i in range(3)]
    joins2 = [
        BGPQuery(stars=(
            StarPattern("?o", ((p_proc, "?s"),), class_id=obs),
            StarPattern("?s", ((p_model, d.lookup(f"model/{m}")),),
                        class_id=sen)))
        for m in range(3)]
    chains3 = [
        BGPQuery(stars=(
            StarPattern("?o", ((p_proc, "?s"), (p_res, "?m")),
                        class_id=obs),
            StarPattern("?s", ((p_model, d.lookup(f"model/{m}")),),
                        class_id=sen),
            StarPattern("?m", ((p_val, "?v"),), class_id=meas)))
        for m in range(3)]
    # pushed-down value filters riding the 3-star chain: the pushed form
    # prunes measurement molecules BEFORE the joins, post-hoc carries
    # the full join frontier to the end
    filtered = [
        BGPQuery(stars=q.stars,
                 filters=(Filter("?v", op, d.lookup(f"val/{k}")),))
        for q in chains3 for op in ("<", ">=") for k in (2, 6)]
    # every shape is represented; weights follow the serving mix the
    # README describes (lookup-dominated with a steady join/scan tail)
    mixed = (lookups[:24] + var_arm[:8] + residual + joins2 * 2
             + filtered[:2])

    def _digest(results) -> str:
        h = hashlib.sha1()
        for b in results:
            h.update(b.canonical().tobytes())
        return h.hexdigest()[:16]

    def _cell(workload, label, strategy, backend, posthoc=False):
        def run_once():
            out, mi = [], 0
            for q in workload:
                b, stq = eng.query_bgp(q, strategy=strategy,
                                       backend=backend,
                                       posthoc_filters=posthoc,
                                       return_stats=True)
                out.append(b)
                mi = max(mi, stq["max_intermediate"])
            return out, mi
        core_sweep.reset_trace_stats()
        t0 = time.perf_counter()
        res, mi = run_once()
        cold = (time.perf_counter() - t0) * 1e3
        traces_cold = core_sweep.trace_count()
        # best-of-3 warm: the planner gates run at 1.15x slack on ~10 ms
        # cells, which a single sample cannot resolve above host jitter
        warm = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            res, mi = run_once()
            warm = min(warm, (time.perf_counter() - t0) * 1e3)
        return {
            "strategy": label, "backend": backend,
            "exec_time_ms": round(cold, 3),
            "exec_time_ms_warm": round(warm, 3),
            "trace_count_cold": traces_cold,
            "trace_count_warm": core_sweep.trace_count() - traces_cold,
            "n_queries": len(workload),
            "n_rows": int(sum(b.n_rows for b in res)),
            "max_intermediate": int(mi),
            "digest": _digest(res),
        }

    out: dict = {
        "graph": {"n_observations": n_obs, "n_triples": store.n_triples,
                  "seed": 42, "sensor_metadata": True},
        "workloads": {},
    }
    for wname, workload in (("lookup", lookups), ("var_arm", var_arm),
                            ("filter", filtered), ("2star", joins2),
                            ("3star", chains3), ("mixed", mixed)):
        cells = [
            _cell(workload, "planner", "auto", "host"),
            _cell(workload, "raw", "raw", "host"),
            _cell(workload, "factorized", "factorized", "host"),
            _cell(workload, "factorized", "factorized", "device"),
        ]
        if wname == "filter":       # identical queries, filters applied last
            cells.append(_cell(workload, "posthoc", "factorized", "host",
                               posthoc=True))
        out["workloads"][wname] = cells
        for c in cells:
            tag = f"{c['strategy']}x{c['backend']}"
            print(f"bgp {wname:8s} {tag:18s} "
                  f"cold {c['exec_time_ms']:8.1f} ms  "
                  f"warm {c['exec_time_ms_warm']:8.1f} ms  "
                  f"maxint={c['max_intermediate']:<7d} "
                  f"rows={c['n_rows']} digest={c['digest']}")
    from repro.query.bgp import calibration_report
    out["calibration"] = calibration_report(eng, {
        "lookup": lookups, "var_arm": var_arm, "filter": filtered,
        "2star": joins2, "3star": chains3, "residual": residual})
    print(f"bgp calibration n={out['calibration']['n_samples']} "
          f"rel_l1={out['calibration']['rel_l1_error']} "
          f"fitted={out['calibration']['fitted']}")
    return out


def scale_smoke() -> None:
    """CI smoke: the two smallest grid cells, live, with the scale
    gates asserted in-process (bytes-per-triple halved, digest parity
    across tiers, zero warm retraces, bounded resident decodes)."""
    res = scale_matrix(grid=SCALE_SMOKE)
    by_key = {(c["shape"], c["n_triples"], c["tier"]): c
              for c in res["cells"]}
    for (shape, n, tier), c in by_key.items():
        if tier != "compressed":
            continue
        p = by_key[(shape, n, "plain")]
        assert c["substrate_bytes"] <= 0.5 * p["substrate_bytes"], \
            (shape, "compressed substrate must be <= half of plain")
        assert c["detect_digest"] == p["detect_digest"]
        assert c["query_digest"] == p["query_digest"]
        assert c["trace_count_warm"] == 0 and p["trace_count_warm"] == 0
        assert c["decode_peak_resident_bytes"] <= \
            0.35 * p["substrate_bytes"], \
            (shape, "streamed detection held too much decoded")
    print(f"scale-smoke OK ({len(by_key)} cells)")


def main() -> None:
    argv = sys.argv[1:]
    fast = "--fast" in argv
    if "--scale-smoke" in argv:
        scale_smoke()
        return
    if "--shard-smoke" in argv:
        shard_smoke()
        return
    if "--snapshot" in argv:
        snapshot(fast=True,
                 scale="full" if "--scale" in argv else None,
                 shard="full" if "--shard" in argv else None)
        return
    from . import (bench_formula, bench_fsp_efficiency, bench_kernels,
                   bench_nodes_edges, bench_repeats, bench_savings)
    t0 = time.time()
    bench_fsp_efficiency.run(fast)      # Table 3
    bench_formula.run(fast)             # Table 4
    bench_savings.run(fast)             # Table 5 + surrogate minting
    bench_repeats.run(fast)             # Figure 8
    bench_nodes_edges.run(fast)         # Figure 9
    bench_kernels.run(fast)             # kernels
    snapshot(fast=fast)                 # detector x backend matrix
    if not fast:
        # separate process: needs 512 host devices before jax init
        _check_chip_free()
        r = subprocess.run([sys.executable, "-m",
                            "benchmarks.bench_fsp_scale"],
                           capture_output=True, text=True, timeout=1800)
        if r.returncode != 0:
            sys.exit(f"fsp_scale FAILED:\n{r.stderr[-2000:]}")
        print(r.stdout[-2000:])
    print(f"\nall benchmarks done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
