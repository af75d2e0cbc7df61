"""One (devices x graph size) cell of the shard matrix, in its OWN process.

The parent (``benchmarks.run --shard``) launches this module once per
cell with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in the
environment, so an N-device cell sees a real N-device jax host platform
for the cross-shard collective, and ``ru_maxrss`` stays attributable.

What one cell does:

1. generate the sensor workload at the target scale;
2. replicated baseline: one ``CompactionPlanner`` run over the whole
   graph (host detection -- the same engine the shard workers run),
   recording detect wall-clock, the graph digest, and resident bytes;
3. partition into ``devices`` shards (``ShardPlan`` balanced on Def. 4.8
   edge counts, frequent classes chunk-split) and detect shard-local --
   fork-parallel one worker per shard on multi-device cells.  Detection
   runs BEFORE any jax usage in this process, so forked workers never
   inherit a jax runtime;
4. chunk-split classes re-count their global AMI through the
   ``ami_bucketed`` collective over the device mesh (the only detection
   step where signatures cross shards; bytes land in ``traffic``);
5. digest parity: sharded == replicated (Def. 4.10 -- the compact form
   differs per partition, the graph it denotes cannot);
6. the star-query workload runs on the replicated engine and through
   the ``ShardedQueryEngine`` fan-out (device molecule-match backend),
   cold + warm, with per-cell trace counts -- warm must add zero;
7. print a one-line JSON report on the last stdout line.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import resource
import sys
import time

import numpy as np


def _rss_kb() -> int:
    return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)


def _fgraph_nbytes(fg) -> int:
    b = int(fg.store.substrate_nbytes(include_dict=False))
    for t in fg.tables.values():
        b += int(t.surrogates.nbytes) + int(t.objects.nbytes)
    return b


def _build_queries(fg, max_lookups: int = 24, max_var: int = 8):
    from repro.query import StarQuery
    queries = []
    for cid, t in sorted(fg.tables.items()):
        for row in t.objects[:max_lookups]:
            queries.append(StarQuery(
                arms=tuple((int(p), int(o))
                           for p, o in zip(t.props, row)),
                class_id=cid))
        for row in t.objects[:max_var]:
            queries.append(StarQuery(
                arms=((int(t.props[0]), int(row[0])),
                      (int(t.props[-1]), None)),
                class_id=cid))
    return queries


def _digest(bindings) -> str:
    h = hashlib.sha1()
    for b in bindings:
        h.update(np.ascontiguousarray(b.canonical()).tobytes())
    return h.hexdigest()[:16]


def run_cell(devices: int, n_triples: int, seed: int) -> dict:
    from repro.api import CompactionPlanner
    from repro.data.synthetic import WorkloadSpec, generate_workload

    t0 = time.perf_counter()
    store = generate_workload(WorkloadSpec(
        shape="sensor", n_triples=n_triples, seed=seed))
    gen_ms = (time.perf_counter() - t0) * 1e3
    n = store.n_triples

    # replicated baseline: detect over the whole graph in this process
    t0 = time.perf_counter()
    snap, rep = CompactionPlanner("gfsp", "host").run(store.copy())
    detect_repl_ms = (time.perf_counter() - t0) * 1e3
    repl_digest = snap.digest()
    repl_bytes = _fgraph_nbytes(snap.fgraph)

    # partition + shard-local detection (fork-parallel when multi-shard;
    # MUST precede any jax import/use so workers fork a jax-free parent)
    from repro.dist.graph import ShardedFactorizedGraph, ShardedQueryEngine
    t0 = time.perf_counter()
    sharded = ShardedFactorizedGraph.partition(store, devices, oversplit=4)
    partition_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    report = sharded.detect_all(backend="host", parallel=devices > 1)
    detect_ms = (time.perf_counter() - t0) * 1e3
    detect_parity = sharded.digest() == repl_digest
    shard_bytes = sharded.shard_nbytes()
    # per-worker detect CPU times: their max is the parallel critical
    # path -- the wall-clock the fork fan-out reaches once every worker
    # has its own core.  Raw wall-clock cannot parallelize on fewer
    # cores than shards, so the matrix records both and the gate arms
    # the wall comparison only where cpu_count covers the shards.
    shard_detect_ms = [report["shards"][sid]["detect_ms"]
                       for sid in sorted(report["shards"])]

    # cross-shard collective AMI over the forced host-device mesh
    collective_ami: dict[str, int] = {}
    if devices > 1:
        import jax

        from repro.launch.mesh import make_mesh
        assert len(jax.devices()) >= devices, \
            (len(jax.devices()), devices)
        mesh = make_mesh((devices,), ("data",))
        for cid in sharded.plan.split_classes:
            got = sharded.cross_shard_ami(cid, mesh=mesh)
            want = report["split_class_ami"][int(cid)]
            assert got == want, (cid, got, want)
            collective_ami[store.dict.term(cid)] = got

    # star-query fan-out: replicated vs sharded, device molecule match
    from repro.core import sweep as core_sweep
    from repro.query import QueryEngine
    queries = _build_queries(snap.fgraph)
    eng_repl = QueryEngine(snap.fgraph)
    res = eng_repl.query_batch(queries, backend="device")
    t0 = time.perf_counter()
    res = eng_repl.query_batch(queries, backend="device")
    query_repl_ms = (time.perf_counter() - t0) * 1e3
    repl_qdigest = _digest(res)

    eng = ShardedQueryEngine(sharded)
    core_sweep.reset_trace_stats()
    t0 = time.perf_counter()
    res = eng.query_batch(queries, backend="device")
    query_cold_ms = (time.perf_counter() - t0) * 1e3
    traces_cold = core_sweep.trace_count()
    t0 = time.perf_counter()
    res = eng.query_batch(queries, backend="device")
    query_warm_ms = (time.perf_counter() - t0) * 1e3
    traces_warm = core_sweep.trace_count() - traces_cold

    return {
        "devices": int(devices), "n_triples": int(n), "seed": seed,
        "gen_ms": round(gen_ms, 1),
        "partition_ms": round(partition_ms, 1),
        "detect_repl_ms": round(detect_repl_ms, 1),
        "detect_ms": round(detect_ms, 1),
        "shard_detect_ms": shard_detect_ms,
        "detect_critical_path_ms": round(max(shard_detect_ms), 1),
        "cpu_count": int(os.cpu_count() or 1),
        "detect_parity": bool(detect_parity),
        "detect_digest": repl_digest,
        "pct_savings_repl": round(float(rep.pct_savings_triples), 2),
        "split_classes": len(sharded.plan.split_classes),
        "collective_ami": collective_ami,
        "shard_weights": [int(w) for w in sharded.plan.shard_weights],
        "repl_resident_bytes": int(repl_bytes),
        "shard_resident_bytes": [int(b) for b in shard_bytes],
        "max_shard_resident_bytes": int(max(shard_bytes)),
        "n_queries": len(queries),
        "query_rows": int(sum(b.n_rows for b in res)),
        "query_repl_ms": round(query_repl_ms, 2),
        "query_cold_ms": round(query_cold_ms, 2),
        "query_warm_ms": round(query_warm_ms, 2),
        "trace_count_cold": int(traces_cold),
        "trace_count_warm": int(traces_warm),
        "query_parity": _digest(res) == repl_qdigest,
        "query_digest": repl_qdigest,
        "traffic": {k: int(v) for k, v in sharded.traffic.items()},
        "rss_peak_kb": _rss_kb(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, required=True)
    ap.add_argument("--n", type=int, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    cell = run_cell(args.devices, args.n, args.seed)
    sys.stdout.flush()
    print(json.dumps(cell))


if __name__ == "__main__":
    main()
