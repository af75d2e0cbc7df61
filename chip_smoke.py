"""Chip smoke test: the main path on one TPU chip at the paper's scale.

Drives detect -> factorize -> serve -> ingest through the public API
on the paper's largest sensor configuration (``configs/rdf_sensor.py``
``D1D2D3``: 200,000 observations, 1.8M triples), and checks each phase
against the host reference:

1. host reference: ``Compactor(detector="gfsp", backend="host")``;
2. device compaction: ``OnlineCompactionService(backend="device")``
   must choose the same factorization, keep the graph's digest, lower
   the bucketed sweep, and compile it with the Pallas kernels;
3. serving: waves of star queries and a few BGPs through
   ``GraphQueryService(backend="device")``, every answer equal to the
   raw evaluation (stars) or ``query/bgp/reference.py`` (BGPs) over the
   original store, none degraded to the raw fallback;
4. ingest: insert batches and a delete batch drained through the
   service, with at least one re-detection on the device, none failed,
   and the result equal to a from-scratch host compaction.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded detection on four chips

``--chips 4`` runs only the sharded detection (``backend="sharded"``
over a ``(4, 1)`` ``("data", "model")`` mesh) and the host reference it
is compared with.  The script exits non-zero, without a result line,
when JAX finds no TPU or any check fails.  The last line of a passing
run is ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# star requests per serving wave, and waves after the compiling one
WAVE_SIZE = 64
WARM_WAVES = 3


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def _timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


def _on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


# -- phases -----------------------------------------------------------------

def make_store(cfg):
    """The sensor graph of a ``configs.rdf_sensor`` configuration."""
    from repro.data.synthetic import SensorGraphSpec, generate
    return generate(SensorGraphSpec(
        n_observations=cfg.n_observations, n_sensors=cfg.n_sensors,
        n_timestamps=cfg.n_timestamps, n_values=cfg.n_values,
        zipf_a=cfg.zipf_a, seed=cfg.seed))


def host_reference(store):
    """gfsp on the host: the snapshot every device result must match."""
    from repro.api import Compactor
    comp = Compactor(detector="gfsp", backend="host")
    rep = comp.run(store)
    return comp.snapshot, rep


def _layout(snap) -> dict:
    """What a compaction decided: per class, its SP and molecule count."""
    return {int(cid): (tuple(int(p) for p in t.props), int(t.n_molecules))
            for cid, t in snap.fgraph.tables.items()}


def check_same_compaction(snap, ref, what: str) -> None:
    check(_layout(snap) == _layout(ref),
          f"{what}: factorization differs from the host reference")
    check(snap.n_triples == ref.n_triples,
          f"{what}: {snap.n_triples} triples, host {ref.n_triples}")
    check(snap.digest() == ref.digest(),
          f"{what}: digest differs from the host reference")


def _compile(fn, args):
    """(HLO text, temporary bytes) of ``fn`` compiled for ``args``."""
    compiled = fn.lower(*args).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def _sweep_args(n_b: int, k_b: int, c_b: int):
    import jax
    import jax.numpy as jnp
    i32 = jnp.int32
    scalar = jax.ShapeDtypeStruct((), i32, weak_type=True)
    return (jax.ShapeDtypeStruct((n_b, k_b), i32),
            jax.ShapeDtypeStruct((n_b,), jnp.bool_),
            jax.ShapeDtypeStruct((c_b, k_b), i32), scalar,
            jax.ShapeDtypeStruct((c_b,), i32), scalar)


def device_compaction(store, ref):
    """Compact through the online service on the device backend."""
    from repro.core import sweep
    from repro.online import OnlineCompactionService
    sweep.clear_compile_cache()
    svc = OnlineCompactionService(store, detector="gfsp", backend="device")
    check_same_compaction(svc.snapshot, ref, "device compaction")
    check(sweep.EXEC_STATS["lowerings"] > 0,
          "device compaction lowered no bucketed sweep")
    shapes = sorted(k[1:] for k in sweep.TRACE_COUNTS if k[0] == "sweep")
    check(bool(shapes), "no sweep bucket was traced")
    stats = {"lowerings": sweep.EXEC_STATS["lowerings"],
             "descents": sweep.EXEC_STATS["descents"],
             "buckets": shapes}
    text, stats["temp_bytes"] = _compile(sweep._bucket_sweep_fn(True),
                                         _sweep_args(*shapes[-1]))
    if _on_tpu():
        check("tpu_custom_call" in text,
              f"sweep {shapes[-1]} compiled without the Pallas kernels")
    return svc, stats


def _star_requests(snap, wave: int, n: int):
    """``n`` term-level star requests over the factorized classes, with
    the id-level queries that the reference evaluates: full molecule
    lookups, two ground arms plus a variable arm, and misses."""
    import numpy as np

    from repro.query import StarQuery
    from repro.serving import GraphQueryRequest
    term = snap.store.dict.term
    rng = np.random.default_rng(wave)
    tables = sorted(snap.fgraph.tables.items())
    out = []
    for i in range(n):
        cid, t = tables[i % len(tables)]
        row = t.objects[int(rng.integers(0, t.n_molecules))]
        props = [int(p) for p in t.props]
        kind = (i // len(tables)) % 3
        if kind == 0 or len(props) < 3:       # full molecule lookup
            arms = [(p, int(o)) for p, o in zip(props, row)]
        elif kind == 1:                       # ground, ground, variable
            arms = [(props[0], int(row[0])), (props[1], int(row[1])),
                    (props[-1], None)]
        else:                                 # miss: a foreign object
            arms = [(props[0], int(row[-1]))]
        q = StarQuery(arms=tuple(arms), class_id=int(cid))
        req = GraphQueryRequest(
            rid=len(out),
            arms=tuple((term(p), None if o is None else term(o))
                       for p, o in arms),
            class_term=term(int(cid)))
        out.append((req, q))
    return out


def _bgp_requests(snap, n: int):
    """Observation -> Measurement joins with a pushed-down value filter,
    at the term level and as id-level queries for the reference."""
    import numpy as np

    from repro.data.synthetic import (MEASUREMENT, OBSERVATION, P_RESULT,
                                      P_TIME, P_VALUE)
    from repro.query import BGPQuery, Filter, StarPattern
    from repro.serving import BGPQueryRequest
    d = snap.store.dict
    rng = np.random.default_rng(7)
    times = [t for t in (f"time/{int(x)}" for x in rng.permutation(50))
             if d.lookup(t) is not None][:n]
    out = []
    for rid, t in enumerate(times):
        limit = f"val/{2 + rid % 4}"
        req = BGPQueryRequest(
            rid=rid,
            stars=(("?o", ((P_TIME, t), (P_RESULT, "?m")), OBSERVATION),
                   ("?m", ((P_VALUE, "?v"),), MEASUREMENT)),
            filters=(("?v", "<", limit),))
        q = BGPQuery(
            stars=(StarPattern("?o", ((d.lookup(P_TIME), d.lookup(t)),
                                      (d.lookup(P_RESULT), "?m")),
                               class_id=d.lookup(OBSERVATION)),
                   StarPattern("?m", ((d.lookup(P_VALUE), "?v"),),
                               class_id=d.lookup(MEASUREMENT))),
            filters=(Filter("?v", "<", d.lookup(limit)),))
        out.append((req, q))
    return out


def _star_answer(resp) -> list:
    return sorted(zip(resp.subjects, resp.var_objects))


def _star_reference(store, q) -> list:
    from repro.query.star import eval_raw
    b = eval_raw(store, q)
    term = store.dict.term
    return sorted(zip([term(int(s)) for s in b.subjects],
                      [tuple(term(int(v)) for v in row)
                       for row in b.var_objects]))


def serve(svc, store, *, wave_size: int = WAVE_SIZE,
          warm_waves: int = WARM_WAVES, n_bgp: int = 4) -> dict:
    """Waves of star queries plus a few BGPs on the device backend,
    each answer checked against the original store."""
    from repro.query.batch import QUERY_EXEC
    from repro.query.bgp.reference import eval_bgp_reference
    from repro.serving import GraphQueryService
    qs = GraphQueryService(svc, backend="device")
    snap = svc.snapshot
    term = snap.store.dict.term
    lowerings0 = QUERY_EXEC["lowerings"]
    wave_s, n_rows = [], 0
    for wave in range(1 + warm_waves):
        reqs = _star_requests(snap, wave, wave_size)
        for req, _ in reqs:
            check(qs.submit(req), "star request shed at admission")
        out, dt = _timed(qs.run)
        wave_s.append(dt)
        for req, q in reqs:
            resp = out[req.rid]
            check(resp.status == "ok",
                  f"wave {wave} request {req.rid}: {resp.status}")
            check(_star_answer(resp) == _star_reference(store, q),
                  f"wave {wave} request {req.rid}: answer differs from "
                  "the raw evaluation")
            n_rows += resp.n_rows
    bgps = _bgp_requests(snap, n_bgp)
    check(len(bgps) == n_bgp, "too few sampling times for the BGPs")
    for req, _ in bgps:
        check(qs.submit(req), "BGP request shed at admission")
    out, bgp_s = _timed(qs.run)
    for req, q in bgps:
        resp = out[req.rid]
        check(resp.status == "ok", f"BGP {req.rid}: {resp.status}")
        ref = eval_bgp_reference(store, q)
        cols = [ref.columns.index(v) for v in resp.variables]
        want = sorted(tuple(term(int(r[c])) for c in cols)
                      for r in ref.rows)
        check(sorted(resp.rows) == want,
              f"BGP {req.rid}: answer differs from the reference")
        n_rows += resp.n_rows
    fallback = qs.metrics.channel("wave.raw_fallback").total
    check(fallback == 0, f"{fallback} requests fell back to raw")
    lowerings = QUERY_EXEC["lowerings"] - lowerings0
    check(lowerings > 0, "no query wave used the device match")
    return {"first_wave_s": wave_s[0], "warm_wave_s": wave_s[1:],
            "bgp_s": bgp_s, "rows": n_rows, "match_lowerings": lowerings,
            "requests": (1 + warm_waves) * wave_size + n_bgp}


def _ingest_batches(snap, store, n_batches: int):
    """Inserts into the widest factorized class: clones of existing
    entities plus a cohort whose SP column takes a fresh object each
    time (the raw residue that makes the class dirty)."""
    import numpy as np
    term = store.dict.term
    type_term = term(store.TYPE)
    cid, t = max(snap.fgraph.tables.items(),
                 key=lambda kv: (len(kv[1].props), int(kv[0])))
    cterm = term(int(cid))
    props = np.asarray(store.class_properties(cid))
    mat = store.object_matrix(cid, props)[1]
    pterms = [term(int(p)) for p in props]
    fresh_col = int(np.searchsorted(props, t.props[-1]))
    rng = np.random.default_rng(11)
    batches, clones, fresh = [], [], []
    for b in range(n_batches):
        ins = []
        for j in range(3):
            row = mat[int(rng.integers(0, mat.shape[0]))]
            s = f"e:smoke/{b}/clone{j}"
            ins.append((s, type_term, cterm))
            ins += [(s, p, term(int(o))) for p, o in zip(pterms, row)]
            clones.append(s)
        for j in range(4):
            s = f"e:smoke/{b}/fresh{j}"
            obj = f"o:smoke/{b}/{j}"
            ins.append((s, type_term, cterm))
            ins += [(s, p, obj if k == fresh_col else term(int(mat[0, k])))
                    for k, p in enumerate(pterms)]
            fresh.append((s, pterms[fresh_col], obj))
        batches.append(ins)
    return cterm, batches, clones, fresh


def ingest(svc, store, *, n_batches: int = 4) -> dict:
    """Insert batches and one delete batch, drained through the service;
    the inserted entities must then be served."""
    from repro.api import Compactor
    from repro.serving import GraphQueryRequest, GraphQueryService
    cterm, batches, clones, fresh = _ingest_batches(
        svc.snapshot, store, n_batches)
    for ins in batches:
        svc.submit(inserts=ins)
    deleted = [clones[0], fresh[0][0]]
    svc.submit(delete_entities=deleted)
    reps, drain_s = _timed(svc.drain)
    check(svc.queue.depth == 0, "ingest queue not drained")
    reds = [r.redetect for r in reps if r.redetect is not None]
    check(any(r.lowerings > 0 for r in reds),
          "no re-detection ran on the device")
    failures = svc.metrics.channel("redetect.failures").total
    check(failures == 0, f"{failures} re-detections failed")
    net = svc.snapshot.fgraph.expand()
    comp = Compactor(detector="gfsp", backend="host")
    comp.run(net)
    check(comp.snapshot.digest() == svc.snapshot.digest(),
          "ingested state != from-scratch host compaction of its net graph")
    # read the acknowledged writes back: each fresh entity is the only
    # one with its object; the deleted one must be gone
    qs = GraphQueryService(svc, backend="device")
    for rid, (s, p, o) in enumerate(fresh):
        qs.submit(GraphQueryRequest(rid=rid, arms=((p, o),),
                                    class_term=cterm))
    out = qs.run()
    for rid, (s, _, _) in enumerate(fresh):
        resp = out[rid]
        check(resp.status == "ok", f"ingest read-back {rid}: {resp.status}")
        want = [] if s in deleted else [s]
        check(resp.subjects == want,
              f"ingest read-back of {s}: got {resp.subjects}")
    return {"drain_s": drain_s, "batches": len(batches) + 1,
            "redetections": len(reds),
            "redetect_lowerings": sum(r.lowerings for r in reds),
            "triples_after": svc.snapshot.n_triples}


def sharded_compaction(store, ref, mesh) -> dict:
    """gfsp with rows sharded over ``mesh`` through the all-to-all."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.api import Compactor
    from repro.core import sweep
    sweep.clear_compile_cache()
    comp = Compactor(detector="gfsp", backend="sharded",
                     backend_opts={"mesh": mesh})
    check(comp.backend.mesh is mesh, "sharded backend lost its mesh")
    _, dt = _timed(comp.run, store)
    check_same_compaction(comp.snapshot, ref, "sharded compaction")
    shapes = sorted(k[1:] for k in sweep.TRACE_COUNTS if k[0] == "sharded")
    check(bool(shapes) and not any(k[0] == "sweep"
                                   for k in sweep.TRACE_COUNTS),
          "sharded compaction did not run the mesh schedule")
    n_b, k_b, c_b = shapes[-1]
    axes = comp.backend.plan.dp_axes
    i32 = jax.numpy.int32
    args = (jax.ShapeDtypeStruct((n_b, k_b), i32,
                                 sharding=NamedSharding(mesh, P(axes, None))),
            jax.ShapeDtypeStruct((n_b,), jax.numpy.bool_,
                                 sharding=NamedSharding(mesh, P(axes))),
            jax.ShapeDtypeStruct((c_b, k_b), i32))
    text, temp = _compile(sweep._sharded_sweep_fn(mesh, tuple(axes), True),
                          args)
    check("all-to-all" in text, "sharded sweep compiled no all-to-all")
    if _on_tpu():
        check("tpu_custom_call" in text,
              "sharded sweep compiled without the Pallas kernels")
    return {"seconds": dt, "buckets": shapes, "temp_bytes": temp,
            "lowerings": sweep.EXEC_STATS["lowerings"]}


# -- entry points ------------------------------------------------------------

def run_one_chip(cfg) -> dict:
    from repro.kernels import ops
    check(not ops._interpret(), "Pallas kernels would run interpreted")
    store, dt = _timed(make_store, cfg)
    log(f"phase generate: {dt:.3f} s ({store.n_triples} triples, "
        f"{cfg.name})")
    (ref, rep), dt = _timed(host_reference, store)
    log(f"phase host reference: {dt:.3f} s ({rep.n_triples_before} -> "
        f"{rep.n_triples_after} triples, {rep.pct_savings_triples:.2f}% "
        f"saved, {len(ref.fgraph.tables)} classes)")
    (svc, st), dt = _timed(device_compaction, store, ref)
    log(f"phase device compaction: {dt:.3f} s incl. compile "
        f"({st['lowerings']} lowerings, {st['descents']} descents, "
        f"buckets {st['buckets']}, largest sweep's temporaries "
        f"{st['temp_bytes']} B; digest == host)")
    sv, dt = _timed(serve, svc, store)
    warm = ", ".join(f"{s * 1e3:.1f}" for s in sv["warm_wave_s"])
    log(f"phase serve: {dt:.3f} s; first wave {sv['first_wave_s'] * 1e3:.1f}"
        f" ms (compiling), warm waves {warm} ms, BGPs "
        f"{sv['bgp_s'] * 1e3:.1f} ms ({sv['requests']} requests, "
        f"{sv['rows']} rows, {sv['match_lowerings']} match lowerings, "
        "all ok, raw fallback 0)")
    ing, dt = _timed(ingest, svc, store)
    log(f"phase ingest: {dt:.3f} s; drain {ing['drain_s']:.3f} s "
        f"({ing['batches']} batches, {ing['redetections']} re-detections, "
        f"{ing['redetect_lowerings']} re-detection lowerings, failures 0, "
        f"queue empty, {ing['triples_after']} triples; digest == host)")
    return {"compaction": st, "serve": sv, "ingest": ing}


def run_four_chips(cfg) -> dict:
    import jax

    from repro.launch.mesh import make_mesh
    check(len(jax.devices()) == 4,
          f"--chips 4 needs 4 devices, JAX has {len(jax.devices())}")
    mesh = make_mesh((4, 1), ("data", "model"))
    store, dt = _timed(make_store, cfg)
    log(f"phase generate: {dt:.3f} s ({store.n_triples} triples, "
        f"{cfg.name})")
    (ref, rep), dt = _timed(host_reference, store)
    log(f"phase host reference: {dt:.3f} s ({rep.pct_savings_triples:.2f}% "
        "saved)")
    sh, dt = _timed(sharded_compaction, store, ref, mesh)
    log(f"phase sharded compaction: {sh['seconds']:.3f} s incl. compile "
        f"({sh['lowerings']} lowerings, buckets {sh['buckets']}, "
        f"temporaries {sh['temp_bytes']} B per device, mesh "
        f"{dict(mesh.shape)}; all-to-all compiled; digest == host); "
        f"{dt:.3f} s with the compiled-program check")
    return {"sharded": sh}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded detection on four chips")
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs.rdf_sensor import D1D2D3
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].device_kind} x {len(devices)}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(D1D2D3)
    else:
        run_one_chip(D1D2D3)
    log(f"total: {time.perf_counter() - t0:.3f} s")
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"device {d.id} memory: " + ", ".join(
            f"{k} {v}" for k, v in sorted(stats.items())
            if k in ("peak_bytes_in_use", "bytes_limit",
                     "largest_alloc_size", "bytes_reserved")))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
