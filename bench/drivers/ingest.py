"""A live sensor feed with retention through the durable online service.

Set-up generates the graph and opens ``OnlineCompactionService.durable``
on it under the run's state directory (initial compaction on the
configured backend, initial checkpoint).  The window offers the traffic
file's ``rate_per_s`` observations per second as an open loop.  Each
arrival is one observation (9 triples) from a uniformly drawn sensor
with a Zipf value, submitted as one batch (journaled and fsynced before
``submit`` returns); a new sampling instant starts every
``observations_per_instant`` arrivals, and when an instant completes
one delete batch removes the entities of the oldest retained instant.
Each turn submits what is due and calls ``step()`` once while batches
are queued.  An observation is visible when the ``step()`` that applied
it returns; its latency runs from its due time to then.

Correctness, after the window: the live graph, and the graph after
closing and reopening the service from its durable root, each expanded
from G' by the plain expansion, must equal the reference net graph
(initial graph, plus every acknowledged observation, minus every triple
of a deleted entity).  Every re-detection the window ran is held to the
reference too: for each class it considered, the star pattern the
device sweep chose and its AMI (the molecule count) must equal plain
G.FSP's over the reference graph as of the batch it ran after; the
window has to hold at least one, and none may have failed.
"""
from __future__ import annotations

import os
import sys
import time

import jax
import numpy as np

from bench import common, gen, reference


def _service(run, source=None):
    from repro.online import OnlineCompactionService
    cfg = run.config
    return OnlineCompactionService.durable(
        os.path.join(run.state_dir, "durable"), source,
        detector=cfg["detector"], backend=cfg["backend"],
        wal_kwargs={"fsync_policy": cfg["fsync_policy"]},
        checkpoint_every=cfg["checkpoint_every"])


def _stream(g, cfg: dict, r: np.random.Generator, n: int):
    """Term triples of ``n`` new observations and the instant each
    belongs to."""
    d = gen.draw(r, n, n_sensors=cfg["n_sensors"], n_timestamps=1,
                 n_values=cfg["n_values"], zipf_a=cfg["zipf_a"])
    per = cfg["observations_per_instant"]
    instant = cfg["n_timestamps"] + np.arange(n) // per
    out = []
    for j in range(n):
        i = g.n_obs + j
        o, m = f"obs/{i}", f"meas/{i}"
        s = f"sensor/{d.sensor[j]}"
        ph = gen.PHENOMENA[d.phen[j]]
        out.append([
            (o, gen.RDF_TYPE, gen.OBSERVATION),
            (o, gen.P_PROPERTY, f"phenom/{ph}"),
            (o, gen.P_PROCEDURE, s), (o, gen.P_GENERATED_BY, s),
            (o, gen.P_TIME, f"time/{instant[j]}"),
            (o, gen.P_RESULT, m),
            (m, gen.RDF_TYPE, gen.MEASUREMENT),
            (m, gen.P_VALUE, f"val/{d.value[j]}"),
            (m, gen.P_UNIT, f"unit/{ph}")])
    return out


def setup(run):
    cfg = run.config
    g = common.graph(cfg, run.seed)
    svc = _service(run, common.load_store(g))
    r = common.rng(run.seed, common.TRAFFIC)
    due = common.arrivals(r, run.traffic["rate_per_s"], run.seconds)
    # the initial graph's observations by sampling instant, oldest first
    order = np.argsort(g.draws.time, kind="stable")
    cuts = np.searchsorted(g.draws.time[order],
                           np.arange(cfg["n_timestamps"] + 1))
    return {"graph": g, "svc": svc, "due": due,
            "obs": _stream(g, cfg, r, len(due)),
            "by_instant": [order[a:b] for a, b in zip(cuts, cuts[1:])],
            "deleted": [], "redetects": []}


def window(run, st):
    svc, due, obs = st["svc"], st["due"], st["obs"]
    per = run.config["observations_per_instant"]
    seq = st["seq"] = np.full(len(due), -1)
    visible = np.full(len(due), np.nan)
    submit_s, step_s = [], []
    red0 = svc.metrics.channel("redetect.ms")
    red_ms0, red_n0 = red0.total, red0.count
    late = 0.0
    t0 = time.monotonic()
    i = applied = 0
    while True:
        now = time.monotonic() - t0
        j = int(np.searchsorted(due, now, "right"))
        if j > i:
            late = max(late, now - due[i])
        for k in range(i, j):
            with jax.profiler.TraceAnnotation("bench.submit"):
                ts = time.monotonic()
                seq[k] = svc.submit(inserts=obs[k]).seq
                if (k + 1) % per == 0:        # an instant completed
                    ents = st["by_instant"][len(st["deleted"])]
                    b = svc.submit(delete_entities=(
                        [f"obs/{e}" for e in ents.tolist()]
                        + [f"meas/{e}" for e in ents.tolist()]))
                    st["deleted"].append((b.seq, ents))
                submit_s.append(time.monotonic() - ts)
        i = j
        if svc.queue.depth:
            with jax.profiler.TraceAnnotation("bench.step"):
                ts = time.monotonic()
                rep = svc.step()
                done = time.monotonic()
            step_s.append(done - ts)
            if rep.redetect is not None:
                st["redetects"].append((rep.seq, _decisions(rep.redetect)))
            fresh = (seq[applied:i] >= 0) & (seq[applied:i] <= rep.seq)
            visible[applied:i][fresh] = done - t0
            applied += int(fresh.sum())
        elif i == len(due):
            break
        else:
            common.sleep_until(t0, due[i])
    red = svc.metrics.channel("redetect.ms")
    ck = svc.metrics.channel("checkpoint.bytes")
    print(f"bench: {len(step_s)} steps, {red.count - red_n0} re-detections, "
          f"{ck.count} checkpoints of {ck.total:.0f} bytes in all",
          file=sys.stderr)
    run.counters.update(
        submit_s=submit_s, turn_s=step_s, late_s=late,
        redetect_ms=red.total - red_ms0, redetects=red.count - red_n0)
    lat = visible - due
    run.counters["latency_s"] = lat
    return {"ingest_visible_p95_ms": common.p95_ms(lat), "attempted":
            len(due), "failed": int(np.isnan(visible).sum())}


def _decisions(report) -> dict:
    """What one re-detection decided: ``{class: (star pattern, AMI)}``
    for every class it considered, ``None`` for a class it left raw."""
    planned = {int(e.class_id): (tuple(int(p) for p in e.props),
                                 int(e.detection.ami))
               for e in report.plan}
    return {int(c): planned.get(int(c)) for c in report.considered}


def _net(st, d, seq: float = np.inf) -> np.ndarray:
    """The reference graph once every batch up to ``seq`` is applied,
    in the service's ids."""
    g = st["graph"]
    gone = [e for s, e in st["deleted"] if s <= seq]
    gone = np.concatenate([np.concatenate([g.ids("obs/", e),
                                           g.ids("meas/", e)])
                           for e in gone]) if gone else np.empty(0, np.int64)
    keep = ~(np.isin(g.spo[:, 0], gone) | np.isin(g.spo[:, 2], gone))
    if "new" not in st:
        st["new"] = np.asarray([[d.lookup(t) for t in tri]
                                for rows in st["obs"] for tri in rows],
                               np.int32).reshape(-1, 3)
    # observations are acknowledged in order, so those up to ``seq`` are
    # a prefix of the stream
    n = int(((st["seq"] >= 0) & (st["seq"] <= seq)).sum())
    return np.concatenate([g.spo[keep], st["new"][:9 * n]])


def check(run, st):
    svc = st.pop("svc")
    g = st["graph"]
    type_id, inst = g.id("rdf:type"), g.id("repro:instanceOf")
    failures = int(svc.metrics.channel("redetect.failures").total)
    d = svc.snapshot.store.dict
    net = _net(st, d)
    live = reference.rows_differ(
        reference.expand(svc.snapshot.store.spo, inst), net)
    svc.close()
    del svc
    try:
        again = _service(run)
        reopened = reference.rows_differ(
            reference.expand(again.snapshot.store.spo, inst), net)
        again.close()
    except Exception as e:            # a state that cannot be reopened
        print(f"bench: reopening the durable root failed: {e!r}",
              file=sys.stderr)
        reopened = int(net.shape[0])
    patterns = 0
    for seq, got in st["redetects"]:
        want = reference.greedy_fsp(_net(st, d, seq), type_id, inst)
        for c, decided in sorted(got.items()):
            print(f"bench: re-detection after batch {seq}, class {c}: "
                  f"{decided} (reference {want.get(c)})", file=sys.stderr)
            patterns += decided != want.get(c)
    return [("live_rows_differ", live, 0),
            ("reopened_rows_differ", reopened, 0),
            ("patterns_differ", patterns, 0),
            ("redetections_missing", int(not st["redetects"]), 0),
            ("redetect_failures", failures, 0)]
