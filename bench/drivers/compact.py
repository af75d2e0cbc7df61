"""Back-to-back whole-graph compactions (the paper's workload).

Each pass loads the seeded graph as a user's load would (a fresh term
dictionary and store, so the store's lazy indexes are rebuilt) and runs
``Compactor.run``; compiled programs stay warm.  One warm pass in
set-up loads or compiles the sweep.

End to end: ``compact_triples_per_s`` is the input triples of every
pass the window started over the seconds those passes took, and
``graph_bytes_per_triple`` the bytes the last pass's snapshot holds
(see ``bench/graph_bytes.py``) over the input triples.  Every pass is
compared with the reference: per class the star pattern and molecule
count of plain G.FSP, and G' expanding to exactly the input graph.
"""
from __future__ import annotations

import time

import jax

from bench import common, reference
from bench.graph_bytes import snapshot_bytes


def _pass(g, cfg):
    with jax.profiler.TraceAnnotation("bench.pass"):
        t0 = time.monotonic()
        comp = common.compactor(cfg)
        comp.run(common.load_store(g))
        return comp.snapshot, time.monotonic() - t0


def setup(run):
    g = common.graph(run.config, run.seed)
    _pass(g, run.config)
    return {"graph": g, "passes": []}


def window(run, st):
    g, passes = st["graph"], st["passes"]
    t0 = time.monotonic()
    while time.monotonic() - t0 < run.seconds:
        snap, dt = _pass(g, run.config)
        passes.append((snap.store.spo, dt))
        last = snap
    st["bytes"] = snapshot_bytes(last)
    del last, snap
    secs = sum(dt for _, dt in passes)
    run.counters["passes"] = len(passes)
    run.counters["pass_s"] = [dt for _, dt in passes]
    return {"compact_triples_per_s": g.n_triples * len(passes) / secs,
            "graph_bytes_per_triple": st["bytes"] / g.n_triples,
            "attempted": len(passes), "failed": 0}


def check(run, st):
    g = st["graph"]
    type_id, inst = g.id("rdf:type"), g.id("repro:instanceOf")
    want = reference.greedy_fsp(g.spo, type_id, inst)
    classes = rows = 0
    for gp, _ in st["passes"]:
        got = reference.layout(gp, type_id, inst)
        classes += len(set(got.items()) ^ set(want.items()))
        rows += reference.rows_differ(reference.expand(gp, inst), g.spo)
    st["passes"].clear()
    return [("classes_differ", classes, 0), ("rows_differ", rows, 0)]
