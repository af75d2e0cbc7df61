"""ShardedFactorizedGraph: partition disjointness, plan balance /
chunk-splitting, shard-local detection digest parity (sequential and
fork-parallel), cross-shard AMI, query fan-out parity, the planner's
``sharded_graph=`` paths, atomic swap discipline, and the
``ShardedQueryService`` request surface."""
from __future__ import annotations

import numpy as np
import pytest

from repro.api import CompactionPlanner
from repro.core.triples import TripleStore
from repro.data.synthetic import SensorGraphSpec, generate
from repro.dist.graph import (ShardedFactorizedGraph, ShardedQueryEngine,
                              ShardPlan)
from repro.query import QueryEngine, StarQuery


def _sensor(n=200, seed=7, **kw):
    return generate(SensorGraphSpec(n_observations=n, seed=seed, **kw))


def _detected(store, n_shards, *, parallel=False, oversplit=2):
    sharded = ShardedFactorizedGraph.partition(store, n_shards,
                                               oversplit=oversplit)
    report = sharded.detect_all(backend="host", parallel=parallel)
    return sharded, report


def _repl(store):
    snap, rep = CompactionPlanner("gfsp", "host").run(store.copy())
    return snap, rep


# ---------------------------------------------------------------------------
# partition + plan
# ---------------------------------------------------------------------------

def test_partition_rows_disjoint_and_complete():
    store = _sensor()
    sharded = ShardedFactorizedGraph.partition(store, 3)
    parts = [s.fgraph.store.spo for s in sharded.snapshots]
    assert sum(p.shape[0] for p in parts) == store.n_triples
    union = np.unique(np.concatenate(parts, axis=0), axis=0)
    assert union.shape[0] == store.n_triples          # disjoint rows
    assert np.array_equal(union, np.unique(store.spo, axis=0))


def test_typed_subject_star_never_straddles_shards():
    store = _sensor()
    sharded = ShardedFactorizedGraph.partition(store, 3)
    plan = sharded.plan
    for sid, snap in enumerate(sharded.snapshots):
        subs = snap.fgraph.store.spo[:, 0].astype(np.int64)
        pos = np.searchsorted(plan.owner_entities, subs)
        pos_c = np.minimum(pos, plan.owner_entities.shape[0] - 1)
        typed = (pos < plan.owner_entities.shape[0]) & \
            (plan.owner_entities[pos_c] == subs)
        # every typed row in this shard is owned by exactly this shard
        assert (plan.owner_shard[pos_c[typed]] == sid).all()


def test_plan_balances_on_edge_counts_and_chunk_splits():
    store = _sensor(400)
    plan = ShardPlan.build(store, 4, oversplit=4)
    w = np.asarray(plan.shard_weights)
    assert w.sum() == store.n_triples
    assert w.max() <= 2 * max(1, w.min())    # LPT on chunked items
    # the sensor shape has few big classes: filling 4 shards forces
    # chunk-splitting, which is what split_classes reports
    assert plan.n_chunks > len(store.classes())
    assert plan.split_classes
    for cid in plan.split_classes:
        assert len(plan.class_shards[cid]) > 1


def test_route_rows_matches_partition():
    store = _sensor()
    plan = ShardPlan.build(store, 3)
    sids = plan.route_rows(store.spo)
    assert sids.shape == (store.n_triples,)
    assert set(np.unique(sids)) <= set(range(3))
    # routing is deterministic and row-order independent
    perm = np.random.default_rng(0).permutation(store.n_triples)
    assert np.array_equal(plan.route_rows(store.spo[perm]), sids[perm])


# ---------------------------------------------------------------------------
# shard-local detection: digest parity (Def. 4.10)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_detect_sequential_digest_parity(n_shards):
    store = _sensor()
    snap, rep = _repl(store)
    sharded, report = _detected(store, n_shards)
    assert sharded.digest() == snap.digest()
    assert sharded.n_triples <= store.n_triples       # compaction paid
    assert set(report["shards"]) == set(range(n_shards))
    for r in report["shards"].values():
        assert r["n_after"] <= r["n_before"]
        assert r["detect_ms"] >= 0.0


def test_detect_fork_parallel_digest_parity_and_shared_dict():
    store = _sensor()
    snap, _ = _repl(store)
    sharded, report = _detected(store, 3, parallel=True)
    assert sharded.digest() == snap.digest()
    # workers minted surrogates through the fork boundary; the parent
    # re-minted them into the ONE shared dictionary
    for s in sharded.snapshots:
        assert s.fgraph.store.dict is store.dict
        for t in s.fgraph.tables.values():
            for sur in t.surrogates[:2]:
                assert store.dict.term(int(sur)).startswith("repro:sg/s")
    assert any(r["classes"] for r in report["shards"].values())


@pytest.mark.parametrize("backend", ["device", "sharded"])
def test_detect_fork_parallel_refuses_device_backends(backend):
    """A forked worker cannot use a chip its parent holds: fork-parallel
    detection is host-only and refuses before any worker starts."""
    sharded = ShardedFactorizedGraph.partition(_sensor(), 2)
    epochs = [s.epoch for s in sharded.snapshots]
    with pytest.raises(ValueError, match="host backend only"):
        sharded.detect_all(backend=backend, parallel=True)
    assert [s.epoch for s in sharded.snapshots] == epochs


def test_detect_bumps_epoch_and_swaps_atomically():
    store = _sensor()
    sharded = ShardedFactorizedGraph.partition(store, 2)
    before = sharded.snapshots
    assert sharded.epoch == 0
    sharded.detect_all(backend="host")
    after = sharded.snapshots
    assert after is not before            # one tuple store, not mutation
    assert sharded.epoch == 1
    assert all(s.epoch == 1 for s in after)


def test_cross_shard_ami_exact():
    store = _sensor(400)
    sharded = ShardedFactorizedGraph.partition(store, 4, oversplit=4)
    assert sharded.plan.split_classes
    for cid in sharded.plan.split_classes:
        props = np.asarray(sharded.plan.class_props[int(cid)], np.int32)
        _, mat = store.copy().object_matrix(int(cid), props)
        want = int(np.unique(mat, axis=0).shape[0])
        assert sharded.cross_shard_ami(cid) == want


def test_swap_shard_replaces_exactly_one():
    store = _sensor()
    sharded, _ = _detected(store, 3)
    before = sharded.snapshots
    sharded.swap_shard(1, before[1])       # identity swap still re-tuples
    after = sharded.snapshots
    assert after is not before
    assert after[0] is before[0] and after[2] is before[2]


# ---------------------------------------------------------------------------
# planner sharded paths
# ---------------------------------------------------------------------------

def test_planner_plan_sharded_graph_returns_per_shard_plans():
    store = _sensor()
    sharded = ShardedFactorizedGraph.partition(store, 2)
    plans = CompactionPlanner("gfsp", "host").plan(sharded_graph=sharded)
    assert set(plans) == {0, 1}
    for p in plans.values():
        assert all(e.predicted_edges >= 0 for e in p)


def test_planner_redetect_sharded_graph_keeps_digest():
    store = _sensor()
    sharded, _ = _detected(store, 2)
    digest = sharded.digest()
    dirty = [int(c) for c in store.classes()][:1]
    before = sharded.snapshots
    out, reports = CompactionPlanner("gfsp", "host").redetect(
        None, dirty, sharded_graph=sharded)
    assert out is sharded
    assert sharded.snapshots is not before     # single atomic tuple swap
    assert sharded.digest() == digest
    assert reports                             # some shard held the class
    touched = {sid for sid in reports}
    for cid in dirty:
        assert touched & set(sharded.plan.shards_for_class(cid))


# ---------------------------------------------------------------------------
# query fan-out parity
# ---------------------------------------------------------------------------

def _queries(fg, per_class=6):
    qs = []
    for cid, t in sorted(fg.tables.items()):
        for row in t.objects[:per_class]:
            qs.append(StarQuery(arms=tuple(
                (int(p), int(o)) for p, o in zip(t.props, row)),
                class_id=cid))
            qs.append(StarQuery(arms=((int(t.props[0]), int(row[0])),
                                      (int(t.props[-1]), None)),
                      class_id=cid))
        # classless variant of the same star: coordinator-merged
        qs.append(StarQuery(arms=((int(t.props[0]), None),),
                            class_id=None))
    return qs


def test_sharded_query_engine_star_parity():
    store = _sensor()
    snap, _ = _repl(store)
    sharded, _ = _detected(store, 3)
    repl = QueryEngine(snap.fgraph)
    eng = ShardedQueryEngine(sharded)
    for q in _queries(snap.fgraph):
        a = repl.query(q)
        b = eng.query(q)
        assert a.same_as(b), q
    assert sharded.traffic["query_bytes"] > 0


def test_sharded_query_engine_batch_parity():
    store = _sensor()
    snap, _ = _repl(store)
    sharded, _ = _detected(store, 3)
    qs = _queries(snap.fgraph)
    ra = QueryEngine(snap.fgraph).query_batch(qs)
    rb = ShardedQueryEngine(sharded).query_batch(qs)
    for q, a, b in zip(qs, ra, rb):
        assert a.same_as(b), q


def test_sharded_bgp_parity():
    from repro.query.bgp.algebra import BGPQuery, Filter, StarPattern
    store = _sensor()
    snap, _ = _repl(store)
    sharded, _ = _detected(store, 3)
    d = store.dict
    cid = d.lookup("ssn:Observation")
    t = snap.fgraph.tables[cid]
    p0, p1 = int(t.props[0]), int(t.props[-1])
    q = BGPQuery(
        stars=(StarPattern("?s", ((p0, "?v"), (p1, "?w")), cid),),
        filters=(Filter("?v", "!=", -1),))
    a = QueryEngine(snap.fgraph).query_bgp(q)
    b = ShardedQueryEngine(sharded).query_bgp(q)
    assert a.columns == b.columns
    assert np.array_equal(np.unique(a.rows, axis=0),
                          np.unique(b.rows, axis=0))


def test_sharded_engine_rebind_follows_swap():
    store = _sensor()
    sharded, _ = _detected(store, 2)
    eng = ShardedQueryEngine(sharded)
    q = _queries(sharded.snapshots[0].fgraph
                 if sharded.snapshots[0].fgraph.tables
                 else sharded.snapshots[1].fgraph, per_class=1)[0]
    before = eng.query(q)
    CompactionPlanner("gfsp", "host").redetect(
        None, [int(c) for c in store.classes()], sharded_graph=sharded)
    eng.rebind()
    for e, s in zip(eng.engines, sharded.snapshots):
        assert e.fgraph is s.fgraph
    assert eng.query(q).same_as(before)


# ---------------------------------------------------------------------------
# ShardedQueryService: fan-out request surface
# ---------------------------------------------------------------------------

def _term_requests(store, fg, d):
    from repro.serving import GraphQueryRequest
    reqs = []
    rid = 0
    for cid, t in sorted(fg.tables.items()):
        cterm = d.term(cid)
        row = t.objects[0]
        reqs.append(GraphQueryRequest(
            rid=rid, arms=tuple((d.term(int(p)), d.term(int(o)))
                                for p, o in zip(t.props, row)),
            class_term=cterm))
        rid += 1
        reqs.append(GraphQueryRequest(
            rid=rid, arms=((d.term(int(t.props[0])), None),),
            class_term=cterm))
        rid += 1
        reqs.append(GraphQueryRequest(          # classless: coordinator
            rid=rid, arms=((d.term(int(t.props[0])), None),),
            class_term=None))
        rid += 1
    return reqs


def test_sharded_service_parity_with_replicated_service():
    from repro.serving import GraphQueryService, ShardedQueryService
    store = _sensor()
    snap, _ = _repl(store)
    sharded, _ = _detected(store, 3)
    reqs = _term_requests(store, snap.fgraph, store.dict)

    ref = GraphQueryService(snap.fgraph)
    svc = ShardedQueryService(sharded)
    for r in reqs:
        assert ref.submit(r)
        assert svc.submit(r)
    want = ref.run()
    got = svc.run()
    assert set(got) == set(want)
    for rid in want:
        a, b = want[rid], got[rid]
        assert a.status == b.status == "ok"
        assert sorted(zip(a.subjects, a.var_objects)) == \
            sorted(zip(b.subjects, b.var_objects)), rid


def test_sharded_service_all_or_nothing_admission():
    from repro.serving import GraphQueryRequest, ShardedQueryService
    store = _sensor(400)
    sharded, _ = _detected(store, 4, oversplit=4)
    assert sharded.plan.split_classes      # some class fans out wide
    svc = ShardedQueryService(sharded, max_pending=1)
    d = store.dict
    cid = sharded.plan.split_classes[0]
    owners = sharded.plan.shards_for_class(cid)
    assert len(owners) > 1
    fg = sharded.snapshots[owners[0]].fgraph
    t = fg.tables[int(cid)]
    mk = lambda rid: GraphQueryRequest(
        rid=rid, arms=((d.term(int(t.props[0])), None),),
        class_term=d.term(int(cid)))
    assert svc.submit(mk(0))               # fills every owner queue
    assert not svc.submit(mk(1))           # ANY full owner -> whole shed
    # no torn fan-out: rid 1 is queued on NO shard
    assert all(all(r.rid != 1 for r in s.queue) for s in svc.shards)
    assert svc.metrics.summary()["admission.shed"]["count"] >= 1
    out = svc.run()
    assert out[0].status == "ok"


def test_sharded_service_coordinator_bgp_and_deadline():
    from repro.serving import BGPQueryRequest, ShardedQueryService
    store = _sensor()
    sharded, _ = _detected(store, 2)
    d = store.dict
    cterm = "ssn:Observation"
    fg = sharded.snapshots[0].fgraph
    if not fg.tables:
        fg = sharded.snapshots[1].fgraph
    t = next(iter(fg.tables.values()))
    star = ("?s", ((d.term(int(t.props[0])), "?v"),), cterm)
    svc = ShardedQueryService(sharded)
    assert svc.submit(BGPQueryRequest(rid=9, stars=(star,)))
    assert svc.queue and not any(s.queue for s in svc.shards)
    out = svc.run()
    assert out[9].status == "ok" and out[9].n_rows > 0

    # an already-expired deadline sheds the coordinator wave
    tick = iter([0.0, 10.0, 20.0, 30.0])
    svc2 = ShardedQueryService(sharded, wave_deadline_s=0.5,
                               clock=lambda: next(tick))
    assert svc2.submit(BGPQueryRequest(rid=1, stars=(star,)))
    out2 = svc2.run()
    assert out2[1].status == "shed"
    assert svc2.metrics.summary()["wave.deadline_shed"]["count"] >= 1
