"""Crash durability: WAL framing / rotation / GC, torn-tail recovery
(byte-level corruption property test), atomic snapshot checkpoints with
damaged-checkpoint fallback, full crash-point recovery parity over every
fault-injection site, and graceful query degradation (admission shed,
wave deadlines, factorized -> raw fallback)."""
from __future__ import annotations

import dataclasses
import json
import os
import signal
import subprocess
import sys
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data.synthetic import SensorGraphSpec, generate
from repro.dist.fault import SITES, FaultPlan, InjectedFault
from repro.dist.graph import ShardedFactorizedGraph
from repro.online import (DurableWAL, OnlineCompactionService,
                          SnapshotCheckpointer, recover)
from repro.online.recovery import wal_dir
from repro.online.wal import WAL_MAGIC, IngestBatch
from repro.serving import GraphQueryRequest, GraphQueryService


def _store(n=40, seed=5):
    return generate(SensorGraphSpec(n_observations=n, seed=seed))


def _batch(seq, n_ins=2, base=100):
    rng = np.random.default_rng(seq + base)
    return IngestBatch(
        seq=seq,
        inserts=rng.integers(0, 99, (n_ins, 3)).astype(np.int32),
        delete_triples=np.empty((0, 3), np.int32),
        delete_entities=np.asarray([seq * 7], np.int64))


def _novel_batches(store, n):
    """Deterministic term-level batches of complete typed entities with
    novel object tuples (each feeds support drift), every third batch
    deleting an earlier insert -- the drift-heavy shape the recovery
    sweep needs so re-detection genuinely runs."""
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


_SVC_KW = dict(detector="gfsp", backend="host", raw_residue_threshold=4,
               support_drift_threshold=3, retry_sleep=lambda _: None)


# ---------------------------------------------------------------------------
# DurableWAL: framing, rotation, GC
# ---------------------------------------------------------------------------

def test_wal_roundtrip_in_write_order(tmp_path):
    wal = DurableWAL(str(tmp_path))
    wal.append_mints([(7, "ex:a"), (8, "lit:é")])   # non-ascii term
    b0, b1 = _batch(0), _batch(1, n_ins=0)
    wal.append_batch(b0)
    wal.append_applied([0])
    wal.append_batch(b1)
    wal.close()

    wal2 = DurableWAL(str(tmp_path))
    recs = list(wal2.replay())
    wal2.close()
    assert [k for k, _ in recs] == ["mint", "batch", "apply", "batch"]
    assert recs[0][1] == [(7, "ex:a"), (8, "lit:é")]
    assert recs[2][1] == [0]
    for got, want in ((recs[1][1], b0), (recs[3][1], b1)):
        assert got.seq == want.seq
        np.testing.assert_array_equal(got.inserts, want.inserts)
        np.testing.assert_array_equal(got.delete_entities,
                                      want.delete_entities)
    assert wal2.truncated_bytes == 0 and wal2.dropped_segments == 0


def test_wal_rotation_and_gc_keeps_uncovered(tmp_path):
    wal = DurableWAL(str(tmp_path), segment_max_bytes=256)
    for seq in range(10):
        wal.append_mints([(100 + seq, f"ex:m{seq}")])
        wal.append_batch(_batch(seq))
        wal.append_applied([seq])
    assert wal.n_segments > 2            # rotation actually happened

    # checkpoint covering seq <= 4 and mints < 105: covered non-active
    # segments go, everything later survives
    removed = wal.gc(applied_seq=4, n_terms=105)
    assert removed > 0
    survivors = {rec.seq for kind, rec in wal.replay() if kind == "batch"}
    assert survivors >= set(range(5, 10)), survivors
    # the prefix property: surviving seqs are a contiguous tail
    assert survivors == set(range(min(survivors), 10))
    # active segment never collected, even when fully covered
    n = wal.n_segments
    wal.gc(applied_seq=99, n_terms=10_000)
    assert wal.n_segments >= 1 and wal.nbytes() > 0
    wal.close()


def test_wal_fsync_interval_policy(tmp_path):
    clock = [0.0]
    wal = DurableWAL(str(tmp_path), fsync_policy="interval",
                     fsync_interval_s=5.0, clock=lambda: clock[0])
    wal.append_batch(_batch(0))
    clock[0] = 6.0
    wal.append_batch(_batch(1))          # interval elapsed -> fsync
    wal.close()
    wal2 = DurableWAL(str(tmp_path))
    assert sum(1 for k, _ in wal2.replay() if k == "batch") == 2
    wal2.close()
    with pytest.raises(ValueError):
        DurableWAL(str(tmp_path / "x"), fsync_policy="sometimes")


# ---------------------------------------------------------------------------
# torn-tail property: ANY byte-level truncation/corruption of the tail
# recovers to the longest valid record prefix
# ---------------------------------------------------------------------------

def _reference_journal(tmp_path):
    wal = DurableWAL(str(tmp_path))
    wal.append_mints([(50, "ex:mint")])
    for seq in range(4):
        wal.append_batch(_batch(seq))
        wal.append_applied([seq])
    wal.close()
    path = wal._segments[-1]
    with open(path, "rb") as f:
        data = f.read()
    w2 = DurableWAL(str(tmp_path))
    kinds = [k for k, _ in w2.replay()]
    w2.close()
    return path, data, kinds


@settings(max_examples=40)
@given(cut=st.integers(min_value=0, max_value=400),
       corrupt=st.booleans(), flip=st.integers(min_value=1, max_value=255))
def test_wal_torn_tail_recovers_longest_valid_prefix(cut, corrupt, flip):
    # a directory per generated example: hypothesis runs every example
    # inside one call of the test, so a function-scoped ``tmp_path``
    # would be shared by all of them
    with tempfile.TemporaryDirectory() as sub:
        _torn_tail_case(sub, cut, corrupt, flip)


def _torn_tail_case(sub, cut, corrupt, flip):
    path, data, full_kinds = _reference_journal(sub)
    cut = min(cut, len(data))
    if corrupt and cut < len(data):
        # flip one byte at ``cut``; everything before stays intact
        damaged = data[:cut] + bytes([data[cut] ^ flip]) + data[cut + 1:]
    else:
        damaged = data[:cut]             # plain truncation
    with open(path, "wb") as f:
        f.write(damaged)

    wal = DurableWAL(str(sub))
    recs = list(wal.replay())
    kinds = [k for k, _ in recs]
    # the recovered log is a PREFIX of the original record sequence
    assert kinds == full_kinds[:len(kinds)]
    if cut < len(data):
        assert wal.truncated_bytes > 0 or not corrupt
    # and the journal is append-ready again: a post-recovery write
    # survives its own reopen
    wal.append_batch(_batch(99))
    wal.close()
    wal2 = DurableWAL(str(sub))
    seqs = [rec.seq for k, rec in wal2.replay() if k == "batch"]
    wal2.close()
    assert seqs[-1] == 99 and seqs[:-1] == [
        rec.seq for k, rec in recs if k == "batch"]


def test_wal_bad_magic_drops_whole_segment_and_later_ones(tmp_path):
    wal = DurableWAL(str(tmp_path), segment_max_bytes=150)
    for seq in range(12):
        wal.append_batch(_batch(seq))
    assert wal.n_segments >= 3
    first, second = wal._segments[0], wal._segments[1]
    wal.close()
    with open(second, "r+b") as f:       # corrupt a MIDDLE segment's magic
        f.write(b"XXXXXXXX")
    wal2 = DurableWAL(str(tmp_path))
    seqs = [rec.seq for k, rec in wal2.replay() if k == "batch"]
    wal2.close()
    # prefix property across segments: only records before the damaged
    # segment survive; later segments were written later and are cut
    with open(first, "rb") as f:
        assert f.read(8) == WAL_MAGIC
    assert seqs == list(range(len(seqs)))
    assert wal2.dropped_segments >= 1


# ---------------------------------------------------------------------------
# checkpoints: atomic write, damaged-newest fallback
# ---------------------------------------------------------------------------

def _durable_service(root, store=None, **kw):
    kw = {**_SVC_KW, **kw}
    return OnlineCompactionService.durable(
        str(root), store if store is not None else _store(),
        checkpoint_every=3, checkpoint_async=False, **kw)


def test_checkpoint_roundtrip_digest_identical(tmp_path):
    svc = _durable_service(tmp_path / "root")
    seq = _novel_batches(_store(), 4)
    for ins, dels in seq:
        svc.submit(inserts=ins, delete_entities=dels)
        svc.drain()
    svc.checkpoint(wait=True)
    want = svc.snapshot.digest()
    svc.close()

    ck = SnapshotCheckpointer(str(tmp_path / "root" / "ckpt"))
    restored = ck.restore_latest()
    assert restored is not None
    assert restored.snapshot.digest() == want
    assert restored.applied_seq == svc.applied_seq
    assert restored.nbytes > 0


def test_checkpoint_damaged_newest_falls_back(tmp_path):
    svc = _durable_service(tmp_path / "root")
    seq = _novel_batches(_store(), 6)
    for ins, dels in seq:
        svc.submit(inserts=ins, delete_entities=dels)
        svc.drain()
    svc.checkpoint(wait=True)
    svc.close()
    ck = SnapshotCheckpointer(str(tmp_path / "root" / "ckpt"))
    steps = ck.steps()
    assert len(steps) >= 2
    newest = steps[-1]
    # corrupt one array of the newest checkpoint: sha1 mismatch
    victim = os.path.join(ck._step_dir(newest), "spo.npy")
    with open(victim, "r+b") as f:
        f.seek(0, os.SEEK_END)
        size = f.tell()
        f.seek(size - 1)
        b = f.read(1)
        f.seek(size - 1)
        f.write(bytes([b[0] ^ 0xFF]))
    assert ck.validate(newest) is None
    assert ck.latest_valid() == steps[-2]
    # recovery survives: it restores the previous step and replays the
    # WAL suffix past it
    svc2 = recover(str(tmp_path / "root"), **_SVC_KW)
    svc2.drain()
    svc2.close()
    assert svc2.last_recovery.checkpoint_step == steps[-2]
    assert svc2.queue.depth == 0


def test_checkpoint_tmp_garbage_is_invisible_and_collected(tmp_path):
    ck = SnapshotCheckpointer(str(tmp_path), keep=2)
    os.makedirs(os.path.join(str(tmp_path), "step_00000009.tmp"))
    assert ck.steps() == [] and ck.latest_valid() is None


# ---------------------------------------------------------------------------
# crash-point recovery: every injection site, digest parity, exact seq
# accounting
# ---------------------------------------------------------------------------

def _reference_digest(seq):
    ref = OnlineCompactionService(_store(), **_SVC_KW)
    for ins, dels in seq:
        ref.submit(inserts=ins, delete_entities=dels)
        ref.drain()
    assert ref.queue.depth == 0
    return ref.snapshot.digest()


def _crash_run(root, seq, site, occurrence):
    """The validated sweep protocol: submit+drain each batch; on an
    injected crash, recover from disk and resubmit the interrupted
    batch once (idempotent under RDF set semantics)."""
    svc = _durable_service(root, fault_plan=FaultPlan(
        site, occurrence=occurrence))
    crashed = False
    for ins, dels in seq:
        for _ in range(2):
            try:
                svc.submit(inserts=ins, delete_entities=dels)
                svc.drain()
                break
            except InjectedFault:
                crashed = True
                svc = recover(str(root), **_SVC_KW)
        else:
            raise AssertionError(f"{site} kept crashing")
    svc.close()
    return svc, crashed


@pytest.mark.parametrize("site", SITES)
def test_crash_at_every_site_recovers_with_digest_parity(tmp_path, site):
    seq = _novel_batches(_store(), 8)
    want = _reference_digest(seq)
    svc, crashed = _crash_run(tmp_path / "root", seq, site, 0)
    assert crashed, f"fault site {site} never fired"
    assert svc.queue.depth == 0
    assert svc.snapshot.digest() == want, \
        f"recovered digest diverged after crash at {site}"

    # exact seq accounting from the journal itself: every journaled
    # batch seq is committed by exactly one surviving APPLY entry (no
    # lost writes, no double-applies)
    wal = DurableWAL(wal_dir(str(tmp_path / "root")))
    batch_seqs, applied = set(), []
    for kind, rec in wal.replay():
        if kind == "batch":
            batch_seqs.add(rec.seq)
        elif kind == "apply":
            applied.extend(rec)
    wal.close()
    # duplicates in the raw journal only ever come from recovery
    # re-journaling replayed runs; the EFFECTIVE apply sequence (first
    # occurrence each) must commit every batch exactly once, in order
    effective = list(dict.fromkeys(applied))
    assert sorted(effective) == effective
    assert set(effective) == batch_seqs
    assert svc.applied_seq == max(batch_seqs)


@settings(max_examples=6)
@given(site=st.sampled_from(SITES), occurrence=st.integers(0, 1))
def test_crash_recovery_parity_property(site, occurrence):
    seq = _novel_batches(_store(), 6)
    want = _reference_digest(seq)
    with tempfile.TemporaryDirectory() as sub:
        svc, _ = _crash_run(os.path.join(sub, "root"), seq, site,
                            occurrence)
    assert svc.queue.depth == 0
    assert svc.snapshot.digest() == want


def test_recovery_restart_of_restart(tmp_path):
    """A crash during the RECOVERED run (second fault) still converges:
    apply-run journaling dedupes already-replayed groups."""
    seq = _novel_batches(_store(), 8)
    want = _reference_digest(seq)
    root = tmp_path / "root"
    svc = _durable_service(root, fault_plan=FaultPlan("apply",
                                                      occurrence=0))
    crashes = 0
    for ins, dels in seq:
        for _ in range(3):
            try:
                svc.submit(inserts=ins, delete_entities=dels)
                svc.drain()
                break
            except InjectedFault:
                crashes += 1
                # re-arm a fresh fault on the FIRST recovery only
                plan = FaultPlan("apply", occurrence=1) \
                    if crashes == 1 else None
                svc = recover(str(root), fault_plan=plan, **_SVC_KW)
        else:
            raise AssertionError("crash loop")
    svc.close()
    assert crashes >= 2
    assert svc.queue.depth == 0
    assert svc.snapshot.digest() == want


def test_recovery_report_metrics_recorded(tmp_path):
    root = tmp_path / "root"
    seq = _novel_batches(_store(), 5)
    svc = _durable_service(root)
    for ins, dels in seq[:4]:
        svc.submit(inserts=ins, delete_entities=dels)
        svc.drain()
    # journal one more batch but do NOT apply it: it must come back
    # as the pending suffix
    svc.submit(inserts=seq[4][0], delete_entities=seq[4][1])
    svc.close()
    svc2 = recover(str(root), **_SVC_KW)
    rep = svc2.last_recovery
    assert rep is not None
    assert rep.checkpoint_bytes > 0
    assert rep.replay_ms >= 0.0
    assert rep.batches_pending >= 1         # the unapplied tail batch
    assert svc2.queue.depth >= 1
    m = svc2.metrics_summary()
    assert m["recovery.checkpoint_bytes"]["last"] == rep.checkpoint_bytes
    assert m["recovery.batches_replayed"]["last"] == rep.batches_pending
    svc2.drain()
    svc2.close()
    assert svc2.queue.depth == 0


def test_durable_reopen_without_crash_is_identity(tmp_path):
    """Clean close -> reopen restores the exact same state (epoch-level
    metadata included) with nothing pending."""
    root = tmp_path / "root"
    seq = _novel_batches(_store(), 6)
    svc = _durable_service(root)
    for ins, dels in seq:
        svc.submit(inserts=ins, delete_entities=dels)
        svc.drain()
    svc.checkpoint(wait=True)
    want, epoch = svc.snapshot.digest(), svc.snapshot.epoch
    svc.close()
    svc2 = OnlineCompactionService.durable(str(root), **_SVC_KW)
    assert svc2.snapshot.digest() == want
    assert svc2.snapshot.epoch == epoch
    assert svc2.queue.depth == 0
    assert svc2.last_recovery.batches_pending == 0
    svc2.close()


# ---------------------------------------------------------------------------
# shard-failure recovery: SIGKILL one shard's durable worker mid-soak,
# restart through recover(), swap back into the sharded graph
# ---------------------------------------------------------------------------

_SHARD_WORKER = """\
import json, sys
from repro.data.synthetic import SensorGraphSpec, generate
from repro.dist.fault import FaultPlan
from repro.dist.graph import ShardedFactorizedGraph
from repro.online import OnlineCompactionService

root, sid, batches_path = sys.argv[1], int(sys.argv[2]), sys.argv[3]
store = generate(SensorGraphSpec(n_observations=40, seed=5))
sharded = ShardedFactorizedGraph.partition(store, 3)
sub = sharded.snapshots[sid].fgraph.store
svc = OnlineCompactionService.durable(
    root, sub, checkpoint_every=3, checkpoint_async=False,
    detector="gfsp", backend="host", raw_residue_threshold=4,
    support_drift_threshold=3, retry_sleep=lambda _: None,
    fault_plan=FaultPlan("apply", occurrence=4, mode="kill"))
with open(batches_path) as f:
    batches = json.load(f)
for ins, dels in batches:
    svc.submit(inserts=[tuple(t) for t in ins], delete_entities=dels)
    svc.drain()
print("SURVIVED")          # the armed kill must preempt this line
"""


def test_shard_worker_sigkill_recovers_and_swaps_back(tmp_path):
    """One shard's durable worker dies by SIGKILL mid-soak (no atexit,
    no flush -- real process death).  The restart recovers it from its
    checkpoint + WAL, finishes the batch stream, and the recovered
    snapshot swaps back into the sharded graph with digest parity
    against a twin whose worker was never interrupted."""
    store = _store()
    sharded = ShardedFactorizedGraph.partition(store, 3)
    cid = int(store.classes()[0])
    sid = sharded.plan.shards_for_class(cid)[0]
    batches = _novel_batches(store, 8)
    bpath = tmp_path / "batches.json"
    bpath.write_text(json.dumps(batches))
    root = tmp_path / "shard_root"
    script = tmp_path / "worker.py"
    script.write_text(_SHARD_WORKER)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    proc = subprocess.run(
        [sys.executable, str(script), str(root), str(sid), str(bpath)],
        cwd=repo, env=env, capture_output=True, timeout=300)
    assert proc.returncode == -signal.SIGKILL, proc.stderr.decode()
    assert b"SURVIVED" not in proc.stdout

    # uninterrupted twin over the SAME shard sub-store.  It must be the
    # first thing minting into the parent dictionary so its novel-term
    # ids line up with the ids the dead worker journaled.
    from repro.core.triples import TripleStore
    twin_sub = TripleStore.from_ids(
        store.dict, sharded.snapshots[sid].fgraph.store.spo.copy(),
        presorted=True)
    twin = OnlineCompactionService(twin_sub, **_SVC_KW)
    for ins, dels in batches:
        twin.submit(inserts=ins, delete_entities=dels)
        twin.drain()

    # restart: recover the shard from disk, apply the journaled-but-
    # unapplied tail, then resubmit what the dead worker never saw
    svc = recover(str(root), **_SVC_KW)
    assert svc.last_recovery is not None
    assert svc.last_recovery.checkpoint_bytes > 0
    svc.drain()
    applied = svc.applied_seq + 1
    assert 0 < applied < len(batches)      # it really died mid-soak
    for ins, dels in batches[applied:]:
        svc.submit(inserts=ins, delete_entities=dels)
        svc.drain()
    svc.close()
    assert svc.queue.depth == 0
    assert svc.snapshot.digest() == twin.snapshot.digest()

    # the recovered shard swaps back in: one atomic tuple store, and
    # the whole sharded graph matches the never-interrupted twin world
    other = ShardedFactorizedGraph.partition(store, 3)
    sharded.swap_shard(sid, svc.snapshot)
    other.swap_shard(sid, twin.snapshot)
    assert sharded.digest() == other.digest()


# ---------------------------------------------------------------------------
# graceful degradation of the query service
# ---------------------------------------------------------------------------

def _query_service(**kw):
    from repro.api import Compactor
    store = _store(n=80, seed=3)
    comp = Compactor(detector="gfsp", backend="host")
    comp.run(store)
    fg = comp.fgraph
    cid, t = next(iter(sorted(fg.tables.items())))
    term = store.dict.term
    row = t.objects[0]
    arms = tuple((term(p), term(int(o))) for p, o in zip(t.props, row))
    svc = GraphQueryService(fg, **kw)
    return svc, arms, term(cid)


def test_admission_shed_on_full_queue():
    svc, arms, cterm = _query_service(max_pending=2)
    mk = lambda rid: GraphQueryRequest(rid=rid, arms=arms,
                                       class_term=cterm)
    assert svc.submit(mk(0)) and svc.submit(mk(1))
    assert not svc.submit(mk(2))         # full: shed, not queued
    assert svc.metrics.channel("admission.shed").count == 1
    out = svc.run()
    assert set(out) == {0, 1}
    assert all(r.status == "ok" for r in out.values())
    assert svc.submit(mk(3))             # wave drained: admission resumes


def test_wave_deadline_sheds_explicitly():
    tick = [0.0]

    def clock():
        tick[0] += 10.0
        return tick[0]

    svc, arms, cterm = _query_service(wave_deadline_s=5.0, clock=clock)
    for rid in range(3):
        svc.submit(GraphQueryRequest(rid=rid, arms=arms,
                                     class_term=cterm))
    out = svc.run()
    assert len(out) == 3                 # shed responses, never drops
    assert all(r.status == "shed" and r.n_rows == 0 for r in out.values())
    assert svc.metrics.channel("wave.deadline_shed").count == 3


def test_factorized_failure_falls_back_to_raw_with_parity():
    svc, arms, cterm = _query_service()
    reqs = [GraphQueryRequest(rid=rid, arms=arms, class_term=cterm)
            for rid in range(3)]
    for r in reqs:
        svc.submit(r)
    want = svc.run()

    svc2, _, _ = _query_service()

    def boom(*a, **k):
        raise RuntimeError("device lost")

    svc2.engine.query_batch = boom       # the batched path is dead
    for r in reqs:
        svc2.submit(dataclasses.replace(r))
    out = svc2.run()
    assert all(r.status == "degraded" and r.strategy == "raw"
               for r in out.values())
    ch = svc2.metrics.channel("wave.raw_fallback")
    assert ch.count == 1 and ch.total == 3      # counted, never silent
    for rid, r in out.items():
        assert r.n_rows == want[rid].n_rows
        assert sorted(r.subjects) == sorted(want[rid].subjects)


def test_bgp_fallback_marks_degraded_with_parity():
    from repro.serving import BGPQueryRequest
    svc, arms, cterm = _query_service()
    stars = (("?s", tuple((p, f"?o{i}") for i, (p, _) in
                          enumerate(arms[:2])), cterm),)
    svc.submit(BGPQueryRequest(rid=9, stars=stars))
    want = svc.run()[9]

    svc2, _, _ = _query_service()
    orig = svc2.engine.query_bgp
    tried = []

    def flaky(q, *, strategy, backend, return_stats):
        tried.append(strategy)
        if strategy != "raw":
            raise RuntimeError("kernel fault")
        return orig(q, strategy=strategy, backend="host",
                    return_stats=return_stats)

    svc2.engine.query_bgp = flaky
    svc2.submit(BGPQueryRequest(rid=9, stars=stars))
    out = svc2.run()[9]
    assert out.status == "degraded"
    assert tried == ["auto", "raw"]
    assert out.n_rows == want.n_rows
    assert sorted(out.rows) == sorted(want.rows)
    assert svc2.metrics.channel("wave.raw_fallback").count == 1
