"""Program spans (``repro.online.metrics.span`` / ``SPANS``): nesting
and parent ids, self time, a cause named across threads, the bounded
ring, channels fed by a span, the profiler's host plane, and the spans
the compaction and online paths record at their layer boundaries."""
from __future__ import annotations

import glob
import os
import threading

import numpy as np
import pytest

from repro.api import Compactor
from repro.data.synthetic import SensorGraphSpec, generate
from repro.online import MetricsHub, OnlineCompactionService, recover
from repro.online.metrics import SPANS, SpanLog, span


def _by_name(log):
    return {r.name: r for r in log.records()}


def _last_id() -> int:
    return max((r.id for r in SPANS.records()), default=0)


def _since(mark: int):
    """The process-wide spans recorded after ``mark`` (the ring may be
    full of earlier tests' spans, so count by id, not by position)."""
    return [r for r in SPANS.records() if r.id > mark]


def test_nested_spans_name_their_parent():
    log = SpanLog()
    with log.span("outer", key=7) as outer:
        assert log.current() == outer.id
        with log.span("inner") as inner:
            assert log.current() == inner.id
        with log.span("sibling"):
            pass
    assert log.current() is None
    got = _by_name(log)
    assert got["outer"].parent is None and got["outer"].key == 7
    assert got["inner"].parent == got["outer"].id
    assert got["sibling"].parent == got["outer"].id
    # the ring holds spans in the order they ended
    assert [r.name for r in log.records()] == ["inner", "sibling", "outer"]
    for r in log.records():
        assert r.start_ns <= r.end_ns
        assert r.thread == threading.get_ident()


def test_self_time_excludes_children_on_the_same_thread():
    log = SpanLog()
    for _ in range(2):
        with log.span("outer"):
            with log.span("inner"):
                sum(range(20000))
            sum(range(20000))
    recs = log.records()
    dur = {}
    for r in recs:
        dur.setdefault(r.name, []).append(r.end_ns - r.start_ns)
    s = log.summary()
    assert s["outer"]["count"] == s["inner"]["count"] == 2
    assert s["outer"]["total_ms"] == pytest.approx(sum(dur["outer"]) / 1e6)
    assert s["outer"]["self_ms"] == pytest.approx(
        (sum(dur["outer"]) - sum(dur["inner"])) / 1e6)
    assert s["inner"]["self_ms"] == s["inner"]["total_ms"]
    assert 0 < s["outer"]["self_ms"] < s["outer"]["total_ms"]


def test_cause_names_a_span_on_another_thread():
    log = SpanLog()
    with log.span("step") as step:
        def work():
            with log.span("background", cause=step.id):
                sum(range(50000))
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    got = _by_name(log)
    bg, st = got["background"], got["step"]
    assert bg.parent == st.id and bg.thread != st.thread
    # time spent on another thread is not taken off the step's self time
    s = log.summary()
    assert s["step"]["self_ms"] == s["step"]["total_ms"]


def test_ring_drops_the_oldest_records_at_capacity():
    log = SpanLog(capacity=3)
    for i in range(5):
        with log.span(f"s{i}", key=i):
            pass
    recs = log.records()
    assert [r.name for r in recs] == ["s2", "s3", "s4"]
    assert [r.key for r in recs] == [2, 3, 4]
    assert [r.id for r in recs] == sorted(r.id for r in recs)
    # the aggregates count every span, the ring keeps the newest
    assert sum(v["count"] for v in log.summary().values()) == 5


def test_record_logs_a_span_measured_elsewhere():
    log = SpanLog()
    sid = log.record("queue.wait", 1_000, 5_000, key=np.int64(4), parent=9)
    (r,) = log.records()
    assert (r.id, r.parent, r.name, r.key) == (sid, 9, "queue.wait", 4)
    assert type(r.key) is int
    assert log.summary()["queue.wait"] == {
        "count": 1, "total_ms": 0.004, "self_ms": 0.004}


def test_span_feeds_a_channel_once_it_completes():
    log, hub = SpanLog(), MetricsHub()
    with log.span("apply", hub=hub, channel="ingest.batch_ms") as sp:
        sum(range(10000))
    ch = hub.channel("ingest.batch_ms")
    assert ch.count == 1 and ch.last == pytest.approx(sp.ms)
    r = log.records()[-1]
    assert ch.last == (r.end_ns - r.start_ns) / 1e6
    with pytest.raises(RuntimeError):
        with log.span("apply", hub=hub, channel="ingest.batch_ms"):
            raise RuntimeError("failed apply")
    # a failed region is a span but no completed work in the channel
    assert ch.count == 1 and len(log.records()) == 2


def test_span_sits_in_the_profilers_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("probe.profiler"):
            sum(range(10000))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:CPU")
             for line in plane.lines for e in line.events}
    assert "probe.profiler" in names


def _store(n=60, seed=3):
    return generate(SensorGraphSpec(n_observations=n, seed=seed))


COMPACT_SPANS = {"dict.build", "store.build", "store.index", "compact.run",
                 "compact.plan", "detect.extract", "sweep.upload",
                 "sweep.batch", "compact.execute", "factorize.class",
                 "fgraph.build"}


def test_compaction_records_its_layer_spans():
    from repro.core.triples import TermDict, TripleStore
    g = _store()
    mark = _last_id()
    store = TripleStore.from_ids(
        TermDict.from_terms(g.dict.term(i) for i in range(len(g.dict))),
        g.spo)
    comp = Compactor(detector="gfsp", backend="device")
    comp.run(store)
    recs = _since(mark)
    assert COMPACT_SPANS <= {r.name for r in recs}
    by_id = {r.id: r for r in recs}
    (run,) = [r for r in recs if r.name == "compact.run"]
    for r in recs:
        if r.name in ("dict.build",) or r.start_ns < run.start_ns:
            continue
        # everything from the plan on hangs under the run
        top = r
        while top.parent is not None:
            top = by_id[top.parent]
        assert top.id == run.id, r.name
    # per-class spans carry the class id
    assert {r.key for r in recs if r.name == "factorize.class"} == \
        set(comp.snapshot.fgraph.tables)


ONLINE_SPANS = {"service.submit", "queue.wait", "service.step",
                "service.apply", "service.swap", "service.redetect",
                "checkpoint.join", "checkpoint.write", "wal.append",
                "wal.fsync", "recovery.replay"}


def _novel_inserts(store, i):
    """Three complete entities of the first class with novel objects:
    raw residue that makes the class drift."""
    term = store.dict.term
    cid = int(store.classes()[0])
    pterms = [term(int(p)) for p in store.class_properties(cid)]
    out = []
    for j in range(3):
        s = f"e:n/b{i}/{j}"
        out.append((s, term(store.TYPE), term(cid)))
        out += [(s, p, f"o:novel/b{i}/{j}/{k}")
                for k, p in enumerate(pterms)]
    return out


def test_online_service_records_its_layer_spans(tmp_path):
    kw = dict(detector="gfsp", backend="host", raw_residue_threshold=4,
              support_drift_threshold=3, retry_sleep=lambda _: None)
    g = _store()
    root = str(tmp_path / "root")
    svc = OnlineCompactionService.durable(root, g, checkpoint_every=2,
                                          **kw)
    mark = _last_id()
    seqs = []
    for i in range(6):
        seqs.append(svc.submit(inserts=_novel_inserts(g, i)).seq)
        svc.step()
    svc.close()
    recover(root, **kw).close()
    recs = _since(mark)
    assert ONLINE_SPANS <= {r.name for r in recs}
    waits = [r for r in recs if r.name == "queue.wait"]
    steps = [r for r in recs if r.name == "service.step"]
    assert sorted(r.key for r in waits) == seqs
    # a batch waits from its acknowledgement to its step's start
    step_start = {r.key: r.start_ns for r in steps}
    submit_end = {r.key: r.end_ns for r in recs if r.name == "service.submit"}
    for r in waits:
        assert r.start_ns == submit_end[r.key]
        assert r.end_ns == step_start[r.key]
    by_id = {r.id: r for r in recs}
    for r in recs:
        if r.name == "checkpoint.write" and r.parent is not None:
            assert by_id[r.parent].name == "service.step"
            assert r.thread != by_id[r.parent].thread
    hub = svc.metrics.summary()
    assert hub["ingest.batch_ms"]["count"] == len(
        [r for r in recs if r.name == "service.apply"
         and by_id.get(r.parent, r).name == "service.step"])


def test_spans_add_nothing_reachable_from_a_snapshot():
    """The ring and its records stay outside the graph: nothing a
    snapshot holds refers to them (``graph_bytes_per_triple`` counts
    every object reachable from a snapshot)."""
    import types
    comp = Compactor(detector="gfsp", backend="host")
    comp.run(_store())
    assert SPANS.records()
    seen, stack = set(), [comp.snapshot]
    forbidden = {id(SPANS), id(SPANS._ring)}
    while stack:
        obj = stack.pop()
        if id(obj) in seen or isinstance(obj, (
                types.ModuleType, type, types.FunctionType, int, float,
                str, bytes, type(None), np.ndarray)):
            continue
        seen.add(id(obj))
        assert id(obj) not in forbidden
        assert not isinstance(obj, SpanLog)
        if isinstance(obj, dict):
            stack += list(obj.keys()) + list(obj.values())
        elif isinstance(obj, (list, tuple, set, frozenset)):
            stack += list(obj)
        else:
            if hasattr(obj, "__dict__"):
                stack.append(vars(obj))
            for cls in type(obj).__mro__:
                for slot in getattr(cls, "__slots__", ()):
                    if hasattr(obj, slot):
                        stack.append(getattr(obj, slot))
