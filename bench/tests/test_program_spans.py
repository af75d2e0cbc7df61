"""The readers of the program's own spans (``bench/program_spans.py``):
on the recorded span log of small CPU runs, on hand-built logs whose
every number is known, and where the window cannot be found.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_program_spans.py
"""
from __future__ import annotations

import json
import os

import pytest

from bench import program_spans
from bench import run as R

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the program's own log, kept before ``conftest.py`` swaps in a recording
PROGRAM_RECORDS = program_spans.records
SPEC = R.load_spec()
COMPACT, INGEST = "sensor-d1d2d3.compact", "sensor-stream-d1d2d3.ingest"
READERS = {
    "store.build_ms": COMPACT, "store.index_ms": COMPACT,
    "detect.extract_ms": COMPACT, "sweep.host_ms": COMPACT,
    "factorize.self_ms": COMPACT, "compact.span_coverage": COMPACT,
    "ingest.queue_wait_p95_ms": INGEST, "ingest.apply_ms": INGEST,
    "ingest.checkpoint_wait_ms": INGEST, "wal.fsync_ms": INGEST,
    "ingest.span_coverage": INGEST,
}
MS = 1_000_000


class _Trace:
    """The part of a reduced trace these readers use."""

    def __init__(self, passes: int) -> None:
        self.passes = passes

    def span_count(self, name: str) -> int:
        return self.passes if name == "bench.pass" else 0


def _read(monkeypatch, name, rows, *, passes=0, steps=0):
    monkeypatch.setattr(program_spans, "records",
                        lambda: None if rows is None else list(rows))
    reading = R.Reading(trace=_Trace(passes),
                        counters={"turn_s": [0.5] * steps}, peaks={},
                        window_s=30.0)
    return R.load_module(os.path.join(BENCH, "metrics",
                                      name + ".py")).read(reading)


def _recorded(cell):
    with open(os.path.join(BENCH, "tests", "data", "spans.json")) as f:
        rec = json.load(f)
    part = rec["compact" if cell == COMPACT else "ingest"]
    return ([tuple(r) for r in part["spans"]],
            part.get("passes", 0), part.get("steps", 0))


def test_every_new_metric_is_declared_for_its_cell():
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    for name, cell in READERS.items():
        m = declared[name]
        assert m["source"] == "program_span" and m["workloads"] == [cell]
        assert not m["layer"].startswith("bench")


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_recorded_span_log(monkeypatch, name):
    rows, passes, steps = _recorded(READERS[name])
    v = _read(monkeypatch, name, rows, passes=passes, steps=steps)
    assert v is not None and v >= 0, name
    if name != "ingest.checkpoint_wait_ms":
        assert v > 0, name
    if name.endswith("coverage"):
        assert v <= 100.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_count_mismatch_reads_nothing(monkeypatch, name):
    """One pass or step more than the log holds: the window cannot be
    found, so nothing is read."""
    rows, passes, steps = _recorded(READERS[name])
    more = {"passes": passes + 1} if READERS[name] == COMPACT \
        else {"steps": steps + 1}
    assert _read(monkeypatch, name, rows, **more) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_without_program_spans_reads_nothing(monkeypatch, name):
    """A program that keeps no span log (an older commit) reads None."""
    assert _read(monkeypatch, name, None, passes=2, steps=5) is None


def _row(i, parent, name, start, end, key=None, thread=0):
    return (i, parent, name, key, thread, start * MS, end * MS)


# a warm pass whose numbers would show if it were counted, then one pass
# of the window; times in ms
COMPACT_LOG = [
    _row(1, None, "dict.build", 0, 10), _row(2, None, "store.build", 10, 60),
    _row(3, None, "compact.run", 60, 190),
    _row(4, 3, "compact.plan", 60, 100),
    _row(5, 4, "detect.extract", 60, 90, key=7),
    _row(6, 5, "store.index", 60, 80),
    _row(10, None, "dict.build", 200, 202),
    _row(11, None, "store.build", 202, 210),
    _row(12, None, "compact.run", 211, 300),
    _row(13, 12, "compact.plan", 211, 250),
    _row(14, 13, "detect.extract", 212, 222, key=7),
    _row(15, 14, "store.index", 212, 216),
    _row(16, 13, "sweep.upload", 222, 224, key=7),
    _row(17, 13, "sweep.batch", 224, 240, key=7),
    _row(18, 12, "compact.execute", 250, 300),
    _row(19, 18, "factorize.class", 250, 280, key=7),
    _row(20, 19, "store.build", 260, 270),
    _row(21, 18, "fgraph.build", 280, 298),
    _row(22, 21, "store.index", 285, 290),
]

# set-up's compaction, two steps of the window (the first starts a
# checkpoint on its own thread), then the check's reopening
INGEST_LOG = [
    _row(1, None, "compact.run", 0, 50),
    _row(2, None, "service.submit", 100, 104, key=0),
    _row(3, 2, "wal.append", 101, 102, key=0),
    _row(4, 2, "wal.fsync", 102, 104, key=0),
    _row(6, None, "queue.wait", 104, 110, key=0),
    _row(5, None, "service.step", 110, 150, key=0),
    _row(7, 5, "service.apply", 110, 130, key=0),
    _row(8, 7, "service.swap", 120, 121, key=0),
    _row(9, 5, "checkpoint.join", 130, 135, key=0),
    _row(10, 5, "checkpoint.write", 136, 180, key=0, thread=1),
    _row(11, None, "service.submit", 150, 156, key=1),
    _row(12, 11, "wal.fsync", 152, 156, key=1),
    _row(13, None, "service.submit", 157, 160, key=2),
    _row(14, 13, "wal.fsync", 158, 160, key=2),
    _row(16, None, "queue.wait", 156, 170, key=1),
    _row(17, None, "queue.wait", 160, 170, key=2),
    _row(15, None, "service.step", 170, 200, key=2),
    _row(18, 15, "service.apply", 170, 190, key=2),
    _row(21, None, "checkpoint.join", 290, 295, key=2),
    _row(19, None, "recovery.replay", 300, 400),
    _row(20, 19, "service.apply", 310, 390, key=2),
]

EXACT = [
    # the load's dictionary and sort, and factorization's rebuild
    ("store.build_ms", 2 + 8 + 10),
    ("store.index_ms", 4 + 5),
    # extraction less the index built inside it
    ("detect.extract_ms", 10 - 4),
    ("sweep.host_ms", 2 + 16),
    # compact.execute less the store layer's spans under it
    ("factorize.self_ms", 50 - 10 - 5),
    # 100 ms of pass: 211-212 and 240-250 are compact.plan's own time,
    # 210-211 lies between the load and compact.run
    ("compact.span_coverage", 86.0),
    # waits of 6, 14 and 10 ms, linear interpolation
    ("ingest.queue_wait_p95_ms", 13.6),
    ("ingest.apply_ms", (20 + 20) / 2),
    # the join after the window (close) is not a step's
    ("ingest.checkpoint_wait_ms", 5 / 2),
    ("wal.fsync_ms", (2 + 4 + 2) / 3),
    # children on the step's thread: 20 + 5 of 40 ms, 20 of 30 ms
    ("ingest.span_coverage", 100.0 * 45 / 70),
]


@pytest.mark.parametrize("name,want", EXACT)
def test_reader_on_hand_built_log(monkeypatch, name, want):
    if READERS[name] == COMPACT:
        v = _read(monkeypatch, name, COMPACT_LOG, passes=1)
    else:
        v = _read(monkeypatch, name, INGEST_LOG, steps=2)
    assert v == pytest.approx(want, rel=1e-12)


def test_records_reads_the_program_log():
    """``records()`` is the program's own ring, as plain tuples."""
    from repro.online.metrics import span
    with span("probe.records", key=3):
        pass
    last = PROGRAM_RECORDS()[-1]
    assert type(last) is tuple
    assert last[program_spans.NAME] == "probe.records"
    assert last[program_spans.KEY] == 3
