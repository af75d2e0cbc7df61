"""The readers of program spans read a recorded span log in these
tests, as the trace readers read a recorded trace: the log of small CPU
runs of both cells in ``data/spans.json``.  ``compact`` holds set-up's
warm pass and the two passes that match ``data/trace_compact.json``'s
two ``bench.pass`` spans; ``ingest`` holds set-up, a window of
``steps`` steps, and the check's reopening of the service."""
from __future__ import annotations

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def recorded_spans() -> dict:
    with open(os.path.join(BENCH, "tests", "data", "spans.json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def recorded_program_spans(monkeypatch):
    """Readers see the recorded compact log in place of this process's
    own spans (which hold whatever earlier tests ran)."""
    from bench import program_spans
    rows = [tuple(r) for r in recorded_spans()["compact"]["spans"]]
    monkeypatch.setattr(program_spans, "records", lambda: rows)
    return rows
