"""The reference's distinct-row primitives against the structured-key
versions they replaced, which this file keeps as its oracle: the same
answers at every id width and column count, and the same G.FSP on a
graph whose ids pass 21 bits."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import reference  # noqa: E402
from bench.gen import SensorGraph  # noqa: E402


def _old_key(rows: np.ndarray) -> np.ndarray:
    rows = np.ascontiguousarray(rows, np.int32)
    return rows.view([("", np.int32)] * rows.shape[1]).ravel()


def old_n_distinct_rows(mat: np.ndarray) -> int:
    if mat.shape[0] == 0:
        return 0
    return int(np.unique(_old_key(mat)).shape[0])


def old_rows_differ(a: np.ndarray, b: np.ndarray) -> int:
    ka, kb = np.unique(_old_key(a)), np.unique(_old_key(b))
    both = np.intersect1d(ka, kb).shape[0]
    return int(ka.shape[0] + kb.shape[0] - 2 * both)


WIDTHS = {"narrow": 2**19 - 1, "boundary": 2**21 - 1, "wide": 2**31 - 1}
KINDS = ("duplicates", "reordered", "disjoint", "one_empty", "one_row")


def _pair(kind: str, hi: int, k: int, seed: int):
    """Two ``(n, k)`` int32 matrices whose ids reach 0 and ``hi``."""
    r = np.random.default_rng(seed)
    base = r.integers(0, hi + 1, (400, k)).astype(np.int32)
    if k > 1:                               # a narrow column beside wide ones
        base[:, 1] = r.integers(0, 8, 400)
    base[0], base[1] = hi, 0
    base = np.unique(base, axis=0)
    n = base.shape[0]
    if kind == "duplicates":
        a = base[r.integers(0, n * 2 // 3, 600)]
        b = base[r.integers(n // 3, n, 500)]
    elif kind == "reordered":
        a = base
        b = base[r.permutation(np.repeat(np.arange(n), 2))]
    elif kind == "disjoint":
        a, b = base[: n // 2], base[n // 2:]
    elif kind == "one_empty":
        a, b = base[:0], base
    else:
        a, b = base[5:6], base[r.permutation(n)[: n // 2]]
    return a, b


@pytest.mark.parametrize("k", (1, 2, 3, 5))
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_distinct_rows_match_structured_keys(width, kind, k):
    a, b = _pair(kind, WIDTHS[width], k,
                 seed=len(KINDS) * k + KINDS.index(kind))
    for x, y in ((a, b), (b, a), (a, a)):
        assert reference.rows_differ(x, y) == old_rows_differ(x, y)
    for x in (a, b):
        assert reference.n_distinct_rows(x) == old_n_distinct_rows(x)
    if kind == "reordered":
        assert reference.rows_differ(a, b) == 0
    if kind == "disjoint":
        assert reference.rows_differ(a, b) == a.shape[0] + b.shape[0]
    if kind == "one_empty":
        assert reference.n_distinct_rows(a) == 0
        assert reference.rows_differ(a, b) == b.shape[0]


@pytest.mark.parametrize("top", (2**21 + 5000, 2**31 - 1))
def test_greedy_fsp_on_wide_ids_matches_structured_keys(top, monkeypatch):
    cfg = {"n_observations": 3000, "n_sensors": 20, "n_timestamps": 60,
           "n_values": 40, "zipf_a": 1.8}
    g = SensorGraph(cfg, seed=2**31 + 29)
    first = g.base["obs/"]
    spo = g.spo.astype(np.int64)
    spo[spo >= first] += top - (len(g.terms) - 1)
    spo = spo.astype(np.int32)
    assert spo.max() == top
    type_id, inst = g.id("rdf:type"), g.id("repro:instanceOf")
    got = reference.greedy_fsp(spo, type_id, inst)
    assert got == reference.greedy_fsp(g.spo, type_id, inst)
    monkeypatch.setattr(reference, "n_distinct_rows", old_n_distinct_rows)
    assert got == reference.greedy_fsp(spo, type_id, inst)
    assert got
