"""GraphIndex substrate: index joins == full-graph scans, merge-on-append,
dedup skipping on provably-sorted paths, and the TermDict dtype contract."""
import numpy as np
import pytest

from repro.api import Compactor
from repro.core import sweep as core_sweep
from repro.core import triples as triples_mod
from repro.core.index import (GraphIndex, KEY_STATS, PSO_PERM, SPO_PERM,
                              in_sorted, merge_disjoint, rows_in_sorted,
                              setdiff_rows, sort_unique)
from repro.core.triples import TermDict, TripleStore
from repro.data.synthetic import SensorGraphSpec, generate


def _random_store(seed=0, n=250):
    return generate(SensorGraphSpec(n_observations=n, seed=seed))


# ---------------------------------------------------------------------------
# index joins reproduce the seed's scan semantics exactly
# ---------------------------------------------------------------------------

def _scan_entities(store, c):
    spo = store.spo
    m = (spo[:, 1] == store.TYPE) & (spo[:, 2] == c)
    return np.unique(spo[m, 0])


def test_index_matches_scans_on_sensor_graph():
    store = _random_store(seed=7)
    spo = store.spo
    for c in store.classes().tolist():
        ents = _scan_entities(store, c)
        np.testing.assert_array_equal(store.entities_of_class(c), ents)
        m = np.isin(spo[:, 0], ents)
        props = np.unique(spo[m, 1])
        props = props[(props != store.TYPE) & (props != store.INSTANCE_OF)]
        np.testing.assert_array_equal(store.class_properties(c), props)
        assert store.labeled_edge_count(c) == \
            int((m & (spo[:, 1] != store.TYPE)).sum())
        assert store.labeled_edge_count(c, props[:2]) == \
            int((m & np.isin(spo[:, 1], props[:2])).sum())


def test_object_matrix_join_excludes_incomplete_and_nonfunctional():
    t = [("c1", "rdf:type", "C"), ("c1", "p1", "e1"), ("c1", "p2", "e2"),
         ("c2", "rdf:type", "C"), ("c2", "p1", "e1"),            # misses p2
         ("c3", "rdf:type", "C"), ("c3", "p1", "a"), ("c3", "p1", "b"),
         ("c3", "p2", "e2")]                                     # p1 x2
    store = TripleStore.from_triples(t)
    C = store.dict.lookup("C")
    p1, p2 = store.dict.lookup("p1"), store.dict.lookup("p2")
    ents, objmat = store.object_matrix(C, [p1, p2])
    assert ents.tolist() == [store.dict.lookup("c1")]
    assert objmat.tolist() == [[store.dict.lookup("e1"),
                                store.dict.lookup("e2")]]
    with pytest.raises(ValueError, match="violate"):
        store.object_matrix(C, [p1, p2], strict=True)
    # unsorted property order is preserved column-wise
    ents2, objmat2 = store.object_matrix(C, [p2, p1])
    np.testing.assert_array_equal(objmat2[:, ::-1], objmat)


def test_pred_slice_is_sorted_vertical_partition():
    store = _random_store(seed=3, n=100)
    idx = store.index
    total = 0
    for p in idx.preds.tolist():
        sl = idx.pred_slice(p)
        total += sl.shape[0]
        assert (sl[:, 1] == p).all()
        key = sl[:, 0].astype(np.int64) << 32 | sl[:, 2]
        assert (np.diff(key) > 0).all()      # strictly (s, o)-sorted
    assert total == store.n_triples
    assert idx.pred_slice(10**6).shape[0] == 0


# ---------------------------------------------------------------------------
# merge primitives + merge-on-append
# ---------------------------------------------------------------------------

def test_merge_primitives_roundtrip():
    rng = np.random.default_rng(0)
    old = sort_unique(rng.integers(0, 40, (300, 3)).astype(np.int32))
    new = rng.integers(0, 40, (120, 3)).astype(np.int32)
    fresh = setdiff_rows(sort_unique(new), old)
    merged = merge_disjoint(old, fresh)
    expect = np.unique(np.concatenate([old, new]), axis=0)
    np.testing.assert_array_equal(merged, expect)
    # PSO order variant used by the index
    old_p = sort_unique(old, PSO_PERM)
    merged_p = merge_disjoint(old_p, setdiff_rows(
        sort_unique(new, PSO_PERM), old_p, PSO_PERM), PSO_PERM)
    assert merged_p.shape == expect.shape


def test_add_ids_merges_index_and_matches_rebuild():
    store = _random_store(seed=11, n=150)
    _ = store.index                      # force build, then merge into it
    rng = np.random.default_rng(1)
    extra = rng.integers(0, 400, (500, 3)).astype(np.int32)
    expect = np.unique(np.concatenate([store.spo, extra]), axis=0)
    store.add_ids(extra)
    np.testing.assert_array_equal(store.spo, expect)
    # the merged index answers like a fresh one
    fresh = GraphIndex(store.spo, store.TYPE, store.INSTANCE_OF)
    for c in store.classes().tolist():
        np.testing.assert_array_equal(store.entities_of_class(c),
                                      fresh.entities_of_class(c))
        np.testing.assert_array_equal(store.class_properties(c),
                                      fresh.class_properties(c))
    np.testing.assert_array_equal(store.index.rows, fresh.rows)


def test_merged_index_cache_carryover_is_safe():
    store = _random_store(seed=13, n=120)
    classes = store.classes().tolist()
    for c in classes:                    # warm every cache
        store.entities_of_class(c)
        store.class_properties(c)
    c0 = classes[0]
    ent0 = int(store.entities_of_class(c0)[0])
    # append a new property edge on an entity of c0 AND a new member
    newp = store.dict.id("p/appended")
    newe = store.dict.id("ent/appended")
    store.add_ids(np.array([[ent0, newp, ent0],
                            [newe, store.TYPE, c0]], np.int32))
    assert newp in store.class_properties(c0).tolist()
    assert newe in store.entities_of_class(c0).tolist()


def test_copy_shares_index_and_diverges_on_append():
    store = _random_store(seed=2, n=80)
    _ = store.index
    clone = store.copy()
    assert clone._index is store._index
    clone.add_ids(np.array([[5, store.TYPE, 7]], np.int32))
    assert clone._index is not store._index
    assert store.n_triples == clone.n_triples - 1


# ---------------------------------------------------------------------------
# row keys: the packed uint64 key and its wide fallback are byte-identical
# to a structured-key reference at every id width
# ---------------------------------------------------------------------------

_REF_DTYPE = np.dtype([("a", np.int32), ("b", np.int32), ("c", np.int32)])


def _ref_key(rows, perm):
    arr = np.ascontiguousarray(rows[:, list(perm)], dtype=np.int32)
    return arr.view(_REF_DTYPE).ravel()


def _ref_sort(rows, perm, unique):
    rows = np.asarray(rows, np.int32).reshape(-1, 3)
    rows = rows[np.argsort(_ref_key(rows, perm), kind="stable")]
    if unique and rows.shape[0]:
        keep = np.append(True, np.any(rows[1:] != rows[:-1], axis=1))
        rows = rows[keep]
    return rows


def _ref_in(rows, ref, perm):
    return np.isin(_ref_key(rows, perm), _ref_key(ref, perm))


def _ref_merge(old, new, perm):
    return _ref_sort(np.concatenate([old, new]), perm, unique=True)


# id ranges, one for all columns or one per (s, p, o) column, and the key
# path each must take: per-column bit widths summing to at most 64 pack
_WIDTHS = {"narrow": [(0, 2**19 - 1)], "boundary": [(0, 2**21 - 1)],
           "wide": [(0, 2**31 - 1)], "negative": [(-2**20, 2**20)],
           "x6": [(0, 2**22 - 1), (0, 2**4 - 1), (0, 2**22 - 1)],
           "sum64": [(0, 2**31 - 1), (0, 2**2 - 1), (0, 2**31 - 1)],
           "sum65": [(0, 2**31 - 1), (0, 2**3 - 1), (0, 2**31 - 1)]}
_PATH = {"narrow": "packed", "boundary": "packed", "wide": "wide",
         "negative": "wide", "x6": "packed", "sum64": "packed",
         "sum65": "wide"}


def _rows_at(rng, width, n, pool=24):
    """Rows over a small pool of ids per column spanning ``width``, both
    ends included, so duplicates and shared prefixes occur."""
    ids = np.stack([np.concatenate([[lo, hi], rng.integers(lo, hi, pool - 2)])
                    for lo, hi in _WIDTHS[width]], axis=1)
    idx = rng.integers(0, pool, (n, 3))
    return np.take_along_axis(ids, idx, axis=0).astype(np.int32)


def _case_rows(rng, width, shape, perm):
    if shape == "tiny":
        return [_rows_at(rng, width, 0), _rows_at(rng, width, 1)]
    rows = _rows_at(rng, width, 400)
    if shape == "duplicates":
        rows = np.concatenate([rows, rows[::3]])
        return [rows[rng.permutation(rows.shape[0])]]
    return [_ref_sort(rows, perm, unique=True)]           # already sorted


def _assert_same(got, want):
    assert got.dtype == np.int32 and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("shape", ["duplicates", "sorted", "tiny"])
@pytest.mark.parametrize("width", list(_WIDTHS))
@pytest.mark.parametrize("perm", [SPO_PERM, PSO_PERM], ids=["spo", "pso"])
def test_row_keys_match_structured_reference(perm, width, shape):
    rng = np.random.default_rng(sum(map(ord, width + shape)) + perm[0])
    other = _rows_at(rng, width, 300)
    old = _ref_sort(other, perm, unique=True)
    before = dict(KEY_STATS)
    for rows in _case_rows(rng, width, shape, perm):
        new = _ref_sort(rows, perm, unique=True)
        _assert_same(sort_unique(rows, perm), new)
        np.testing.assert_array_equal(rows_in_sorted(new, old, perm),
                                      _ref_in(new, old, perm))
        for a, b in ((new, old), (old, new)):
            fresh = setdiff_rows(a, b, perm)
            _assert_same(fresh, a[~_ref_in(a, b, perm)])
            _assert_same(merge_disjoint(b, fresh, perm),
                         _ref_merge(b, a, perm))
        # the index's (p, s, o) order keeps duplicates; merged dedups
        _assert_same(GraphIndex(rows, 0, 1).rows,
                     _ref_sort(rows, PSO_PERM, unique=False))
        _assert_same(GraphIndex(old, 0, 1).merged(rows).rows,
                     _ref_merge(old, rows, PSO_PERM))
    path = _PATH[width]
    other_path = "wide" if path == "packed" else "packed"
    assert KEY_STATS[path] > before[path]
    assert KEY_STATS[other_path] == before[other_path]


@pytest.mark.parametrize("old_hi", [2**19 - 1, 2**31 - 1],
                         ids=["packed", "wide"])
@pytest.mark.parametrize("perm", [SPO_PERM, PSO_PERM], ids=["spo", "pso"])
def test_row_keys_share_one_width_across_both_sides(perm, old_hi):
    """``new`` alone would pack at 5 bits a column; ``old`` needs 19 bits
    a column or the wide path.  Keyed apart, equal rows would get unequal
    keys."""
    rng = np.random.default_rng(old_hi % 97)
    new = sort_unique(rng.integers(0, 32, (200, 3)).astype(np.int32), perm)
    far = np.array([[old_hi, 3, 4], [5, old_hi, 6], [7, 8, old_hi]],
                   np.int32)
    old = _ref_sort(np.concatenate([new[::2], far]), perm, unique=True)
    before = dict(KEY_STATS)
    _assert_same(setdiff_rows(new, old, perm), new[1::2])
    _assert_same(merge_disjoint(old, new[1::2], perm),
                 _ref_merge(old, new, perm))
    assert rows_in_sorted(new, old, perm).tolist() == \
        [i % 2 == 0 for i in range(new.shape[0])]
    path = "packed" if old_hi < 2**21 else "wide"
    assert KEY_STATS[path] - before[path] == 3


def _with_ids_past(store, n_pad):
    """``store`` relabelled through a dictionary that puts its predicates
    first and ``n_pad`` filler terms before every other term, as the
    benchmark's generator lays out its schema: small predicate ids,
    subjects and objects past ``n_pad``."""
    terms = [store.dict.term(i) for i in range(len(store.dict))]
    low = list(dict.fromkeys([store.TYPE, store.INSTANCE_OF,
                              *np.unique(store.spo[:, 1]).tolist()]))
    order = low + sorted(set(range(len(terms))) - set(low))
    new_id = np.empty(len(terms), np.int32)
    new_id[order] = np.arange(len(terms), dtype=np.int32)
    new_id[order[len(low):]] += n_pad
    d = TermDict.from_terms([terms[i] for i in low]
                            + [f"ent/pad/{i}" for i in range(n_pad)]
                            + [terms[i] for i in order[len(low):]])
    return TripleStore.from_ids(d, new_id[store.spo])


def test_key_stats_count_paths_and_reset():
    """Ids in the benchmark's range (402,629 terms, under 2^19) take only
    the packed path, through a whole compaction, and so do subjects and
    objects past 2^21 beside small predicates (48-bit keys at 22 bits);
    an id at 2^21 alone still packs, while a row whose column widths sum
    past 64 bits, or a negative id, takes the wide path;
    ``reset_trace_stats`` zeroes both."""
    core_sweep.reset_trace_stats()
    assert KEY_STATS == {"packed": 0, "wide": 0}
    store = _random_store(seed=4, n=200)
    d = store.dict
    ents = d.ids([f"ent/pad/{i}" for i in range(402_629 - len(d))])
    assert int(ents[-1]) == 402_628
    c0 = int(store.classes()[0])
    store.add_ids(np.stack([ents[-50:], np.full(50, store.TYPE, np.int32),
                            np.full(50, c0, np.int32)], axis=1))
    Compactor(detector="gfsp", backend="host").run(store)
    assert KEY_STATS["packed"] > 0 and KEY_STATS["wide"] == 0
    for far, path in (([2**21, 0, 0], "packed"),
                      ([2**31 - 1, 2**31 - 1, 2**31 - 1], "wide"),
                      ([-1, 0, 0], "wide")):
        before = dict(KEY_STATS)
        grown = TripleStore.from_ids(
            store.dict, np.concatenate([store.spo, [far]]))
        assert grown.index.rows.shape[0] == store.n_triples + 1
        # the (s, p, o) and (p, s, o) sorts
        assert {k: KEY_STATS[k] - before[k] for k in KEY_STATS} == \
            {"packed": 2 * (path == "packed"), "wide": 2 * (path == "wide")}
    core_sweep.reset_trace_stats()
    assert KEY_STATS == {"packed": 0, "wide": 0}
    source = _random_store(seed=4, n=200)
    x6 = _with_ids_past(source, 2**21)
    assert int(x6.spo[:, [0, 2]].min()) >= 2**21
    assert int(x6.spo[:, 1].max()) < 2**4
    want = Compactor(detector="gfsp", backend="host").run(source)
    core_sweep.reset_trace_stats()
    got = Compactor(detector="gfsp", backend="host").run(x6)
    assert got.n_triples_after == want.n_triples_after < x6.n_triples
    assert KEY_STATS["packed"] > 0 and KEY_STATS["wide"] == 0
    core_sweep.reset_trace_stats()
    assert KEY_STATS == {"packed": 0, "wide": 0}


# ---------------------------------------------------------------------------
# dedup skipping (satellite): provably-sorted paths never re-dedup
# ---------------------------------------------------------------------------

def test_restrict_subjects_skips_dedup_and_matches_isin(monkeypatch):
    store = _random_store(seed=5, n=100)
    subs = store.entities_of_class(store.classes()[0].item())
    expect = store.spo[np.isin(store.spo[:, 0], subs)]

    calls = []
    orig = triples_mod.sort_unique

    def counting(rows, perm=SPO_PERM):
        calls.append(rows.shape[0])
        return orig(rows, perm)

    monkeypatch.setattr(triples_mod, "sort_unique", counting)
    sub = store.restrict_subjects(subs)
    np.testing.assert_array_equal(sub.spo, expect)
    assert calls == []                   # presorted slice: no dedup pass


def test_add_ids_dedups_only_the_appended_block(monkeypatch):
    store = _random_store(seed=6, n=100)
    n_before = store.n_triples
    rows = np.concatenate([store.spo[:10],                 # duplicates
                           np.array([[9, 9, 9]], np.int32)])
    calls = []
    orig = triples_mod.sort_unique

    def counting(r, perm=SPO_PERM):
        calls.append(r.shape[0])
        return orig(r, perm)

    monkeypatch.setattr(triples_mod, "sort_unique", counting)
    store.add_ids(rows)
    assert store.n_triples == n_before + 1
    assert calls and max(calls) == rows.shape[0]   # never the full graph


# ---------------------------------------------------------------------------
# TermDict dtype contract (satellite): minted ids match spo's int32
# ---------------------------------------------------------------------------

def test_termdict_ids_dtype_matches_spo():
    d = TermDict()
    got = d.ids([f"t/{i}" for i in range(10)])
    assert got.dtype == np.int32
    store = TripleStore()
    assert store.spo.dtype == got.dtype


def test_surrogate_minting_roundtrip_through_from_ids():
    """Regression: TermDict.ids used to return int64 while spo is int32 --
    minted surrogate rows silently upcast every concatenation.  The bulk-
    minted block must flow into from_ids/add_ids without casts and
    round-trip by name."""
    store = _random_store(seed=1, n=50)
    d = store.dict
    names = [f"repro:sg/test/{i}" for i in range(7)]
    sgs = d.ids(names)
    assert sgs.dtype == store.spo.dtype == np.int32
    c0 = int(store.classes()[0])
    rows = np.stack([sgs, np.full(7, store.TYPE, np.int32),
                     np.full(7, c0, np.int32)], axis=1)
    assert rows.dtype == np.int32        # no silent upcast in the stack
    g = TripleStore.from_ids(d, np.concatenate([store.spo, rows]))
    ents = g.entities_of_class(c0)
    assert np.isin(sgs, ents).all()
    assert [g.dict.term(int(s)) for s in sgs] == names
    # second mint of the same names is a pure lookup, same ids, same dtype
    again = d.ids(names)
    assert again.dtype == np.int32
    np.testing.assert_array_equal(again, sgs)
