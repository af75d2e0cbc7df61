"""Plain evaluators over raw triples: the benchmark's reference.

Nothing here imports the program.  Each function is the textbook
reading of the semantics the program implements, on ``(n, 3)`` int id
rows:

* :func:`greedy_fsp` -- the paper's G.FSP (Algorithm 2) with the
  Def. 4.8 objective, over entities complete and functional over the
  class's properties (§4.3 (a), (b)); ties go to the first candidate,
  an AMI of 1 wins at once, and a class is planned only if it saves an
  edge.
* :func:`layout` / :func:`expand` -- what a factorized graph G' says:
  per class its star pattern and molecule count, and the graph it
  stands for (every ``instanceOf`` member takes back its surrogate's
  edges).
* :class:`Triples` -- raw triples by predicate, with sorted lookups.
"""
from __future__ import annotations

import numpy as np


def _distinct(mat: np.ndarray) -> np.ndarray:
    """The distinct rows of an ``(n, k)`` integer matrix, sorted: one
    ``np.lexsort`` over the columns, then each row that differs from the
    one before it."""
    mat = mat[np.lexsort(mat.T[::-1])]
    keep = np.ones(mat.shape[0], bool)
    keep[1:] = (mat[1:] != mat[:-1]).any(axis=1)
    return mat[keep]


def n_distinct_rows(mat: np.ndarray) -> int:
    return int(_distinct(mat).shape[0])


def rows_differ(a: np.ndarray, b: np.ndarray) -> int:
    """Size of the symmetric difference of two row sets: twice their
    union less the sizes of both."""
    da, db = _distinct(a), _distinct(b)
    union = _distinct(np.concatenate([da, db]))
    return int(2 * union.shape[0] - da.shape[0] - db.shape[0])


class Triples:
    """Raw triples as per-predicate (subject, object) columns, sorted by
    subject."""

    def __init__(self, spo: np.ndarray, type_id: int) -> None:
        spo = np.asarray(spo, np.int32)
        self.type_id = int(type_id)
        self.by_p: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        order = np.lexsort((spo[:, 2], spo[:, 0], spo[:, 1]))
        spo = spo[order]
        cuts = np.flatnonzero(np.diff(spo[:, 1])) + 1
        for part in np.split(spo, cuts):
            if part.shape[0]:
                self.by_p[int(part[0, 1])] = (part[:, 0].copy(),
                                              part[:, 2].copy())

    def preds(self) -> list[int]:
        return sorted(self.by_p)

    def subjects_with(self, p: int, o: int) -> np.ndarray:
        s, ob = self.by_p.get(int(p), (np.empty(0, np.int32),) * 2)
        return np.unique(s[ob == o])

    def of_class(self, c: int) -> np.ndarray:
        return self.subjects_with(self.type_id, c)


def greedy_fsp(spo: np.ndarray, type_id: int, instance_of_id: int
               ) -> dict[int, tuple[tuple[int, ...], int]]:
    """G.FSP over every class: ``{class: (SP, AMI)}`` for the classes a
    compaction plans (predicted Def. 4.8 saving of at least one edge)."""
    tr = Triples(spo, type_id)
    out = {}
    classes = np.unique(tr.by_p[type_id][1]) if type_id in tr.by_p else []
    for c in classes.tolist():
        ents = tr.of_class(c)
        am = int(ents.shape[0])
        props = []
        for p in tr.preds():
            if p in (type_id, instance_of_id):
                continue
            s = tr.by_p[p][0]
            if np.isin(ents, s).any():
                props.append(p)
        n_s = len(props)
        if n_s < 2 or am == 0:
            continue
        # entities complete and functional over S
        cols, ok = [], np.ones(am, bool)
        for p in props:
            s, ob = tr.by_p[p]
            lo = np.searchsorted(s, ents, "left")
            hi = np.searchsorted(s, ents, "right")
            ok &= (hi - lo) == 1
            cols.append(ob[np.minimum(lo, s.shape[0] - 1)])
        mat = np.stack(cols, axis=1)[ok]

        def edges(cur: list[int]) -> tuple[int, int]:
            a = n_distinct_rows(mat[:, cur])
            return a, a * (len(cur) + 1) + am * (n_s - len(cur))

        cur = list(range(n_s))
        a, e = edges(cur)
        while len(cur) >= 3 and a != 1:
            kids = [edges(cur[:j] + cur[j + 1:]) for j in range(len(cur))]
            ones = [j for j, (ka, _) in enumerate(kids) if ka == 1]
            j = ones[0] if ones else min(range(len(kids)),
                                         key=lambda i: (kids[i][1], i))
            if kids[j][1] >= e:
                break
            cur = cur[:j] + cur[j + 1:]
            a, e = kids[j]
        if len(cur) < 2 or am * n_s - e < 1:
            continue
        out[int(c)] = (tuple(props[i] for i in cur), a)
    return out


def _surrogates(gp: np.ndarray, instance_of_id: int) -> np.ndarray:
    return np.unique(gp[gp[:, 1] == instance_of_id, 2])


def layout(gp: np.ndarray, type_id: int, instance_of_id: int
           ) -> dict[int, tuple[tuple[int, ...], int]]:
    """``{class: (star pattern, molecule count)}`` read off G'."""
    sg = _surrogates(gp, instance_of_id)
    rows = gp[np.isin(gp[:, 0], sg)]
    out: dict[int, tuple[set, int]] = {}
    cls_of = {int(s): int(o) for s, p, o in rows[rows[:, 1] == type_id]}
    props: dict[int, set] = {}
    for s, p in rows[rows[:, 1] != type_id][:, :2].tolist():
        props.setdefault(cls_of.get(s, -1), set()).add(p)
    counts: dict[int, int] = {}
    for c in cls_of.values():
        counts[c] = counts.get(c, 0) + 1
    for c, n in counts.items():
        out[c] = (tuple(sorted(props.get(c, ()))), n)
    if -1 in props:                      # a surrogate with no class
        out[-1] = (tuple(sorted(props[-1])), 0)
    return out


def expand(gp: np.ndarray, instance_of_id: int) -> np.ndarray:
    """The graph G' stands for: members take back their surrogate's
    edges; surrogate rows and ``instanceOf`` links disappear."""
    gp = np.asarray(gp, np.int32)
    sg = _surrogates(gp, instance_of_id)
    inst = gp[gp[:, 1] == instance_of_id]
    of_sg = np.isin(gp[:, 0], sg)
    keep = gp[(gp[:, 1] != instance_of_id) & ~of_sg]
    srows = gp[of_sg]
    srows = srows[np.argsort(srows[:, 0], kind="stable")]
    lo = np.searchsorted(srows[:, 0], inst[:, 2], "left")
    hi = np.searchsorted(srows[:, 0], inst[:, 2], "right")
    cnt = hi - lo
    src = np.repeat(np.arange(inst.shape[0]), cnt)
    pos = np.repeat(lo - np.cumsum(cnt) + cnt, cnt) + np.arange(int(cnt.sum()))
    moved = srows[pos].copy()
    moved[:, 0] = inst[src, 0]
    return np.concatenate([keep, moved], axis=0)
