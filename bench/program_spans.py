"""Which of the program's own spans belong to a run's measured window.

The program records a span at each layer boundary in the process-wide
ring ``repro.online.metrics.SPANS``; each record is ``(id, parent,
name, key, thread, start_ns, end_ns)`` on ``time.perf_counter_ns()``.
The readers under ``bench/metrics/`` that name ``program_span`` as
their source read that ring in the benchmark's own process, after the
window, through the rules here.  The window is never guessed from the
clock: it is found from the program's root spans and the number of
passes or steps the benchmark counted, and where they do not match
(the ring dropped them, or the program records no spans) a reader
returns ``None``.

* compact: the window's N passes (N = the ``bench.pass`` spans in the
  trace) are the spans after the end of the (N+1)-th-last
  ``compact.run`` root; the one before them is set-up's warm pass.
  Pass k holds the spans that start after the end of root k-1 and up
  to the end of root k: the load (``dict.build``, ``store.build``),
  then ``compact.run``.
* ingest: the window's steps are the last ``len(turn_s)``
  ``service.step`` roots (``check()`` reopens the service through
  ``recover()``, which applies through ``apply_exact``, not ``step()``).
"""
from __future__ import annotations

import bisect

import numpy as np

ID, PARENT, NAME, KEY, THREAD, START, END = range(7)

# phases, not layers: their own time is no layer's
CONTAINERS = frozenset({"compact.run", "compact.plan", "compact.execute"})
STORE = frozenset({"dict.build", "store.build", "store.index"})


def records() -> list[tuple] | None:
    """The program's span log, or ``None`` where it keeps none."""
    try:
        from repro.online import metrics
    except ImportError:
        return None
    log = getattr(metrics, "SPANS", None)
    return None if log is None else [tuple(r) for r in log.records()]


def dur(r) -> int:
    return r[END] - r[START]


class Log:
    """Parent and child links over a list of span records."""

    def __init__(self, rows) -> None:
        self.rows = [tuple(r) for r in rows]
        self.by_id = {r[ID]: r for r in self.rows}
        self.children: dict[int, list[tuple]] = {}
        for r in self.rows:
            if r[PARENT] is not None:
                self.children.setdefault(r[PARENT], []).append(r)

    def roots(self, name: str) -> list[tuple]:
        return sorted((r for r in self.rows
                       if r[NAME] == name and r[PARENT] is None),
                      key=lambda r: r[START])

    def descendants(self, roots) -> list[tuple]:
        out, stack = [], list(roots)
        while stack:
            r = stack.pop()
            kids = self.children.get(r[ID], [])
            out += kids
            stack += kids
        return out

    def has_ancestor_in(self, r, names) -> bool:
        p = self.by_id.get(r[PARENT])
        while p is not None:
            if p[NAME] in names:
                return True
            p = self.by_id.get(p[PARENT])
        return False

    def self_ns(self, r) -> int:
        """Duration less what its children on the same thread cover."""
        return dur(r) - sum(dur(c) for c in self.children.get(r[ID], [])
                            if c[THREAD] == r[THREAD])

    def outermost_ns(self, rows, names) -> int:
        """Total of the spans named ``names`` that no other such span
        encloses (nothing counted twice)."""
        return sum(dur(r) for r in rows
                   if r[NAME] in names and not self.has_ancestor_in(r, names))


# -- compact --------------------------------------------------------------

def compact_passes(rows, n: int) -> tuple[Log, list[list[tuple]]] | None:
    """The window's ``n`` passes, each its list of spans."""
    if rows is None or n <= 0:
        return None
    log = Log(rows)
    roots = sorted(log.roots("compact.run"), key=lambda r: r[END])
    if len(roots) < n + 1:
        return None
    bounds = [r[END] for r in roots[-(n + 1):]]
    passes: list[list[tuple]] = [[] for _ in range(n)]
    for r in log.rows:
        i = bisect.bisect_left(bounds, r[START]) - 1
        if 0 <= i < n and r[START] <= bounds[i + 1]:
            passes[i].append(r)
    return log, passes


def per_pass_ms(reading, fn) -> float | None:
    """Mean over the window's passes of ``fn(log, spans)`` ns, in ms."""
    got = compact_passes(records(), reading.trace.span_count("bench.pass"))
    if got is None:
        return None
    log, passes = got
    return sum(fn(log, p) for p in passes) / len(passes) / 1e6


def pass_ns(p) -> int:
    """Host time of one pass: the load's start to ``compact.run``'s end."""
    run = [r for r in p if r[NAME] == "compact.run" and r[PARENT] is None]
    return run[-1][END] - min(r[START] for r in p)


def covered_ns(log: Log, p) -> int:
    """Time of the pass inside a span of some layer: the outermost
    spans that are not phases (``CONTAINERS``)."""
    return sum(dur(r) for r in p if r[NAME] not in CONTAINERS and (
        r[PARENT] is None or log.by_id[r[PARENT]][NAME] in CONTAINERS))


def compact_coverage(reading) -> float | None:
    got = compact_passes(records(), reading.trace.span_count("bench.pass"))
    if got is None:
        return None
    log, passes = got
    return 100.0 * sum(covered_ns(log, p) for p in passes) \
        / sum(pass_ns(p) for p in passes)


def factorize_self_ns(log: Log, p) -> int:
    """``compact.execute`` and everything under it, less the store
    layer's spans there (they have their own metrics)."""
    execs = [r for r in p if r[NAME] == "compact.execute"]
    return sum(dur(r) for r in execs) - log.outermost_ns(
        log.descendants(execs), STORE)


# -- ingest ---------------------------------------------------------------

def ingest_steps(rows, n: int) -> tuple[Log, list[tuple]] | None:
    """The window's ``n`` ``service.step`` roots."""
    if rows is None or n <= 0:
        return None
    log = Log(rows)
    steps = log.roots("service.step")
    if len(steps) < n:
        return None
    return log, steps[-n:]


def _steps(reading):
    return ingest_steps(records(), len(reading.counters.get("turn_s", ())))


def per_step_ms(reading, name: str) -> float | None:
    """Total of ``name`` under the window's steps, per step, in ms."""
    got = _steps(reading)
    if got is None:
        return None
    log, steps = got
    return log.outermost_ns(log.descendants(steps), {name}) \
        / len(steps) / 1e6


def queue_waits(log: Log, steps) -> list[tuple]:
    """``queue.wait`` of every batch a window step applied (each ends
    where its step starts)."""
    starts = {r[START] for r in steps}
    return [r for r in log.rows
            if r[NAME] == "queue.wait" and r[END] in starts]


def queue_wait_p95_ms(reading) -> float | None:
    got = _steps(reading)
    if got is None:
        return None
    waits = queue_waits(*got)
    if not waits:
        return None
    return float(np.percentile([dur(r) for r in waits], 95)) / 1e6


def submit_fsync_ms(reading) -> float | None:
    """Mean ``wal.fsync`` inside the submits of the window's batches."""
    got = _steps(reading)
    if got is None:
        return None
    log, steps = got
    seqs = {r[KEY] for r in queue_waits(log, steps)}
    submits = [r for r in log.roots("service.submit") if r[KEY] in seqs]
    syncs = [r for r in log.descendants(submits) if r[NAME] == "wal.fsync"]
    if not syncs:
        return None
    return sum(dur(r) for r in syncs) / len(syncs) / 1e6


def step_coverage(reading) -> float | None:
    """Share of the window's step time inside the steps' child spans."""
    got = _steps(reading)
    if got is None:
        return None
    log, steps = got
    covered = sum(dur(c) for s in steps for c in log.children.get(s[ID], [])
                  if c[THREAD] == s[THREAD])
    return 100.0 * covered / sum(dur(s) for s in steps)
