"""The program's observability surface: accumulator channels and spans.

One :class:`Channel` per measured quantity (queue depth, batch latency,
recompaction latency, per-class savings, swap count, ...): ``observe``
records the latest value and folds it into the running count / total /
min / max, so a dashboard (or the bench snapshot) can read both "what
happened this batch" and "how has it gone overall" off the same surface
without the service keeping history lists.  The hub is just a name ->
channel map with auto-vivification; channels are cheap enough that
callers never pre-register.

:func:`span` marks a region at a layer boundary (store build,
extraction, sweep dispatch, factorization, WAL, service step, ...).
One ``with span(name, key=...)`` does three things:

* opens a ``jax.profiler.TraceAnnotation`` when JAX is loaded, so the
  region sits in the profiler's host plane on the device planes' clock
  (a TensorBoard or Perfetto trace shows it; without a profiler session
  the annotation costs well under a microsecond);
* appends ``(id, parent, name, key, thread, start_ns, end_ns)`` on
  ``time.perf_counter_ns()`` to the process-wide bounded ring
  :data:`SPANS` (the oldest records drop out first);
* folds the duration into per-name aggregates (count, total, self time
  = duration less what its children on the same thread cover),
  :meth:`SpanLog.summary`.

The parent is the innermost open span on the same thread; a span that
starts on another thread names its cause with ``cause=`` (the
checkpoint writer names the step that started it).  ``key`` is what the
spans of one request share: the batch seq on ingest, the class id in
compaction.  With ``hub=``/``channel=`` the duration in ms is also
``observe``d into that channel when the block completes, so a channel
timing exactly the span's region needs no clock of its own.  Records
hold only ints and strings and nothing in the graph refers to them.
Spans belong at layer boundaries -- per class, descent, batch or step
-- never per row.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import sys
import threading
import time
from typing import NamedTuple


@dataclasses.dataclass
class Channel:
    """One metric stream: last observed value plus running aggregates."""

    name: str
    last: float = 0.0
    count: int = 0
    total: float = 0.0
    min: float = 0.0
    max: float = 0.0

    def observe(self, value: float) -> None:
        v = float(value)
        self.last = v
        if self.count == 0:
            self.min = self.max = v
        else:
            self.min = min(self.min, v)
            self.max = max(self.max, v)
        self.count += 1
        self.total += v

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def summary(self) -> dict:
        return {"last": self.last, "count": self.count,
                "total": self.total, "min": self.min, "max": self.max,
                "mean": self.mean}


class MetricsHub:
    """Name -> :class:`Channel` map with observe-creates semantics."""

    def __init__(self) -> None:
        self.channels: dict[str, Channel] = {}

    def channel(self, name: str) -> Channel:
        ch = self.channels.get(name)
        if ch is None:
            ch = self.channels[name] = Channel(name=name)
        return ch

    def observe(self, name: str, value: float) -> Channel:
        ch = self.channel(name)
        ch.observe(value)
        return ch

    def summary(self) -> dict[str, dict]:
        return {name: ch.summary()
                for name, ch in sorted(self.channels.items())}


class Span(NamedTuple):
    """One finished span; times on ``time.perf_counter_ns()``."""

    id: int
    parent: int | None
    name: str
    key: int | None
    thread: int
    start_ns: int
    end_ns: int


class SpanLog:
    """Bounded ring of finished spans plus per-name aggregates.

    Appends need no lock: ``deque.append`` and ``next`` on a counter are
    atomic under the interpreter lock, and each thread folds its
    aggregates into a dict of its own (registered once, under a lock,
    on the thread's first span), which :meth:`summary` merges.
    """

    def __init__(self, capacity: int = 65536) -> None:
        self.capacity = int(capacity)
        self._ring: collections.deque[Span] = collections.deque(
            maxlen=self.capacity)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._aggs: list[dict[str, list[int]]] = []

    def _thread(self):
        """This thread's open-span stack and aggregate dict."""
        loc = self._local
        try:
            return loc.stack, loc.aggs
        except AttributeError:
            loc.stack, loc.aggs = [], {}
            with self._lock:
                self._aggs.append(loc.aggs)
            return loc.stack, loc.aggs

    def span(self, name: str, *, key=None, hub: "MetricsHub | None" = None,
             channel: str | None = None, cause: int | None = None
             ) -> "_OpenSpan":
        return _OpenSpan(self, name, key, hub, channel, cause)

    def current(self) -> int | None:
        """Id of the innermost span open on this thread."""
        stack, _ = self._thread()
        return stack[-1].id if stack else None

    def record(self, name: str, start_ns: int, end_ns: int, *, key=None,
               parent: int | None = None) -> int:
        """Log a span measured elsewhere: a stretch that ended before
        it could be opened (a batch's wait in the queue)."""
        _, aggs = self._thread()
        sid = next(self._ids)
        dur = end_ns - start_ns
        _fold(aggs, name, dur, dur)
        self._ring.append(Span(sid, parent, name, _key(key),
                               threading.get_ident(), start_ns, end_ns))
        return sid

    def records(self) -> list[Span]:
        """The retained spans, in the order they ended."""
        return list(self._ring)

    def summary(self) -> dict[str, dict]:
        """Per name since the log was made: ``count``, ``total_ms`` and
        ``self_ms``."""
        with self._lock:
            aggs = list(self._aggs)
        out: dict[str, list[int]] = {}
        for a in aggs:
            for name, (n, total, own) in list(a.items()):
                acc = out.setdefault(name, [0, 0, 0])
                acc[0] += n
                acc[1] += total
                acc[2] += own
        return {name: {"count": n, "total_ms": total / 1e6,
                       "self_ms": own / 1e6}
                for name, (n, total, own) in sorted(out.items())}


def _key(key):
    return None if key is None else int(key)


def _fold(aggs: dict, name: str, dur: int, own: int) -> None:
    a = aggs.get(name)
    if a is None:
        aggs[name] = [1, dur, own]
    else:
        a[0] += 1
        a[1] += dur
        a[2] += own


class _OpenSpan:
    """The context manager :func:`span` returns.  ``key`` may be set
    inside the block (a batch's seq is known once it is queued)."""

    __slots__ = ("log", "name", "key", "hub", "channel", "id", "parent",
                 "start_ns", "end_ns", "child_ns", "_ann")

    def __init__(self, log, name, key, hub, channel, cause) -> None:
        self.log = log
        self.name = name
        self.key = key
        self.hub = hub
        self.channel = channel
        self.parent = cause
        self.child_ns = 0
        self.end_ns = None
        self._ann = None

    def __enter__(self) -> "_OpenSpan":
        stack, _ = self.log._thread()
        if self.parent is None and stack:
            self.parent = stack[-1].id
        self.id = next(self.log._ids)
        prof = sys.modules.get("jax.profiler")
        if prof is not None:
            self._ann = prof.TraceAnnotation(self.name)
            self._ann.__enter__()
        stack.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = self.end_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        log = self.log
        stack, aggs = log._thread()
        stack.pop()
        dur = end - self.start_ns
        if stack:
            stack[-1].child_ns += dur
        _fold(aggs, self.name, dur, dur - self.child_ns)
        log._ring.append(Span(self.id, self.parent, self.name,
                              _key(self.key), threading.get_ident(),
                              self.start_ns, end))
        if self.hub is not None and exc[0] is None:
            self.hub.observe(self.channel, dur / 1e6)

    @property
    def ms(self) -> float:
        """Duration in ms: final once the block has exited, the time
        so far inside it."""
        end = self.end_ns if self.end_ns is not None \
            else time.perf_counter_ns()
        return (end - self.start_ns) / 1e6


#: the process-wide span log every :func:`span` writes to
SPANS = SpanLog()


def span(name: str, *, key=None, hub: MetricsHub | None = None,
         channel: str | None = None, cause: int | None = None
         ) -> _OpenSpan:
    """``with span("store.build"): ...`` -- see the module docstring."""
    return SPANS.span(name, key=key, hub=hub, channel=channel, cause=cause)
