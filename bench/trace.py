"""Reduce a JAX profiler trace to the benchmark's device numbers.

* device busy time: the union of the intervals of the ``XLA Ops`` line
  of each ``/device:TPU:<n>`` plane, clipped to the measured window,
  averaged over the chips;
* operation time by name: the HLO instruction's name without its
  ``%`` and ``.<n>`` suffix (a Pallas kernel appears by its kernel name,
  the sort as ``sort``), summed;
* program time by name: the ``XLA Modules`` line (``jit_sweep``, ...);
* idle gaps: the stretches inside the window in which no operation ran,
  each named by the innermost benchmark span (``bench.*``
  ``TraceAnnotation``) that covers its middle on the host.

The window is the ``bench.window`` span the harness opens around the
measured loop.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s*=|$)")


def op_name(text: str) -> str:
    """Short name of an HLO op event (``%sig_hash.1 = u32[...]`` ->
    ``sig_hash``)."""
    m = _OP_NAME.match(text.strip())
    return m.group(1) if m else text.split(" ", 1)[0]


def module_name(text: str) -> str:
    """``jit_sweep(8252747027281712896)`` -> ``jit_sweep``."""
    return text.split("(", 1)[0]


def union_length(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """Stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


@dataclasses.dataclass
class Event:
    name: str
    start: float        # seconds
    dur: float


@dataclasses.dataclass
class Reduced:
    """What the metric readers see of one traced window."""

    window_s: float
    busy_s: float                      # averaged over the chips
    ops: list[Event]                   # device op events, all chips
    modules: list[Event]               # device program events
    spans: list[Event]                 # benchmark host spans
    named_gaps: list[list] = dataclasses.field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for e in self.ops:
            k = op_name(e.name)
            out[k] = out.get(k, 0.0) + e.dur
        return out

    def module_seconds(self, prefix: str) -> float:
        return sum(e.dur for e in self.modules
                   if module_name(e.name) == prefix)

    def span_count(self, name: str) -> int:
        return sum(1 for e in self.spans if e.name == name)

    def top_ops(self, n: int = 10) -> list[list]:
        s = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in s[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        return self.named_gaps[:n]


def _span_at(spans: list[Event], t: float) -> str:
    best = None
    for e in spans:
        if e.name == WINDOW_SPAN or not (e.start <= t <= e.start + e.dur):
            continue
        if best is None or e.dur < best.dur:
            best = e
    return best.name if best is not None else "host.other"


def reduce_events(device: dict[int, tuple[list[Event], list[Event]]],
                  spans: list[Event]) -> Reduced:
    """``device``: per chip, (op events, module events)."""
    win = [e for e in spans if e.name == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(win)}")
    lo, hi = win[0].start, win[0].start + win[0].dur
    busy, all_ops, all_mods, named_gaps = [], [], [], []
    for chip, (ops, mods) in sorted(device.items()):
        ops = [e for e in ops if e.start + e.dur > lo and e.start < hi]
        iv = [(max(e.start, lo), min(e.start + e.dur, hi)) for e in ops]
        busy.append(union_length(iv))
        all_ops += ops
        all_mods += [e for e in mods if e.start + e.dur > lo and e.start < hi]
        for a, b in gaps(iv, lo, hi):
            named_gaps.append([_span_at(spans, (a + b) / 2), b - a])
    if not device:
        raise ValueError("the trace holds no TPU device plane")
    return Reduced(window_s=hi - lo, busy_s=sum(busy) / len(busy),
                  ops=all_ops, modules=all_mods,
                  spans=[e for e in spans if lo <= e.start <= hi],
                  named_gaps=sorted(named_gaps, key=lambda g: -g[1]))


def _events(line) -> list[Event]:
    return [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def reduce_dir(log_dir: str, span_prefix: str = "bench.") -> Reduced:
    """Reduce the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    device: dict[int, tuple[list[Event], list[Event]]] = {}
    spans: list[Event] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = _events(line)
                elif line.name == "XLA Modules":
                    mods = _events(line)
            device[int(m.group(1))] = (ops, mods)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e.name.startswith(span_prefix)]
    return reduce_events(device, spans)
