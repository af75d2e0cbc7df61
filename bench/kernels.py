"""Bytes a kernel call needs, from the shapes in its trace event.

A device op event of the profiler carries the HLO instruction, e.g.
``%sig_hash.1 = u32[8,262144,2]{2,1,0:T(8,128)} custom-call(s32[8,262144,8]
{2,1,0:T(8,128)} %x), ...``.  The call reads each operand once and
writes its result once, so its least HBM traffic is the bytes of those
arrays at their logical shapes (lane padding is the layout's cost, not
the algorithm's).  An array placed in on-chip memory (layout memory
space ``S(1)``) moves no HBM bytes, so an HBM roofline does not bound
such a call: the functions return ``None`` for it.
"""
from __future__ import annotations

import re

_ITEM = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}
_ARRAY = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\](\{[^}]*\})?")


def _arrays(text: str) -> list[tuple[int, bool]]:
    """(bytes, on-chip) of each array shape in ``text``."""
    out = []
    for dtype, dims, layout in _ARRAY.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((n * _ITEM[dtype], "S(1)" in (layout or "")))
    return out


def call_hbm_bytes(event_text: str) -> int | None:
    """HBM bytes of one custom call: its result plus its operands.
    ``None`` when any of them sits in on-chip memory or the text holds
    no custom call."""
    head, sep, rest = event_text.partition(" custom-call(")
    if not sep:
        return None
    operands = rest.split(")", 1)[0]
    arrays = _arrays(head.partition("=")[2]) + _arrays(operands)
    if not arrays or any(on_chip for _, on_chip in arrays):
        return None
    return sum(b for b, _ in arrays)
