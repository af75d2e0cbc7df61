"""Device time of the ``seg_boundaries`` Pallas kernel
(``kernels/seg_count.py``) per compaction pass started in the traced
window, in ms."""


def read(r):
    s = sum(e.dur for e in r.trace.ops
            if e.name.lstrip("%").startswith("seg_boundaries"))
    n = r.trace.span_count("bench.pass")
    return s * 1e3 / n if s and n else None
