"""Device time of the detection sweep programs (``jit_sweep``) per
compaction pass started in the traced window, in ms."""


def read(r):
    ms = r.trace.module_seconds("jit_sweep") * 1e3
    n = r.trace.span_count("bench.pass")
    return ms / n if ms and n else None
