"""Device time of the mesh-sharded detection sweep programs
(``jit_sharded_sweep``, ``core/sweep.py``) per chip and per compaction
pass started in the traced window, in ms: the programs' time summed over
the cell's chips, over the number of chips and of ``bench.pass`` spans."""
from bench import common


def read(r):
    ms = r.trace.module_seconds("jit_sharded_sweep") * 1e3
    n = r.trace.span_count("bench.pass")
    chips = len(common.DEVICES)
    return ms / chips / n if ms and n and chips else None
