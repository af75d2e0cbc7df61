"""Device time of the sharded sweep's exchanges (the ``all_to_all`` of
``core/distributed.py``: HLO ops of opcode ``all-to-all``, which the TPU
compiler names ``%all_to_all.<n>``) per chip and per compaction pass
started in the traced window, in ms: the ops' time summed over the
cell's chips, over the number of chips and of ``bench.pass`` spans."""
from bench import common
from bench.trace import op_name


def _exchange(text: str) -> bool:
    return "all-to-all(" in text or \
        op_name(text).replace("_", "-") == "all-to-all"


def read(r):
    s = sum(e.dur for e in r.trace.ops if _exchange(e.name))
    n = r.trace.span_count("bench.pass")
    chips = len(common.DEVICES)
    return s * 1e3 / chips / n if s and n and chips else None
