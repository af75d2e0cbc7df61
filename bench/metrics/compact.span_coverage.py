"""Share of the window's compaction passes, from each load's start to
its ``compact.run``'s end, that a layer's span covers, in %.  The phase
spans ``compact.run``, ``compact.plan`` and ``compact.execute`` do not
count: their own time is host work no layer span names."""
from bench import program_spans as ps


def read(r):
    return ps.compact_coverage(r)
