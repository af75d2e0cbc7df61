"""Host time of the store layer's builds per compaction pass started in
the window, in ms: every ``dict.build`` and ``store.build`` span (the
load's term dictionary and (s, p, o) sort, and the store rebuilds in
factorization), from the program's own spans."""
from bench import program_spans as ps


def read(r):
    return ps.per_pass_ms(r, lambda log, p: log.outermost_ns(
        p, {"dict.build", "store.build"}))
