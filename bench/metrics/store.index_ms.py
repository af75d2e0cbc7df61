"""Host time of ``GraphIndex`` construction (``store.index`` spans:
the per-predicate CSR each new store builds on first use) per
compaction pass started in the window, in ms."""
from bench import program_spans as ps


def read(r):
    return ps.per_pass_ms(r, lambda log, p: log.outermost_ns(
        p, {"store.index"}))
