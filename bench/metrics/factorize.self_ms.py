"""Host time of factorization per compaction pass started in the
window, in ms: the ``compact.execute`` span and everything under it
(``factorize.class`` per class, ``fgraph.build``), less the store
layer's spans there (``store.build``, ``store.index``)."""
from bench import program_spans as ps


def read(r):
    return ps.per_pass_ms(r, ps.factorize_self_ns)
