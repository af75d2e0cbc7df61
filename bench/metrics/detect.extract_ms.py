"""Host time of extraction per compaction pass started in the window,
in ms: the self time of the ``detect.extract`` spans (class statistics,
each class's object matrix and its star groups; an index built inside
one is ``store.index``'s)."""
from bench import program_spans as ps


def read(r):
    return ps.per_pass_ms(r, lambda log, p: sum(
        log.self_ns(s) for s in p if s[ps.NAME] == "detect.extract"))
