"""Mean host time of one ``submit()`` (journal append and fsync), in
ms (the benchmark's span around the call)."""


def read(r):
    s = r.counters.get("submit_s")
    return 1e3 * sum(s) / len(s) if s else None
