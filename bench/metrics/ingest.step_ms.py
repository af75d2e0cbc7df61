"""Mean host time of one ``OnlineCompactionService.step()``, in ms (the
benchmark's span around the call)."""


def read(r):
    s = r.counters.get("turn_s")
    return 1e3 * sum(s) / len(s) if s else None
