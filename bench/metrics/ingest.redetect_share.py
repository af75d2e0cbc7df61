"""Share of the window spent in re-detection, in %: the service's own
``redetect.ms`` channel total over the window (0 when the window held
no re-detection)."""


def read(r):
    ms = r.counters.get("redetect_ms")
    return None if ms is None else 100.0 * ms / (r.window_s * 1e3)
