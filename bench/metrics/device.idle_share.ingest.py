"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of the device's op intervals) / window."""


def read(r):
    return 100.0 * r.trace.idle_share
