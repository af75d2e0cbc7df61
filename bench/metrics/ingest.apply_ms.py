"""Mean host time per step in the window of ``service.apply``, in ms:
building the successor snapshot of the applied batches (insert and
delete applies, surrogate mints, swap, journaled apply record, drift
counters)."""
from bench import program_spans as ps


def read(r):
    return ps.per_step_ms(r, "service.apply")
