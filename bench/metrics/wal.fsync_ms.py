"""Mean ``wal.fsync`` inside the submits of the window's batches, in
ms: the inside view of ``wal.submit_ms``."""
from bench import program_spans as ps


def read(r):
    return ps.submit_fsync_ms(r)
