"""95th percentile over the window's batches of ``queue.wait``, in ms:
from a batch's acknowledgement in ``submit`` to the start of the step
that applies it, the part of a write's visibility spent queued behind
earlier steps."""
from bench import program_spans as ps


def read(r):
    return ps.queue_wait_p95_ms(r)
