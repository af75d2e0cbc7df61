"""Roofline share of the ``sig_hash`` Pallas kernel, in %: the HBM bytes
its calls need (``bench/kernels.py``, from the shapes in each trace
event) over the chip's HBM peak, over the kernel's device time.  The
kernel hashes int32 rows with integer VPU work, for which no peak is
published, so HBM bandwidth is the bound.  Nothing is read when a call
keeps an operand in on-chip memory (no HBM bound holds then)."""
from bench.kernels import call_hbm_bytes


def read(r):
    calls = [e for e in r.trace.ops
             if e.name.lstrip("%").startswith("sig_hash")]
    if not calls:
        return None
    nbytes = [call_hbm_bytes(e.name) for e in calls]
    secs = sum(e.dur for e in calls)
    if None in nbytes or secs <= 0:
        return None
    return 100.0 * sum(nbytes) / r.peaks["hbm_bytes_per_s"] / secs
