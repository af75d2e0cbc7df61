"""Time the window's steps spent blocked on the previous background
checkpoint (``checkpoint.join``), per step, in ms."""
from bench import program_spans as ps


def read(r):
    return ps.per_step_ms(r, "checkpoint.join")
