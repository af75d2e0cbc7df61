"""Share of the window's ``service.step`` time that the steps' child
spans cover, in %."""
from bench import program_spans as ps


def read(r):
    return ps.step_coverage(r)
