"""Device time per execution of the fused verify+draft program
(``fused_verify_and_draft``), from the profiler trace, in ms."""
from bench.xplane import matching

PROGRAM = r"fused_verify_and_draft"


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    n, t = matching(red.programs, PROGRAM)
    return t / n / 1e6 if n else None
