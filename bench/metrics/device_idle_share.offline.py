"""Share of the traced window in which no operation ran on the device,
1 - busy / window from the profiler trace, in %."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red.window_ns <= 0:
        return None
    return 100.0 * (1.0 - red.busy_ns / red.window_ns)
