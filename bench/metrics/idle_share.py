"""Share of the window in which no operation ran on the device (%):
1 - (union of the device's op intervals) / window, from the profiler trace."""


def read(ctx):
    if ctx["busy_s"] is None:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["trace"].window_s)
