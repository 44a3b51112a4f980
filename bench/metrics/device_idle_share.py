"""Share of the traced window in which no operation ran on the device:
1 - busy / window, from the profiler trace (``trace_reduce``)."""


def read(ctx):
    red = ctx.reduction
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * red.idle_share
