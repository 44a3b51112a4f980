"""Share of in-flight requests that the window's ticks served: the
engine's ``served_request_ticks`` over its ``inflight_request_ticks``
(each summed at every tick's selection), both differenced across the
window. The rest waited in flight for another segment's turn."""


def read(ctx):
    s0, s1 = ctx.window["stats0"], ctx.window["stats1"]
    if "served_request_ticks" not in s1:
        return None
    inflight = s1["inflight_request_ticks"] - s0["inflight_request_ticks"]
    if inflight <= 0:
        return None
    served = s1["served_request_ticks"] - s0["served_request_ticks"]
    return 100.0 * served / inflight
