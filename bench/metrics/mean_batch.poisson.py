"""Samples per forward inside the window: samples batched over forwards
run, the engine's counters differenced across the window."""


def read(ctx):
    s0, s1 = ctx.window["stats0"], ctx.window["stats1"]
    forwards = s1["forwards"] - s0["forwards"]
    if forwards <= 0:
        return None
    samples = (s1["mean_batch"] * s1["forwards"]
               - s0["mean_batch"] * s0["forwards"])
    return samples / forwards
