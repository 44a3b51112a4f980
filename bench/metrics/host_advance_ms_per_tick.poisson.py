"""Host milliseconds per engine tick advancing the samplers: the
``advance`` span (guidance combination, each request's sampler step,
retirement) inside each window tick that ran a forward (obs spans,
engine clock)."""
import tick_spans


def read(ctx):
    return tick_spans.ms_per_tick(ctx, ("advance",))
