"""Host milliseconds per engine tick spent batching: the ``batch`` spans
(rows concatenated, ``t``/``y`` arrays, padding to the bucket) and
``unbatch`` spans (eps sliced back into rows) inside each window tick
that ran a forward (obs spans, engine clock)."""
import tick_spans


def read(ctx):
    return tick_spans.ms_per_tick(ctx, ("batch", "unbatch"))
