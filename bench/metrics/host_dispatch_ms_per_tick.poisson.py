"""Host milliseconds per engine tick in the call into the compiled
forward: the ``dispatch`` spans inside each window tick that ran a
forward, ticks that compiled one left out (obs spans, engine clock)."""
import tick_spans


def read(ctx):
    return tick_spans.ms_per_tick(
        ctx, ("dispatch",),
        skip=lambda e: e.get("args", {}).get("compiled"))
