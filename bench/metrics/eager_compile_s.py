"""Seconds of JAX compiles in set-up outside the engine's compiled
forwards: the ``compile`` spans (trace, lowering, backend compile or
persistent-cache read of eager ops and the bank's merge and pack) before
the window that lie in no ``dispatch`` span, counted once where they nest.
The forwards' own compiles are in ``compile_s``. A program that does not
listen for compiles (no ``compile_listener`` mark) reads nothing."""
import tick_spans


def read(ctx):
    if not any(e.get("ph") == "i" and e.get("name") == "compile_listener"
               for e in ctx.events):
        return None
    return tick_spans.setup_seconds_outside(ctx, "compile", "dispatch")
