"""Seconds of the engine's first forward per program in set-up (trace,
compile or read back from the persistent cache, and dispatch): the sum of
``forward`` spans marked ``compiled`` before the window."""


def read(ctx):
    spans = [e for e in ctx.spans("forward", in_window=False)
             if e.get("args", {}).get("compiled")]
    return sum(e["dur"] for e in spans) / 1e6 if spans else None
