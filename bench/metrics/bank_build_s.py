"""Seconds of weight-bank builds (TALoRA merge + FP4 pack) in set-up: the
sum of the bank's ``bank_build`` spans before the window."""


def read(ctx):
    spans = ctx.spans("bank_build", in_window=False)
    return sum(e["dur"] for e in spans) / 1e6 if spans else None
