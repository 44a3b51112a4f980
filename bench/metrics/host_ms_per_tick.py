"""Host milliseconds per engine tick: the mean, over the window's ticks
that ran a forward, of the engine's ``tick`` span minus its ``forward``
child span (obs spans, engine clock)."""


def read(ctx):
    ticks = [e for e in ctx.spans("tick", in_window=True)
             if not e.get("args", {}).get("idle")]
    fwds = sorted(ctx.spans("forward", in_window=True), key=lambda e: e["ts"])
    if not ticks:
        return None
    own, k = [], 0
    for t in sorted(ticks, key=lambda e: e["ts"]):
        end = t["ts"] + t["dur"]
        child = 0.0
        while k < len(fwds) and fwds[k]["ts"] < t["ts"]:
            k += 1
        while k < len(fwds) and fwds[k]["ts"] + fwds[k]["dur"] <= end:
            child += fwds[k]["dur"]
            k += 1
        own.append(t["dur"] - child)
    return sum(own) / len(own) / 1e3
