"""Host time inside the engine's ticks, from the program's obs spans.

The readers of the tick's phases (``bench/metrics/host_*_ms_per_tick.*``)
each name the spans they sum; ``eager_compile_s`` reads the compiles of
set-up. Spans are Chrome ``X`` events on the engine clock (us), on the
track (``tid``) of the thread that ran them. A program without such spans
reads ``None``.
"""
from __future__ import annotations

import bisect


def _key(e: dict) -> float:
    return e["ts"]


def ms_per_tick(ctx, names: tuple[str, ...], *, skip=None) -> float | None:
    """Mean host milliseconds, over the window's ticks that ran a forward,
    of the spans named ``names`` that lie inside the tick on its track.
    A tick holding a span for which ``skip`` is true is left out."""
    ticks = sorted((e for e in ctx.spans("tick", in_window=True)
                    if not e.get("args", {}).get("idle")), key=_key)
    inner: dict[int, list] = {}
    for name in names:
        for e in ctx.spans(name, in_window=True):
            inner.setdefault(e["tid"], []).append(e)
    if not ticks or not inner:
        return None
    for spans in inner.values():
        spans.sort(key=_key)
    at = dict.fromkeys(inner, 0)
    total, n = 0.0, 0
    for t in ticks:
        spans = inner.get(t["tid"], [])
        k, end = at.get(t["tid"], 0), t["ts"] + t["dur"]
        while k < len(spans) and spans[k]["ts"] < t["ts"]:
            k += 1
        held = []
        while k < len(spans) and spans[k]["ts"] + spans[k]["dur"] <= end:
            held.append(spans[k])
            k += 1
        at[t["tid"]] = k
        if skip is not None and any(skip(e) for e in held):
            continue
        total += sum(e["dur"] for e in held)
        n += 1
    return total / n / 1e3 if n else None


def _union_us(intervals: list[tuple[float, float]]) -> float:
    total, reach = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


def setup_seconds_outside(ctx, name: str, container: str) -> float:
    """Seconds of set-up covered by ``name`` spans that lie in no
    ``container`` span on their track: the union per track, so that
    spans nested in one another count once."""
    within: dict[int, list] = {}
    for e in sorted(ctx.spans(container, in_window=False), key=_key):
        within.setdefault(e["tid"], []).append(e)
    starts = {tid: [e["ts"] for e in es] for tid, es in within.items()}
    kept: dict[int, list] = {}
    for e in ctx.spans(name, in_window=False):
        es = within.get(e["tid"], [])
        k = bisect.bisect_right(starts.get(e["tid"], []), e["ts"]) - 1
        if k >= 0 and e["ts"] + e["dur"] <= es[k]["ts"] + es[k]["dur"]:
            continue
        kept.setdefault(e["tid"], []).append((e["ts"], e["ts"] + e["dur"]))
    return sum(_union_us(iv) for iv in kept.values()) / 1e6
