"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device numbers.

The harness opens a host annotation named ``WINDOW`` when the profiler
starts and closes it when the window's last work has finished; the
reduction reads the device inside it:

* busy seconds: the union of the intervals in which an operation ran on
  a device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  averaged over the devices; idle share is 1 - busy / window;
* device seconds per operation name (``op_key``);
* the longest idle gaps, each with the host annotation that overlapped it
  most (what the host was doing meanwhile).

    python bench/trace_reduce.py <trace dir>     # prints the planes,
                                                 # lines and op names
"""
from __future__ import annotations

import dataclasses
import re
import sys
from pathlib import Path

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    n_devices: int
    op_seconds: dict            # op_key -> device seconds in the window
    op_counts: dict             # op_key -> events in the window
    gaps: list                  # [(seconds, host annotation)] longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[h, s] for s, h in self.gaps[:10]]}


def op_key(name: str) -> str:
    """An op's name without its HLO text and numeric suffix:
    ``"%w4a4_conv2d_implicit.52 = f32[...] custom-call(...)"`` ->
    ``"w4a4_conv2d_implicit"``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)


def reduce_file(path: Path) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host = [(n, s, e) for pl in pd.planes if pl.name == HOST_PLANE
            for line in pl.lines for n, s, e in _events(line)]
    marks = [(s, e) for n, s, e in host if n == WINDOW]
    devices = [pl for pl in pd.planes if DEVICE_PLANE.match(pl.name)]
    if not devices:
        raise ValueError(f"no TPU device plane in {path}: planes "
                         f"{[pl.name for pl in pd.planes]}")
    ops_by_dev = []
    for pl in devices:
        lines = [ln for ln in pl.lines if ln.name == OPS_LINE]
        ops_by_dev.append([ev for ln in lines for ev in _events(ln)])
    if marks:
        w0, w1 = marks[0]
    else:
        w0 = min(s for ops in ops_by_dev for _, s, _ in ops)
        w1 = max(e for ops in ops_by_dev for _, _, e in ops)
    op_ns: dict[str, int] = {}
    op_n: dict[str, int] = {}
    busy_ns = 0
    gaps: list[tuple[int, int]] = []
    for ops in ops_by_dev:
        clipped = []
        for n, s, e in ops:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            n = op_key(n)
            op_ns[n] = op_ns.get(n, 0) + (e - s)
            op_n[n] = op_n.get(n, 0) + 1
        busy = _union(clipped)
        busy_ns += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps += [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                 if edges[k + 1] > edges[k]]
    # the harness's own annotations; "$file:line function" events are the
    # Python tracer's, too coarse to say what the host was doing
    named = [(n, s, e) for n, s, e in host
             if n != WINDOW and not n.startswith("$")]

    def doing(g0: int, g1: int) -> str:
        best, best_ov = "host: nothing traced", 0
        for n, s, e in named:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = n, ov
        return best

    gaps.sort(key=lambda g: g[0] - g[1])
    top = [((g1 - g0) / 1e9, doing(g0, g1)) for g0, g1 in gaps[:10]]
    nd = len(devices)
    return Reduction(window_s=(w1 - w0) / 1e9, busy_s=busy_ns / 1e9 / nd,
                     n_devices=nd,
                     op_seconds={n: v / 1e9 / nd for n, v in op_ns.items()},
                     op_counts=op_n, gaps=top)


def reduce_dir(trace_dir: Path) -> Reduction:
    return reduce_file(find_xplane(trace_dir))


def describe(path: Path, per_line: int = 12) -> None:
    """Print each plane's lines, their event counts and first op names
    with their stats: how to learn what a trace calls things."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    for pl in pd.planes:
        print(f"plane {pl.name!r}")
        for ln in pl.lines:
            evs = list(ln.events)
            print(f"  line {ln.name!r}: {len(evs)} events")
            seen = set()
            for ev in evs:
                if ev.name in seen or len(seen) >= per_line:
                    continue
                seen.add(ev.name)
                stats = {k: (str(v)[:160]) for k, v in ev.stats}
                print(f"    {ev.name!r} {ev.duration_ns} ns {stats}")


if __name__ == "__main__":
    describe(find_xplane(Path(sys.argv[1])))
