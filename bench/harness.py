"""One run of one cell: set-up, the measured window, the check, the result.

Driven by data: the cell's entry in ``BENCHMARK.json`` names its
configuration file (``bench/configs/``) and its traffic mix
(``bench/traffic/<mix>.json``); each per-layer metric is read by
``bench/metrics/<metric>.py``. Adding a cell, a configuration or a metric
adds files and entries and edits none.

The system under test is the program's serving stack, driven as its
launcher drives it: ``DiffusionServingEngine`` over a ``WeightBank`` of
the configuration's TALoRA-merged, FP4-packed weights, with per-tensor
FP4 activation quantizers at every site. The benchmark gives it weights,
adapters and a pinned routing segmentation made from the seed, and the
requests of the traffic mix.

``run`` does not look for a chip; ``run.py`` does, before calling it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import heapq
import importlib.util
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import check
import trace_reduce
import traffic as traffic_mod
import unet_ref as ref

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# how long the traced run records the device, at the end of its window
TRACE_SECONDS = 4.0
# how long after the window closes its due requests may still finish
DRAIN_SECONDS = 60.0


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    cfg: dict
    mix: dict
    spec: dict

    @property
    def end_to_end(self) -> list[dict]:
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    @property
    def per_layer(self) -> list[dict]:
        moved = {m["name"] for m in self.end_to_end}
        return [m for m in self.spec["per_layer"]
                if self.name in m.get("workloads", [self.name])
                and m["moves"] in moved]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(wl)})")
    conf = {c["name"]: c for c in spec["configs"]}[wl[name]["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    return Cell(name, wl[name], cfg, traffic_mod.load(wl[name]["traffic"]),
                spec)


def load_reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# compiles, counted
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts programs traced for compilation (a jit cache miss, whether the
    persistent cache then has it or not)."""

    _instance = None

    def __init__(self):
        self.n = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._on_event)
        return cls._instance

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.n += 1


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def build(cell: Cell, seed: int, obs):
    """(engine, bank): the program's serving stack on the cell's
    configuration, every routing segment built."""
    from repro.core.talora import TALoRAConfig
    from repro.diffusion.schedule import make_schedule
    from repro.launch.serve_diffusion import fp4_act_qps
    from repro.nn.unet import UNetConfig, io_sites
    from repro.serving import (DiffusionServingEngine, WeightBank,
                               default_serving_plan)

    cfg, m, tl = cell.cfg, cell.cfg["model"], cell.cfg["talora"]
    act = cfg["quant"]["act_format"]
    if (act["exp_bits"], act["man_bits"]) != (2, 1):
        raise ValueError("the serving path quantizes activations to E2M1")
    ucfg = UNetConfig(
        image_size=m["image_size"], in_ch=m["in_ch"], out_ch=m["out_ch"],
        ch=m["ch"], ch_mult=tuple(m["ch_mult"]),
        num_res_blocks=m["num_res_blocks"],
        attn_resolutions=tuple(m["attn_resolutions"]),
        num_classes=m.get("num_classes"), gn_groups=m.get("gn_groups", 32))
    params, hubs = ref.make_weights(seed, cfg)
    flat = ref.flat(params)
    plan = default_serving_plan({k: flat[k] for k in hubs},
                                io_sites=io_sites(params))
    sched = make_schedule(cfg["schedule"]["kind"], cfg["T"],
                          beta_start=cfg["schedule"]["beta_start"],
                          beta_end=cfg["schedule"]["beta_end"])
    bank = WeightBank(params, plan, hubs, {},
                      TALoRAConfig(hub_size=tl["hub_size"], rank=tl["rank"],
                                   alpha=tl["alpha"]),
                      cfg["T"], max_cached=tl["segments"],
                      signatures=ref.signatures(seed, cfg))
    if bank.n_segments != tl["segments"]:
        raise RuntimeError(f"{bank.n_segments} routing segments, configured "
                           f"{tl['segments']}")
    engine = DiffusionServingEngine(ucfg, sched, bank,
                                    act_qps=fp4_act_qps(act["maxval"]),
                                    max_batch=cfg["max_batch"], obs=obs)
    # The configuration states float32: the merge's adapter product runs
    # at it, not at the TPU's default one-pass bfloat16.
    with jax.default_matmul_precision("highest"):
        for s in range(bank.n_segments):
            bank.prefetch(s, block=True)
    return engine, bank


def warm_up(engine, cell: Cell) -> None:
    """Serve one wave of each of the mix's batch sizes, so that every
    program and shape the window meets is compiled before it opens."""
    mix = cell.mix
    labelled = mix["labels"] is not None
    for n in mix["warm_batches"]:
        for k in range(n):
            engine.submit(steps=min(mix["steps"]), eta=mix["eta"],
                          sampler=mix["sampler"], seed=k,
                          y=k if labelled else None,
                          guidance_scale=mix["guidance"] if labelled else 0.0)
        engine.run()
    engine.results.clear()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


class Window:
    """Drives the engine with the mix's requests for ``seconds`` and keeps
    what the metrics and the check need."""

    def __init__(self, engine, cell: Cell, seed: int, seconds: float,
                 trace_dir: Path | None):
        self.engine, self.cell, self.seconds = engine, cell, seconds
        self.mix = cell.mix
        self.requests = traffic_mod.Requests(
            self.mix, seed, cell.cfg["model"].get("num_classes"))
        self.open = self.mix["loop"] == "open"
        self.schedule = self.requests.schedule(seconds) if self.open else None
        self.tracked_ordinals = self.requests.tracked(self.schedule)
        self.trace_dir = trace_dir
        self.due: dict[int, float] = {}          # rid -> due (window clock)
        self.done: dict[int, float] = {}         # rid -> finished (window clock)
        self.ordinal: dict[int, int] = {}        # rid -> ordinal
        self.states: dict[int, object] = {}      # rid -> RequestState
        self.tracks: dict[int, list] = {}        # rid -> [x_0, x_1, ...]
        self.parts: dict[int, list] = {}         # rid -> batch of each step
        self.next_ordinal = 0
        self.ordinal_pending = -1
        engine.on_submit.append(self._on_submit)
        self.annotate = (jax.profiler.TraceAnnotation if trace_dir is not None
                         else lambda name: contextlib.nullcontext())

    def _on_submit(self, rs) -> None:
        self.states[rs.req.rid] = rs
        if self.ordinal_pending in self.tracked_ordinals:
            self.tracks[rs.req.rid] = [rs.state.x]
            self.parts[rs.req.rid] = []

    def submit(self, req: traffic_mod.Req, due: float) -> None:
        self.ordinal_pending = req.ordinal
        rid = self.engine.submit(steps=req.steps, eta=self.mix["eta"],
                                 sampler=self.mix["sampler"], seed=req.seed,
                                 y=req.y, guidance_scale=req.guidance)
        self.due[rid] = due
        self.ordinal[rid] = req.ordinal

    def _record(self, before: dict, ticked: list) -> None:
        """Each tracked request's new state, and the sizes of the two
        partitions (unlabelled rows, labelled rows) of the tick's batch."""
        members = [rs for rs in (*self.engine.batcher.inflight, *ticked)
                   if rs.n_evals > before.get(id(rs), 0)]
        guided = [rs.req.guidance_scale > 0 for rs in members]
        part = (sum(g or rs.req.y is None for g, rs in zip(guided, members)),
                sum(g or rs.req.y is not None for g, rs in zip(guided, members)))
        for rid, xs in self.tracks.items():
            rs = self.states[rid]
            while len(xs) <= rs.n_evals:
                xs.append(rs.state.x)
                self.parts[rid].append(part)

    def _finish(self, finished, clock) -> list:
        if not finished:
            return []
        jax.block_until_ready([rs.x0 for rs in finished])
        now = clock()
        for rs in finished:
            self.done[rs.req.rid] = now
        return finished

    def _busy(self) -> bool:
        b = self.engine.batcher
        return bool(b.inflight or b.pending)

    def run(self) -> dict:
        engine, seconds, ann = self.engine, self.seconds, self.annotate
        counter = CompileCounter.get()
        stats0 = engine.stats()
        compiles0 = counter.n
        # requests not yet sent, by due time: the open loop's schedule, or
        # each closed-loop client's next request
        queue = list(self.schedule or [])
        if not self.open:
            queue = [self.requests.closed(k, 0.0)
                     for k in range(self.mix["clients"])]
            self.next_ordinal = len(queue)
        heapq.heapify(queue := [(r.due, r.ordinal, r) for r in queue])
        trace_from = max(0.0, seconds - TRACE_SECONDS)
        traced = mark = None
        t0 = time.perf_counter()

        def clock() -> float:
            return time.perf_counter() - t0

        def send(until: float) -> None:
            with ann("submit"):
                while queue and queue[0][0] <= until:
                    req = heapq.heappop(queue)[2]
                    self.submit(req, req.due)

        def tick() -> None:
            before = {id(rs): rs.n_evals for rs in engine.batcher.inflight}
            with ann("engine.tick"):
                ticked = engine.tick()
            with ann("wait_for_finished"):
                finished = self._finish(ticked, clock)
            self._record(before, finished)
            if not self.open:
                for rs in finished:
                    due = self.done[rs.req.rid] + self.mix["think_s"]
                    if due < seconds:
                        req = self.requests.closed(self.next_ordinal, due)
                        heapq.heappush(queue, (due, req.ordinal, req))
                        self.next_ordinal += 1

        engine_t0 = engine.now()
        while (now := clock()) < seconds:
            if self.trace_dir is not None and traced is None and now >= trace_from:
                jax.profiler.start_trace(str(self.trace_dir))
                mark = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
                mark.__enter__()
                traced = [clock(), engine.now()]
            send(now)
            if self._busy():
                tick()
            else:
                nxt = min(queue[0][0] if queue else seconds, seconds)
                if self.trace_dir is not None and traced is None:
                    nxt = min(nxt, trace_from)
                with ann("wait_for_arrival"):
                    time.sleep(max(0.0, nxt - now))
        # the window's work: every step dispatched in it, waited for
        jax.block_until_ready([rs.state.x for rs in engine.batcher.inflight])
        elapsed = clock()
        engine_t1 = engine.now()
        if traced is not None:
            mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced += [elapsed, engine_t1]
        steps = sum(rs.n_evals for rs in self.states.values())
        compiles = counter.n - compiles0
        stats1 = engine.stats()
        # what was due in the window finishes, or fails
        send(seconds)
        queue.clear()
        while self._busy() and clock() < elapsed + DRAIN_SECONDS:
            tick()
        return {"elapsed": elapsed, "steps": steps, "compiles": compiles,
                "stats0": stats0, "stats1": stats1, "traced": traced,
                "engine_window": (engine_t0, engine_t1)}

    def latencies(self) -> list[float]:
        return sorted(self.done[r] - self.due[r] for r in self.done)

    def trajectories(self) -> list[dict]:
        out = []
        for rid in sorted(self.tracks):
            rs = self.states[rid]
            if rid in self.done:
                out.append({"steps": rs.req.steps, "xs": self.tracks[rid],
                            "parts": self.parts[rid], "y": rs.req.y,
                            "guidance": rs.req.guidance_scale,
                            "ordinal": self.ordinal[rid]})
        return out


# ---------------------------------------------------------------------------
# what the program computed, read for the check
# ---------------------------------------------------------------------------


def program_outputs(engine, bank, cell: Cell, seed: int,
                    trajectories: list[dict]) -> dict:
    """``check.compare``'s ``outputs`` from the program: the eps of every
    forward row the tracked steps needed, from the compiled forward the
    window drove for the row's batch size (``engine._jit``), in batches of
    that size; for the rows ``check.site_sample`` draws, the same forward
    with each site's input recorded as well (the probe, at ``max_batch``
    rows); and the weights the bank serves for their segments."""
    from repro.core.qmodule import PackedW4, dequant_weight
    from repro.quant.calibrate import QuantContext

    class Recording(QuantContext):
        def __init__(self, act_qps):
            super().__init__("serve", act_qps=act_qps)
            self.seen = {}

        def act(self, name, x):
            self.seen[name] = x
            return x

    @jax.jit
    def probe(params, x, tb, y):
        ctx = Recording(engine.ctx.act_qps)
        return engine._apply(params, x, tb, y, ctx), ctx.seen

    @jax.jit
    def dequant(weights):
        return {site: (dequant_weight(w, jnp.float32)
                       if isinstance(w, PackedW4) else w)
                for site, w in weights.items()}

    _, rows = check.layout(cell.cfg, trajectories)
    eps = [None] * len(rows)
    groups: dict[tuple, list[int]] = {}
    for k, r in enumerate(rows):
        groups.setdefault((r.seg, engine._bucket(r.n_part), r.y is not None),
                          []).append(k)
    for (seg, b, labelled), idx in sorted(groups.items()):
        params = bank.params_for_segment(seg)
        fn = engine._jit[(b, labelled)]
        for lo in range(0, len(idx), b):
            part = idx[lo:lo + b]
            x, tb, y = check.batch(rows, part, b)
            out = fn(params, x, tb) if y is None else fn(params, x, tb, y)
            for p, k in enumerate(part):
                eps[k] = out[p:p + 1]
    size = cell.cfg["max_batch"]
    sample = sorted(check.site_sample(cell.cfg, seed, rows))
    seen, probe_eps, weights = {}, {}, {}
    for seg in sorted({rows[k].seg for k in sample}):
        params = bank.params_for_segment(seg)
        flat = ref.flat(params)
        weights[seg] = dequant({site: flat[site] for site
                                in ref.weight_sites(cell.cfg["model"])})
        for labelled in (False, True):
            idx = [k for k in sample if rows[k].seg == seg
                   and (rows[k].y is not None) == labelled]
            for lo in range(0, len(idx), size):
                part = idx[lo:lo + size]
                out, rec = probe(params, *check.batch(rows, part, size))
                for p, k in enumerate(part):
                    probe_eps[k] = out[p:p + 1]
                    seen[k] = {site: h[p:p + 1] for site, h in rec.items()}
    return {"eps": eps, "seen": seen, "probe_eps": probe_eps,
            "weights": weights}


# ---------------------------------------------------------------------------
# numbers
# ---------------------------------------------------------------------------


def percentile(sorted_vals, p: float) -> float:
    """Nearest-rank percentile over an ascending-sorted sequence."""
    if not sorted_vals:
        return float("nan")
    k = min(len(sorted_vals) - 1,
            int(round(p / 100 * (len(sorted_vals) - 1))))
    return sorted_vals[max(k, 0)]


def device_info() -> dict:
    """The device as JAX reports it; the peak memory of the fullest chip."""
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, log=print, control: bool = False) -> dict:
    """One run of ``cell``; returns the result object. ``t_start`` is the
    process's start on the ``time.perf_counter`` clock. With ``control``
    the result also holds the control judged on the same served states
    (``control``: its ``correct`` and ``checks``), which the benchmark's
    own runs never ask for."""
    from repro.serving.obs import NULL_OBS, Observability

    obs = Observability() if trace else NULL_OBS
    trace_dir = ROOT / ".bench_trace" if trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    engine, bank = build(cell, seed, obs)
    warm_up(engine, cell)
    window = Window(engine, cell, seed, seconds, trace_dir)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; window opens")
    w = window.run()
    log(f"window {w['elapsed']:.3f} s: {w['steps']} steps, "
        f"{len(window.due)} requests due, {len(window.done)} finished; "
        f"compiles inside the window: {w['compiles']}")
    device = device_info()
    attempted = len(window.due)
    finished = len(window.done)
    lat = window.latencies()
    x0s = [window.states[r].x0 for r in window.done]
    finite = bool(all(np.isfinite(np.asarray(x)).all() for x in x0s))
    events = obs.tracer.events() if trace else []
    trajectories = window.trajectories()
    n_tracked = len(window.tracked_ordinals)
    outputs = program_outputs(engine, bank, cell, seed, trajectories)
    # the program's state goes before the reference runs
    del engine, bank, window, x0s
    gc.collect()

    def judge(outputs):
        gaps = check.compare(cell.cfg, seed, trajectories, outputs)
        checks = {k: {"value": gaps[k], "limit": cell.cfg["limits"][k]}
                  for k in check.NUMBERS}
        checks["unfinished"] = {"value": attempted - finished, "limit": 0}
        checks["untracked"] = {"value": n_tracked - len(trajectories),
                               "limit": 0}
        checks["nonfinite"] = {"value": 0 if finite else 1, "limit": 0}
        return gaps, checks, all(c["value"] <= c["limit"]
                                 for c in checks.values())

    gaps, checks, correct = judge(outputs)
    del outputs

    result = {"correct": correct, "attempted": attempted,
              "failed": attempted - finished, "metrics": {},
              "device": device}
    if not trace:
        values = {
            "setup_s": setup_s,
            "steps_per_s": w["steps"] / w["elapsed"],
            "latency_p50_s": percentile(lat, 50),
            "latency_p95_s": percentile(lat, 95),
        }
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        if w["traced"] is None:
            raise RuntimeError("the window closed before the trace began")
        red = trace_reduce.reduce_dir(trace_dir)
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
        ctx = Context(cell=cell, events=events, window=w, reduction=red,
                      device_kind=device["kind"])
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"compared {gaps['steps']} steps of {len(trajectories)} tracked "
        f"requests (median step gap {gaps['step_gap_median']!r}) and "
        f"{gaps['rows_probed']} forward rows site by site; widest sites "
        f"{gaps['worst_sites']}; {gaps['weights_at_midpoint']} weights at a "
        f"midpoint, widest weight gap elsewhere {gaps['weight_gap']!r}; probe "
        f"eps against the window's {gaps['probe_gap']!r}")
    if control:
        _, ctl, ok = judge(check.control_outputs(cell.cfg, seed, trajectories))
        result["control"] = {"correct": ok, "checks": ctl}
    result["checks"] = checks
    return result


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader reads."""

    cell: Cell
    events: list           # the program's obs spans (engine clock, us)
    window: dict           # Window.run()'s record
    reduction: object      # trace_reduce.Reduction of the traced window
    device_kind: str

    def spans(self, name: str, *, in_window: bool) -> list[dict]:
        t0, t1 = (x * 1e6 for x in self.window["engine_window"])
        out = []
        for e in self.events:
            if e.get("ph") != "X" or e["name"] != name:
                continue
            inside = t0 <= e["ts"] and e["ts"] + e["dur"] <= t1
            if inside == in_window and (in_window or e["ts"] < t0):
                out.append(e)
        return out
