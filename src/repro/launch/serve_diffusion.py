"""Diffusion serving launcher: trace replay / scenario runs on the engine.

Quantizes a UNet preset to real packed FP4 (TALoRA-merged per routing
segment via the weight bank), then feeds the continuous-batching engine
one of:

  * ``--trace file.jsonl``  — replay a recorded/generated trace file,
  * ``--scenario name``     — a named workload from the traffic registry
    (``steady`` | ``burst`` | ``diurnal`` | ``heavy_tail`` |
    ``closed_loop`` | ``deadline_mix`` | ``tight_deadlines`` |
    ``golden``; default steady),

and reports sliding-window + whole-run SLO metrics (throughput, latency
percentiles from arrival, goodput vs per-request deadlines, queue depth,
segment-cache and prefetch behavior), plus a deterministic outcome
digest — two replays of the same trace under ``--replay-clock virtual``
must print the same digest.

    PYTHONPATH=src python -m repro.launch.serve_diffusion --smoke \
        --scenario golden --kernels interpret --replay-clock virtual

``--policy slo`` swaps the largest-group-wins scheduler for the
slack-aware one (EDF pressure vs segment-switch cost, preemptive group
splits — see ``serving/scheduler.py``); both policies stay benchable
against the same scenario. ``--save-trace out.jsonl`` captures whatever
workload actually ran (including closed-loop realized arrivals) back
into a replayable trace.
``--plan absmax`` (default) builds the calibration-free abs-max FP4 plan;
``--plan search`` runs the paper's calibrate + MSE-search pipeline first
(slow — minutes on CPU).

Observability (``serving/obs``) switches on when any of ``--trace-out``
(Perfetto-loadable span trace), ``--metrics-out`` (text exposition of
the metrics registry) or ``--report-json`` (machine-readable run report
— summary, SLO verdicts, engine stats, kernel route counts, outcome
digest; what CI asserts on) is given; otherwise the engine runs with the
no-op ``NULL_OBS``. Tracing follows the engine clock, so a virtual-clock
replay's trace — and its digest — is deterministic.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.clock import wall_clock
from repro.common.compile_cache import setup_compile_cache
from repro.configs.diffusion_presets import DIFFUSION_PRESETS, tiny_ddim
from repro.core import talora
from repro.diffusion.schedule import make_schedule
from repro.kernels import ops
from repro.nn.unet import io_sites, unet_init
from repro.quant.fakequant import KIND_FP_SIGNED, QuantizerParams
from repro.serving import (DiffusionServingEngine, VirtualClock, WeightBank,
                           absmax_talora_setup, act_qps_from_plan)
from repro.serving.obs import NULL_OBS, Observability
from repro.serving.traffic import (MetricsCollector, Scenario, TraceWriter,
                                   get_scenario, list_scenarios, load_trace,
                                   run_scenario)


# TALoRA shaping of the diffusion launchers. The gateway and fleet
# launchers build with it too, so a one-model gateway or one-replica fleet
# replays this launcher's golden digest.
SERVE_TALORA = talora.TALoRAConfig(hub_size=2, rank=4, t_emb_dim=32,
                                   router_hidden=16)


def fp4_act_qps(maxval: float = 6.0) -> dict:
    """Per-tensor signed E2M1 act quant at every site (``--act-quant fp4``)."""
    return {"*": QuantizerParams(KIND_FP_SIGNED, 2, 1, 4,
                                 jnp.float32(maxval))}


def build_bank(cfg, T: int, *, seed: int, plan_mode: str = "absmax",
               bank_cap: int = 4):
    """(sched, q_params, plan, bank): params made from ``seed``, quantized
    under ``plan_mode``, behind a TALoRA weight bank over a T-step linear
    schedule."""
    sched = make_schedule("linear", T)
    q_params, plan, hubs, router = build_quantized(
        cfg, sched, jax.random.PRNGKey(seed), plan_mode=plan_mode,
        talora_cfg=SERVE_TALORA)
    bank = WeightBank(q_params, plan, hubs, router, SERVE_TALORA, T,
                      max_cached=bank_cap)
    return sched, q_params, plan, bank


def assert_finite_x0(results) -> None:
    """Every request that ran must have produced a finite sample."""
    for rs in results.values():
        if not rs.expired and not bool(jnp.isfinite(rs.x0).all()):
            raise AssertionError(f"non-finite x0 rid={rs.req.rid}")


def check_conv_sites(q_params, bank, plan_mode: str) -> tuple[int, int]:
    """(packed, total) conv weight sites. Under the absmax plan every
    even-width non-io conv must serve packed through the W4A4 conv routes,
    never from the bf16 fallback bucket."""
    from repro.common.tree import flatten_paths
    flat_q = dict(flatten_paths(q_params))
    conv_w = [k for k, v in flat_q.items()
              if k.endswith("/w") and getattr(v, "ndim", 0) == 4]
    packed_sites = set(bank.pack_stats["packed"])
    if plan_mode == "absmax":
        missing = [k for k in conv_w
                   if k not in io_sites(q_params)
                   and flat_q[k].shape[-1] % 2 == 0
                   and k not in packed_sites]
        if missing:
            raise AssertionError(f"conv sites fell back to bf16: {missing}")
    return sum(k in packed_sites for k in conv_w), len(conv_w)


def build_quantized(cfg, sched, key, *, plan_mode: str, talora_cfg):
    """(q_params, plan, hubs, router) for the weight bank."""
    params = unet_init(key, cfg)
    if plan_mode == "search":
        from repro.diffusion.pipeline import quantize_diffusion
        bundle = quantize_diffusion(params, cfg, sched, key,
                                    talora_cfg=talora_cfg)
        return bundle.q_params, bundle.plan, bundle.hubs, bundle.router
    plan, hubs, router = absmax_talora_setup(params, talora_cfg, key,
                                             io_sites=io_sites(params))
    return params, plan, hubs, router


def outcome_digest(results) -> str:
    """Deterministic digest of per-request outcomes (step counts, final
    latents, expiry) — the replay-determinism check compares this line
    across runs of the same trace."""
    h = hashlib.sha256()
    for rid in sorted(results):
        rs = results[rid]
        h.update(f"{rid}:{rs.n_evals}:{int(rs.expired)}".encode())
        if rs.x0 is not None:
            h.update(np.asarray(rs.x0, np.float32).tobytes())
    return h.hexdigest()[:16]


def _warn_ignored_shaping(args) -> None:
    ignored = [f for f, v in (("--steps", args.steps),
                              ("--steps-jitter", args.steps_jitter),
                              ("--eta", args.eta),
                              ("--samplers", args.samplers),
                              ("--requests", args.requests),
                              ("--rate", args.rate)) if v is not None]
    if ignored:
        print(f"note: {', '.join(ignored)} ignored — a trace replays its "
              "recorded requests verbatim")


def _scenario_from_args(args) -> Scenario:
    if args.trace:
        _warn_ignored_shaping(args)
        return Scenario(name=f"trace:{args.trace}", kind="trace",
                        desc="ad-hoc trace replay", trace_path=args.trace)
    scn = get_scenario(args.scenario)
    if scn.kind == "trace":        # e.g. the golden fixture scenario
        _warn_ignored_shaping(args)
        return scn
    mix = scn.mix
    if args.steps is not None:
        mix = dataclasses.replace(mix, steps=args.steps)
    if args.steps_jitter is not None:
        mix = dataclasses.replace(mix, steps_jitter=args.steps_jitter)
    if args.eta is not None:
        mix = dataclasses.replace(mix, eta=args.eta)
    if args.samplers is not None:
        mix = dataclasses.replace(mix, samplers=tuple(
            args.samplers.split(",")))
    scn = dataclasses.replace(scn, mix=mix)
    if args.requests is not None:
        scn = dataclasses.replace(scn, n_requests=args.requests)
    if args.rate is not None and scn.kind == "open":
        kw = dict(scn.gen_kw)
        if "rate" in kw:
            kw["rate"] = args.rate
            scn = dataclasses.replace(scn, gen_kw=tuple(kw.items()))
        else:
            print(f"note: --rate ignored for generator {scn.gen!r} "
                  f"(tune {sorted(kw)} via the registry)")
    if args.smoke and scn.kind != "trace":
        scn = dataclasses.replace(
            scn, n_requests=min(scn.n_requests, 2), n_users=2,
            requests_per_user=1,
            mix=dataclasses.replace(scn.mix, steps=min(scn.mix.steps, 3),
                                    steps_jitter=min(scn.mix.steps_jitter,
                                                     1)))
    return scn


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny-ddim",
                    choices=sorted(DIFFUSION_PRESETS))
    ap.add_argument("--image-size", type=int, default=16,
                    help="tiny-ddim only; other presets fix their size")
    ap.add_argument("--T", type=int, default=100, help="schedule length")
    src = ap.add_mutually_exclusive_group()
    src.add_argument("--trace", default=None,
                     help="replay a recorded JSONL trace file")
    src.add_argument("--scenario", default="steady",
                     choices=list_scenarios(),
                     help="named workload from the traffic registry")
    ap.add_argument("--save-trace", default=None,
                    help="capture the run's submissions to a trace file")
    ap.add_argument("--replay-clock", default="wall",
                    choices=["wall", "virtual"],
                    help="virtual: deterministic admission/batching "
                         "(replay checks); wall: real SLO timing")
    ap.add_argument("--policy", default="fifo", choices=["fifo", "slo"],
                    help="group selection: fifo = largest-group-wins "
                         "baseline; slo = slack-aware EDF vs segment-"
                         "switch cost with preemptive group splits")
    ap.add_argument("--sync-prefetch", action="store_true",
                    help="build prefetched segments inline instead of on "
                         "the bank's background thread (virtual-clock "
                         "replay is always synchronous)")
    ap.add_argument("--requests", type=int, default=None,
                    help="override the scenario's open-loop request count")
    ap.add_argument("--rate", type=float, default=None,
                    help="override the scenario's arrival rate (req/s), "
                         "generators with a 'rate' knob only")
    ap.add_argument("--steps", type=int, default=None,
                    help="override base sampler steps per request")
    ap.add_argument("--steps-jitter", type=int, default=None)
    ap.add_argument("--eta", type=float, default=None)
    ap.add_argument("--samplers", default=None,
                    help="comma list cycled across requests "
                         "(ddim,plms,dpm_solver2)")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="in-flight slots (default: the scenario's "
                         "max_batch hint)")
    ap.add_argument("--max-idle-sleep", type=float, default=0.25,
                    help="cap (s) on one idle sleep while waiting for the "
                         "next arrival")
    ap.add_argument("--metrics-window", type=float, default=1.0,
                    help="sliding-window width (s) for the metrics report")
    ap.add_argument("--bank-cap", type=int, default=4,
                    help="LRU cap on cached segment weight-sets")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable eager next-segment weight-bank builds")
    ap.add_argument("--plan", default="absmax", choices=["absmax", "search"])
    ap.add_argument("--act-quant", default="fp4", choices=["off", "fp4"],
                    help="fp4 = fuse E2M1 act quant into packed matmuls")
    ap.add_argument("--act-maxval", type=float, default=6.0)
    ap.add_argument("--kernels", default="auto",
                    choices=["auto", "xla", "interpret", "pallas"])
    ap.add_argument("--conv-route", default="auto",
                    choices=["auto", "implicit", "im2col"],
                    help="Pallas conv route: implicit GEMM vs im2col "
                         "(auto: implicit on compiled TPU when it fits "
                         "VMEM; im2col in interpret mode — the golden "
                         "trace digest is pinned to its numerics)")
    ap.add_argument("--trace-out", default=None,
                    help="write the run's span trace here: .json = Chrome "
                         "trace-event format (open in Perfetto / "
                         "chrome://tracing), .jsonl = one event per line")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics registry's text exposition "
                         "(Prometheus-style) here at run end")
    ap.add_argument("--report-json", default=None,
                    help="write a machine-readable run report (summary, "
                         "SLO verdicts, engine stats, obs counters, "
                         "outcome digest) here — what CI asserts on")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny everything (CI: 2 concurrent requests)")
    args = ap.parse_args(argv)
    setup_compile_cache()

    if args.kernels != "auto":
        ops.FORCE = args.kernels
    if args.conv_route != "auto":
        ops.CONV_ROUTE = args.conv_route
    if args.smoke:
        args.image_size = min(args.image_size, 8)
        args.T = min(args.T, 50)

    scn = _scenario_from_args(args)
    max_batch = (args.max_batch if args.max_batch is not None
                 else scn.max_batch)
    if args.smoke:
        max_batch = min(max_batch, 2)

    if args.preset == "tiny-ddim":
        cfg = tiny_ddim(args.image_size)
    else:
        cfg = DIFFUSION_PRESETS[args.preset]()

    t0 = wall_clock()
    sched, q_params, plan, bank = build_bank(
        cfg, args.T, seed=args.seed, plan_mode=args.plan,
        bank_cap=args.bank_cap)
    act_qps = act_qps_from_plan(plan) if args.plan == "search" else {}
    if args.act_quant == "fp4":
        for site, qp in fp4_act_qps(args.act_maxval).items():
            act_qps.setdefault(site, qp)
    elif args.act_quant == "off":
        act_qps = {}
    clock = VirtualClock() if args.replay_clock == "virtual" else None
    obs = (Observability() if (args.trace_out or args.metrics_out
                               or args.report_json) else NULL_OBS)
    obs.install_kernels()
    engine = DiffusionServingEngine(cfg, sched, bank, act_qps=act_qps,
                                    max_batch=max_batch, clock=clock,
                                    policy=args.policy,
                                    max_idle_sleep=args.max_idle_sleep,
                                    prefetch=not args.no_prefetch,
                                    async_prefetch=not args.sync_prefetch,
                                    obs=obs)
    print(f"bank ready: {bank.n_segments} routing segments, plan={args.plan}, "
          f"kernels={args.kernels} ({wall_clock() - t0:.1f}s)")
    print(f"workload: {scn.name} — {scn.desc} "
          f"[clock={args.replay_clock}, policy={args.policy}]")

    writer = None
    if args.save_trace:
        writer = TraceWriter(args.save_trace,
                             meta={"scenario": scn.name,
                                   "seed": args.seed}).attach(engine)

    collector = MetricsCollector(window_s=args.metrics_window)
    summary = run_scenario(scn, engine, seed=args.seed, collector=collector)
    if writer is not None:
        writer.close()
        print(f"captured {writer.n} requests -> {args.save_trace}")
    results = engine.results
    assert_finite_x0(results)

    s = engine.stats()
    evals = sum(rs.n_evals for rs in results.values())
    wall = summary["wall_s"]
    print(f"served {summary['requests']} requests "
          f"({summary['expired']} expired) in {wall:.2f}s "
          f"({summary['requests'] / max(wall, 1e-9):.2f} req/s, "
          f"{evals / max(wall, 1e-9):.1f} denoise evals/s)")
    print(f"latency p50={summary['p50_s']:.2f}s p95={summary['p95_s']:.2f}s "
          f"p99={summary['p99_s']:.2f}s  goodput={summary['goodput_frac']:.2f} "
          f"({summary['deadline_misses']} deadline misses)")
    print(f"batching: mean batch {s['mean_batch']:.2f} "
          f"({s['forwards']} forwards / {s['ticks']} ticks), "
          f"peak queue depth {summary['peak_queue_depth']}")
    print(f"scheduler: policy={s['policy']}, {s['preemptions']} preemptions, "
          f"{s['deadline_saves']} deadline saves")
    for row in collector.windows()[:8]:
        hr = row.get("cache_hit_rate")
        print(f"  window t={row['t']:5.1f}s: {row['throughput_rps']:6.2f} "
              f"req/s, p95 {row['p95_s']:6.2f}s, goodput "
              f"{row['goodput_rps']:6.2f}/s, queue {row['queue_depth']:4.1f}"
              + (f", cache hit {hr:.2f}" if hr is not None else ""))
    slo = summary["slo"]
    if slo["checks"]:
        verdict = "PASS" if slo["passed"] else "FAIL"
        detail = ", ".join(f"{k}={c['actual']:.3g} (limit {c['limit']:.3g})"
                           for k, c in slo["checks"].items())
        print(f"SLO {verdict}: {detail}")
    print(f"weight bank: hit rate {s['bank_hit_rate']:.2f} "
          f"({s['bank_hits']} hits / {s['bank_misses']} misses, "
          f"{s['bank_evictions']} evictions, cap {args.bank_cap}), "
          f"{s['prefetch_hits']} prefetch hits / {s['bank_prefetches']} "
          f"prefetches, {s['bank_builds']} builds "
          f"({s['bank_build_joins']} joined in-progress), "
          f"{s['bank_packed_sites']} packed / "
          f"{s['bank_fallback_sites']} bf16-fallback sites")
    print(f"jit cache: {s['compiled_forwards']} compiled forwards "
          f"(buckets {s['buckets']}), {s['padded_samples']} padded samples, "
          f"{s['idle_sleeps']} idle sleeps")

    n_conv_packed, n_conv = check_conv_sites(q_params, bank, args.plan)
    print(f"conv sites: {n_conv_packed}/{n_conv} packed (W4A4 conv route)")
    digest = outcome_digest(results)
    print(f"outcome digest: {digest} "
          f"({len(results)} requests, {summary['expired']} expired)")

    obs.finalize(engine, collector)
    obs.uninstall_kernels()
    if args.trace_out:
        n = obs.tracer.export(args.trace_out)
        dropped = (f" ({obs.tracer.dropped} dropped)"
                   if obs.tracer.dropped else "")
        print(f"trace: {n} events -> {args.trace_out}{dropped}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(obs.metrics.to_text())
        print(f"metrics: -> {args.metrics_out}")
    if args.report_json:
        report = {
            "scenario": scn.name,
            "policy": args.policy,
            "replay_clock": args.replay_clock,
            "kernels": args.kernels,
            "seed": args.seed,
            "outcome_digest": digest,
            "n_requests": len(results),
            "summary": {k: v for k, v in summary.items() if k != "slo"},
            "slo": summary["slo"],
            "engine": s,
            "kernel_routes": (obs.kernel_profiler.route_counts()
                              if obs.kernel_profiler is not None else {}),
            "obs": obs.metrics.snapshot(),
        }
        with open(args.report_json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True, default=float)
        print(f"report: -> {args.report_json}")


if __name__ == "__main__":
    main()
