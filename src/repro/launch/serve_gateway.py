"""Multi-model gateway launcher: mixed-model traffic over per-model banks.

Registers the requested models (``--models tiny-ddim,smollm-135m``) from
the gateway registry's curated entries, builds one engine + weight bank
per model — the diffusion preset through the same quantize/pack path as
``serve_diffusion``, the LM through ``quantize_lm_for_serving`` via the
bank's ``build_fn`` seam — and drives a named traffic scenario through
one ``ServingGateway``:

    PYTHONPATH=src python -m repro.launch.serve_gateway --smoke \
        --models tiny-ddim,smollm-135m --scenario mixed_model \
        --kernels interpret --clock virtual

Clocks: ``--clock virtual`` replays deterministically (two runs of the
same scenario print the same outcome digest — the CI check); ``--clock
sim`` scores SLOs under simulated service time shared across every
engine (machine-independent goodput, the bench rows); ``--clock wall``
is real timing on a shared origin.

Identity check: with a single diffusion model the gateway adds zero
behavior — ``--models tiny-ddim --scenario golden --smoke --kernels
interpret --clock virtual`` reproduces ``serve_diffusion``'s golden
outcome digest bit-for-bit (CI asserts the literal digest).

The report (``--report-json``) carries per-model goodput/SLO verdicts,
per-bank counters with their reconciliation check (``builds +
build_failures == misses + prefetches`` *per bank*), the aggregate
outcome digest over gateway-wide request ids, and per-model digests.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.common.clock import wall_clock
from repro.common.compile_cache import setup_compile_cache
from repro.configs.diffusion_presets import DIFFUSION_PRESETS, tiny_ddim
from repro.configs.registry import ARCHS
from repro.kernels import ops
from repro.launch.serve_diffusion import (_scenario_from_args, build_bank,
                                          fp4_act_qps, outcome_digest)
from repro.launch.steps import quantize_lm_for_serving
from repro.models.lm import lm_init
from repro.serving import DiffusionServingEngine, VirtualClock, WeightBank
from repro.serving.gateway import (LMServingEngine, ModelRegistry,
                                   ServingGateway, default_entries)
from repro.serving.obs import NULL_OBS, Observability
from repro.serving.traffic import MetricsCollector, TraceWriter, run_scenario
from repro.serving.traffic.scenarios import list_scenarios
from repro.serving.traffic.sim import SimClock


def build_diffusion_engine(entry, args, eng_kw, obs, max_batch):
    """The exact quantize -> bank -> engine path ``serve_diffusion``
    takes with ``--plan absmax --act-quant fp4`` — same seed, same
    TALoRA shaping — so a single-model gateway run is digest-identical
    to the standalone launcher."""
    if entry.config == "tiny-ddim":
        cfg = tiny_ddim(args.image_size)
    else:
        cfg = DIFFUSION_PRESETS[entry.config]()
    sched, _, _, bank = build_bank(cfg, args.T, seed=args.seed,
                                   bank_cap=args.bank_cap or entry.bank_cap)
    return DiffusionServingEngine(cfg, sched, bank, act_qps=fp4_act_qps(),
                                  max_batch=max_batch, policy=args.policy,
                                  obs=obs, model=entry.name, **eng_kw)


def build_lm_engine(entry, args, eng_kw, obs, max_batch):
    """LM adapter path: init -> quantize_lm_for_serving (calibration-free
    abs-max W4) through the bank's build_fn seam; one weight segment."""
    arch = ARCHS[entry.config]
    cfg = arch.smoke() if entry.smoke else arch.full()
    params = lm_init(jax.random.PRNGKey(args.seed), cfg)
    bank = WeightBank(params, None, {}, None, None, 1,
                      max_cached=args.bank_cap or entry.bank_cap,
                      build_fn=lambda p: quantize_lm_for_serving(
                          p, searched=False))
    return LMServingEngine(cfg, bank, max_batch=max_batch,
                           policy=args.policy, obs=obs, model=entry.name,
                           **eng_kw)


BUILDERS = {"diffusion": build_diffusion_engine, "lm": build_lm_engine}


def build_gateway(model_names, args, obs=NULL_OBS):
    """(gateway, sim_clock | None): registry-resolved engines behind one
    routing surface, all on one shared clock."""
    registry = ModelRegistry(default_entries())
    entries = [registry.resolve(n) for n in model_names]
    sim = None
    if args.clock == "virtual":
        clock = VirtualClock()
        gw = ServingGateway(clock=clock)
        eng_kw = {"clock": clock}
    elif args.clock == "sim":
        sim = SimClock()
        gw = ServingGateway(now_fn=sim.now, max_idle_sleep=0.0)
        eng_kw = {"now_fn": sim.now, "max_idle_sleep": 0.0}
    else:
        t0 = wall_clock()
        now_fn = lambda: wall_clock() - t0   # noqa: E731 — shared origin
        gw = ServingGateway(now_fn=now_fn)
        eng_kw = {"now_fn": now_fn}
    for entry in entries:
        mb = min(args.gateway_max_batch, entry.max_batch)
        engine = BUILDERS[entry.family](entry, args, eng_kw, obs, mb)
        if sim is not None:
            sim.attach(engine)
        gw.add_model(entry, engine)
    return gw, sim


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="tiny-ddim,smollm-135m",
                    help="comma list of registered model names "
                         f"(registry: {[e.name for e in default_entries()]})")
    src = ap.add_mutually_exclusive_group()
    src.add_argument("--trace", default=None,
                     help="replay a recorded JSONL trace file (v1 files "
                          "route every request to the default model)")
    src.add_argument("--scenario", default="mixed_model",
                     choices=list_scenarios())
    ap.add_argument("--save-trace", default=None,
                    help="capture the run (gateway-wide rids + model "
                         "routing) to a v2 trace file")
    ap.add_argument("--clock", default="wall",
                    choices=["wall", "virtual", "sim"],
                    help="virtual: deterministic replay; sim: simulated "
                         "service time shared across models (machine-"
                         "independent SLOs); wall: real timing")
    ap.add_argument("--policy", default="fifo", choices=["fifo", "slo"])
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--steps-jitter", type=int, default=None)
    ap.add_argument("--eta", type=float, default=None)
    ap.add_argument("--samplers", default=None)
    ap.add_argument("--max-batch", type=int, default=None,
                    help="cap on any model engine's in-flight slots "
                         "(default: scenario hint; each entry's own "
                         "max_batch still applies)")
    ap.add_argument("--bank-cap", type=int, default=None,
                    help="override every bank's LRU cap (default: each "
                         "registry entry's)")
    ap.add_argument("--image-size", type=int, default=16)
    ap.add_argument("--T", type=int, default=100)
    ap.add_argument("--kernels", default="auto",
                    choices=["auto", "xla", "interpret", "pallas"])
    ap.add_argument("--trace-out", default=None,
                    help="span trace (per-model tracks) — .json/.jsonl")
    ap.add_argument("--metrics-out", default=None,
                    help="metrics registry text exposition (per-model "
                         "labeled series)")
    ap.add_argument("--report-json", default=None,
                    help="machine-readable run report — what CI asserts on")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny everything (CI shaping)")
    args = ap.parse_args(argv)
    setup_compile_cache()

    if args.kernels != "auto":
        ops.FORCE = args.kernels
    if args.smoke:
        args.image_size = min(args.image_size, 8)
        args.T = min(args.T, 50)

    scn = _scenario_from_args(args)
    mb = args.max_batch if args.max_batch is not None else scn.max_batch
    if args.smoke:
        mb = min(mb, 2)
    args.gateway_max_batch = mb

    model_names = [s.strip() for s in args.models.split(",") if s.strip()]
    if not model_names:
        raise SystemExit("--models needs at least one registered name")

    obs = (Observability() if (args.trace_out or args.metrics_out
                               or args.report_json) else NULL_OBS)
    obs.install_kernels()
    t0 = wall_clock()
    gw, _sim = build_gateway(model_names, args, obs=obs)
    for name in gw.list_models():
        e = gw.engine(name)
        print(f"model {name}: {e.bank.n_segments} segments, "
              f"cap {e.bank.max_cached}, max_batch {e.batcher.max_batch}")
    print(f"gateway ready: {len(model_names)} models "
          f"({wall_clock() - t0:.1f}s) [clock={args.clock}, "
          f"policy={args.policy}]")
    print(f"workload: {scn.name} — {scn.desc}")

    writer = None
    if args.save_trace:
        writer = TraceWriter(args.save_trace,
                             meta={"scenario": scn.name, "seed": args.seed,
                                   "models": model_names}).attach(gw)

    collector = MetricsCollector()
    summary = run_scenario(scn, gw, seed=args.seed, collector=collector)
    if writer is not None:
        writer.close()
        print(f"captured {writer.n} requests -> {args.save_trace}")

    for gid, rs in gw.results.items():
        if not rs.expired:
            assert bool(jnp.isfinite(rs.x0).all()), f"non-finite x0 gid={gid}"

    gs = gw.stats()
    agg = gs["aggregate"]
    digest = outcome_digest(gw.results)
    wall = summary["wall_s"]
    print(f"served {agg['requests']} requests ({agg['expired']} expired) "
          f"across {len(model_names)} models in {wall:.2f}s")
    per_model_digest = {}
    reconciled = {}
    for name in gw.list_models():
        p = gs["per_model"][name]
        e = gw.engine(name)
        bank = e.bank
        ok = (bank.builds + bank.build_failures
              == bank.misses + bank.prefetches)
        reconciled[name] = ok
        per_model_digest[name] = outcome_digest(e.results)
        slo = p["slo"]
        verdict = ("PASS" if slo["passed"] else "FAIL") if slo["checks"] \
            else "n/a"
        print(f"  {name} [{p['family']}]: "
              f"{p['engine']['requests']} done / "
              f"{p['engine']['expired']} expired, "
              f"goodput {p['summary']['goodput_frac']:.2f}, "
              f"p95 {p['summary']['p95_s']:.2f}s, SLO {verdict}; "
              f"bank {bank.builds} builds = {bank.misses} misses + "
              f"{bank.prefetches} prefetches "
              f"[{'reconciled' if ok else 'MISMATCH'}]")
        assert ok, f"bank counters do not reconcile for {name}"
    print(f"outcome digest: {digest} ({len(gw.results)} requests)")

    for name in gw.list_models():
        obs.finalize(gw.engine(name),
                     gw._models[name].collector)
    obs.uninstall_kernels()
    if args.trace_out:
        n = obs.tracer.export(args.trace_out)
        print(f"trace: {n} events -> {args.trace_out}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(obs.metrics.to_text())
        print(f"metrics: -> {args.metrics_out}")
    if args.report_json:
        report = {
            "scenario": scn.name,
            "models": model_names,
            "clock": args.clock,
            "policy": args.policy,
            "kernels": args.kernels,
            "seed": args.seed,
            "outcome_digest": digest,
            "n_requests": len(gw.results),
            "summary": {k: v for k, v in summary.items() if k != "slo"},
            "slo": summary["slo"],
            "aggregate": agg,
            "per_model": {
                name: {
                    "digest": per_model_digest[name],
                    "family": gs["per_model"][name]["family"],
                    "goodput_frac":
                        gs["per_model"][name]["summary"]["goodput_frac"],
                    "summary": gs["per_model"][name]["summary"],
                    "slo": gs["per_model"][name]["slo"],
                    "engine": gs["per_model"][name]["engine"],
                    "bank_reconciled": reconciled[name],
                } for name in gw.list_models()},
            "obs": obs.metrics.snapshot() if obs.enabled else {},
        }
        with open(args.report_json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True, default=float)
        print(f"report: -> {args.report_json}")


if __name__ == "__main__":
    main()
