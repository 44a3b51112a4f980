import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The 512 devices are host (CPU) devices; pinning the platform keeps the
# dry-run off any attached TPU, which belongs to one process at a time.
os.environ["JAX_PLATFORMS"] = "cpu"
# (required before ANY jax import — jax locks device count on first init.
#  REPRO_DRYRUN_DEVICES overrides for quick local runs, e.g. 64.)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent (shardings
compose, collectives legal, memory fits) and extracts the roofline inputs:
  * compiled.memory_analysis()  -> per-device bytes (args/temps/outputs)
  * compiled.cost_analysis()    -> per-device HLO FLOPs + bytes accessed
  * optimized HLO text          -> per-device collective bytes by op type

Results land in ``experiments/dryrun/<cell>.json``; benchmarks/roofline.py
turns them into the EXPERIMENTS.md tables.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-27b --shape decode_32k \
      --quant w4 --kv fp4          # the paper-technique serving variant
"""
import argparse
import dataclasses
import json
import re
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.clock import wall_clock
from repro.common.compile_cache import setup_compile_cache
from repro.configs.registry import ARCH_IDS, all_cells, get_config
from repro.configs.shapes import SHAPES
from repro.launch.mesh import make_production_mesh, mesh_chip_count
from repro.launch.sharding import (cache_shardings, data_spec,
                                   param_shardings)
from repro.launch.steps import (abstract_caches, abstract_opt,
                                abstract_params, input_specs,
                                make_decode_fn, make_prefill_step,
                                make_train_step, quantize_abstract)
from repro.optim.adam import AdamConfig

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1}

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\)|\S+))\s+(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")


def parse_collectives(hlo: str) -> dict:
    """Sum per-device operand bytes of every collective in optimized HLO."""
    totals: dict[str, float] = {}
    counts: dict[str, int] = {}
    largest: list[tuple[float, str, str]] = []
    for m in _COLL_RE.finditer(hlo):
        ty, op = m.group(1), m.group(2)
        nbytes = 0
        for sm in _SHAPE_RE.finditer(ty):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        # all-reduce moves ~2x payload (reduce-scatter + all-gather phases)
        moved = nbytes * (2 if op == "all-reduce" else 1)
        totals[op] = totals.get(op, 0) + moved
        counts[op] = counts.get(op, 0) + 1
        largest.append((moved, op, ty[:120]))
    largest.sort(reverse=True)
    return {"bytes_by_op": totals, "count_by_op": counts,
            "total_bytes": sum(totals.values()),
            "top5": [dict(bytes=b, op=o, type=t) for b, o, t in largest[:5]]}


def _mem_dict(ma) -> dict:
    if ma is None:
        return {}
    return {k: getattr(ma, k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes") if hasattr(ma, k)}


def with_depth(cfg, n_groups: int):
    """Reduced-depth clone (same per-group body) for cost extrapolation."""
    return dataclasses.replace(
        cfg, n_layers=cfg.first_k_dense + cfg.period * n_groups)


def _compile_cell(cfg, shape, mesh, *, quant: str, kv: str, big: bool,
                  multi_pod: bool, opts: frozenset = frozenset(),
                  save_hlo: str | None = None) -> dict:
    """Lower + compile one configuration; return raw analysis record.

    ``opts`` are hillclimb variants: 'headfix' (head-divisibility-aware
    attention sharding), 'accumN' (N-way gradient accumulation)."""
    acfg = AdamConfig(lr=3e-4,
                      moment_dtype=jnp.bfloat16 if big else jnp.float32)
    rule_cfg = cfg if "headfix" in opts else None
    grad_accum = 1
    for o in opts:
        if o.startswith("accum"):
            grad_accum = int(o[5:])
    if "moeep" in opts:
        cfg = dataclasses.replace(cfg, moe_impl="ep")
    if "noremat" in opts:
        cfg = dataclasses.replace(cfg, remat=False)
    # serving weights are read every step: FSDP sharding would all-gather
    # them per token — 'nofsdp' keeps them TP-resident (§Perf iteration 1)
    use_fsdp = not ("nofsdp" in opts and shape.kind != "train")
    # 'dpall': small-model config — pure DP, batch over every mesh axis,
    # params replicated (no TP, no FSDP)
    dpall = "dpall" in opts
    use_tp = not dpall
    if dpall:
        use_fsdp = False
    batch_axes = (("pod", "data", "model") if dpall else ("pod", "data"))
    from repro.common.sharding import set_dp_axes
    set_dp_axes(batch_axes)  # activation hints must match input shardings
    rec: dict = {}
    t0 = wall_clock()
    with jax.set_mesh(mesh):
        aparams = abstract_params(cfg)
        if quant == "w4" and shape.kind != "train":
            aparams = quantize_abstract(aparams)
        ps = param_shardings(aparams, mesh, fsdp=use_fsdp,
                             fsdp_over_pod=(big and multi_pod), cfg=rule_cfg,
                             tp=use_tp)
        specs = input_specs(cfg, shape)
        if shape.kind == "train":
            aopt = abstract_opt(aparams, acfg)
            os_ = param_shardings(aopt, mesh, fsdp=not dpall,
                                  fsdp_over_pod=(big and multi_pod),
                                  cfg=rule_cfg, tp=use_tp)
            bs = {k: NamedSharding(mesh, data_spec(v.shape, mesh,
                                                   axes=batch_axes))
                  for k, v in specs["batch"].items()}
            step = make_train_step(cfg, acfg, grad_accum=grad_accum)
            jitted = jax.jit(step, in_shardings=(ps, os_, bs),
                             out_shardings=(ps, os_, None))
            lowered = jitted.lower(aparams, aopt, specs["batch"])
        elif shape.kind == "prefill":
            bs = {k: NamedSharding(mesh, data_spec(v.shape, mesh,
                                                   axes=batch_axes))
                  for k, v in specs["batch"].items()}
            step = make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(ps, bs))
            lowered = jitted.lower(aparams, specs["batch"])
        else:  # decode
            acaches = specs["caches"]
            cs = cache_shardings(acaches, mesh)
            ts = NamedSharding(mesh, data_spec(specs["token"].shape, mesh))
            step = make_decode_fn(cfg)
            jitted = jax.jit(step, in_shardings=(ps, cs, ts, NamedSharding(mesh, P())),
                             out_shardings=(None, cs))
            lowered = jitted.lower(aparams, acaches, specs["token"],
                                   specs["pos"])
        rec["lower_s"] = round(wall_clock() - t0, 1)
        t1 = wall_clock()
        compiled = lowered.compile()
        rec["compile_s"] = round(wall_clock() - t1, 1)
        try:
            rec["memory"] = _mem_dict(compiled.memory_analysis())
        except Exception as e:  # CPU backend quirks
            rec["memory"] = {"error": str(e)}
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and "{" not in k}
        hlo = compiled.as_text()
        rec["collectives"] = parse_collectives(hlo)
        rec["hlo_bytes"] = len(hlo)
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(hlo)
    return rec


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             quant: str = "bf16", kv: str = "bf16", opts: frozenset = frozenset(),
             save_hlo: str | None = None, extrapolate: bool = True) -> dict:
    """Full-depth compile (the deliverable: shardings + memory are exact)

    plus, because XLA's cost_analysis counts a scan body ONCE regardless of
    trip count, a two-point depth extrapolation (1-group and 2-group
    clones) that recovers true per-step FLOPs/bytes/collective-bytes:
        total(L) = shallow(1) + (L - 1) * [shallow(2) - shallow(1)].
    """
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    if shape.kind == "decode":
        cfg = dataclasses.replace(cfg, kv_dtype=kv)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chip_count(mesh)
    big = cfg.param_count() > 3e11  # kimi-class: bf16 moments + pod-FSDP
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi" if multi_pod else "single", "chips": chips,
           "quant": quant, "kv": kv, "kind": shape.kind,
           "params": cfg.param_count(),
           "active_params": cfg.active_param_count(),
           "n_groups": cfg.n_groups, "opts": sorted(opts)}
    kw = dict(quant=quant, kv=kv, big=big, multi_pod=multi_pod, opts=opts)
    rec.update(_compile_cell(cfg, shape, mesh, save_hlo=save_hlo, **kw))
    if extrapolate and cfg.n_groups > 1:
        # fully-unrolled shallow clones: every scan/map becomes straightline
        # HLO so cost_analysis counts true per-depth work
        ucfg = dataclasses.replace(cfg, unroll=True)
        r1 = _compile_cell(with_depth(ucfg, 1), shape, mesh, **kw)
        r2 = _compile_cell(with_depth(ucfg, 2), shape, mesh, **kw)
        g = cfg.n_groups

        def lin(a, b):
            return a + (g - 1) * (b - a)

        cost = {}
        for k in ("flops", "bytes accessed", "transcendentals"):
            if k in r1["cost"] and k in r2["cost"]:
                cost[k] = lin(r1["cost"][k], r2["cost"][k])
        coll_by_op = {}
        ops1 = r1["collectives"]["bytes_by_op"]
        ops2 = r2["collectives"]["bytes_by_op"]
        for op in set(ops1) | set(ops2):
            coll_by_op[op] = lin(ops1.get(op, 0), ops2.get(op, 0))
        rec["extrap"] = {
            "cost": cost,
            "collective_bytes_by_op": coll_by_op,
            "collective_bytes": sum(coll_by_op.values()),
            "shallow": [{"cost": r1["cost"],
                         "coll": ops1},
                        {"cost": r2["cost"], "coll": ops2}],
        }
    else:
        rec["extrap"] = {
            "cost": {k: rec["cost"].get(k, 0.0)
                     for k in ("flops", "bytes accessed", "transcendentals")},
            "collective_bytes_by_op": rec["collectives"]["bytes_by_op"],
            "collective_bytes": rec["collectives"]["total_bytes"],
        }
    return rec


def cell_id(rec_or_args) -> str:
    r = rec_or_args
    extra = ""
    if r.get("quant", "bf16") != "bf16":
        extra += f"_{r['quant']}"
    if r.get("kv", "bf16") != "bf16":
        extra += f"_kv{r['kv']}"
    for o in r.get("opts", []) or []:
        extra += f"_{o}"
    return f"{r['arch']}_{r['shape']}_{r['mesh']}{extra}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--quant", default="bf16", choices=["bf16", "w4"])
    ap.add_argument("--kv", default="bf16", choices=["bf16", "fp8", "fp4"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--opts", default="",
                    help="comma list of hillclimb variants, e.g. headfix,accum4")
    ap.add_argument("--no-extrapolate", action="store_true",
                    help="skip the shallow cost-extrapolation compiles "
                         "(pass/fail + memory only — multi-pod sweep)")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    setup_compile_cache()
    os.makedirs(args.out, exist_ok=True)

    cells = all_cells()
    if args.arch != "all":
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape != "all":
        cells = [(a, s) for a, s in cells if s == args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    opts = frozenset(o for o in args.opts.split(",") if o)

    n_fail = 0
    for arch, shape in cells:
        for mp in meshes:
            rec_key = cell_id({"arch": arch, "shape": shape,
                               "mesh": "multi" if mp else "single",
                               "quant": args.quant, "kv": args.kv,
                               "opts": sorted(opts)})
            path = os.path.join(args.out, rec_key + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {rec_key}")
                continue
            try:
                rec = run_cell(arch, shape, multi_pod=mp, quant=args.quant,
                               kv=args.kv, opts=opts, save_hlo=args.save_hlo,
                               extrapolate=not args.no_extrapolate)
                rec["ok"] = True
                coll = rec["collectives"]["total_bytes"] / 1e6
                mem = rec.get("memory", {}).get("temp_size_in_bytes", 0) / 1e9
                print(f"[ok]   {rec_key}: compile={rec['compile_s']}s "
                      f"flops/dev={rec['cost'].get('flops', 0):.3e} "
                      f"coll={coll:.1f}MB/dev temp={mem:.2f}GB/dev")
            except Exception as e:
                n_fail += 1
                rec = {"arch": arch, "shape": shape,
                       "mesh": "multi" if mp else "single", "ok": False,
                       "quant": args.quant, "kv": args.kv,
                       "opts": sorted(opts),
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(f"[FAIL] {rec_key}: {type(e).__name__}: {e}")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    print(f"done: {len(cells) * len(meshes)} cells, {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
