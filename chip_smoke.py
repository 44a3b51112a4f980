"""Smoke run of the W4A4 diffusion serving path on one TPU chip.

Builds the paper's DDIM CIFAR-10 UNet (``ddim-cifar10``: 32 px, ch 128,
ch_mult (1, 2, 2, 2), attention at 16 px) at full width from random
weights made from ``SEED``, packs it into the TALoRA weight bank over a
T=100 schedule, and serves requests through the continuous-batching
engine on the wall clock with observability on, every packed site
dispatching to a compiled Pallas kernel. Then it runs one batched forward
on one segment's packed params twice on the same chip: through the
engine's own compiled forward, as served, and through the reference
oracles (``kernels/ref.py``).

    python chip_smoke.py

The timings it prints are smoke timings of one run, not benchmark
results. It exits non-zero, printing no result line, when JAX finds no
TPU or any phase fails. The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""
from __future__ import annotations

import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

PRESET = "ddim-cifar10"
T = 100
SEED = 0
STEPS = 20
# Requests arrive in waves of 1, 2 and 4; each wave shares its timesteps,
# so its requests batch together and buckets 1, 2 and 4 all compile.
WAVES = (1, 2, 4)
MAX_BATCH = 4

# Pallas vs reference on one forward: max |pallas - ref| over max |ref|.
# Both sides decode the same nibbles, snap activations to the same E2M1
# grid and run every f32 dot at f32 precision, with no precision set
# around either, so they differ only in f32 summation order. The forward
# amplifies a kernel error through the snaps that follow it: a kernel
# that rounds its operands to bf16 (one MXU pass, the default precision
# for f32 dots on the TPU) fails this bound.
PARITY_RTOL = 2e-3
PARITY_BATCH = MAX_BATCH     # a bucket the served requests compiled
TIMED_FORWARDS = 5

# ``ref`` routes allowed on the chip, as {"op:route": reason}. None:
# every packed dense and conv site of ddim-cifar10 has a Pallas kernel.
EXPECTED_REF: dict[str, str] = {}
# Routes that mean the chip was not used: interpret mode, or the XLA
# serving path that dispatch takes off the TPU.
FORBIDDEN_ROUTES = ("interpret", "xla_fast")


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def route_of(key: str) -> str:
    """``"w4a4_conv2d:pallas:implicit"`` -> ``"pallas"``."""
    return key.split(":")[1]


def serve(cfg, sched, bank, obs):
    """Serve ``WAVES`` of STEPS-step requests; return (engine, wall_s)."""
    from repro.launch.serve_diffusion import assert_finite_x0, fp4_act_qps
    from repro.serving import DiffusionServingEngine

    engine = DiffusionServingEngine(cfg, sched, bank, act_qps=fp4_act_qps(),
                                    max_batch=MAX_BATCH, obs=obs)
    t0 = time.perf_counter()
    rid = 0
    for n in WAVES:
        for _ in range(n):
            engine.submit(steps=STEPS, seed=SEED + rid)
            rid += 1
        engine.run()
    assert_finite_x0(engine.results)     # waits for the last forward
    wall = time.perf_counter() - t0
    if len(engine.results) != sum(WAVES) or engine.n_expired:
        fail(f"served {len(engine.results)} of {sum(WAVES)} requests, "
             f"{engine.n_expired} expired")
    return engine, wall


def spans(events, name: str) -> list[dict]:
    return [e for e in events if e.get("ph") == "X" and e["name"] == name]


def check_routes(counts: dict, events, n_packed: int, n_traces: int) -> None:
    bad = {k: n for k, n in counts.items()
           if route_of(k) in FORBIDDEN_ROUTES}
    if bad:
        fail(f"dispatch left the chip: {bad}")
    refs = {k: n for k, n in counts.items() if route_of(k) == "ref"}
    for k, n in refs.items():
        shapes = sorted({str(e["args"].get("shape")) for e in events
                         if e.get("cat") == "kernel"
                         and e["name"] == "{}[{}]".format(*k.split(":", 1))})
        why = EXPECTED_REF.get(k)
        print(f"ref route {k} x{n} on shapes {shapes}: "
              + (f"expected ({why})" if why else "NOT EXPECTED"))
    unexpected = sorted(set(refs) - set(EXPECTED_REF))
    if unexpected:
        fail(f"packed sites fell back to the reference oracles: "
             f"{unexpected}")
    kernel_sites = sum(n for k, n in counts.items()
                       if route_of(k) == "pallas"
                       and k.split(":")[0] in ("w4a4_matmul", "w4_matmul",
                                               "w4a4_conv2d"))
    if kernel_sites != n_packed * n_traces:
        fail(f"{kernel_sites} packed-site Pallas dispatches over {n_traces} "
             f"compiled forwards, expected {n_packed} per forward")


def parity(engine, bank):
    """(max abs err, max rel err, max |ref|, median forward s): one batched
    forward on one segment's params, through the engine's compiled bucket
    forward (the Pallas kernels as served) and through the ref oracles;
    then the served forward timed ``TIMED_FORWARDS`` times."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    cfg = engine.cfg
    seg = bank.segments[bank.segment_of(T - 1)]
    params = bank.params_for_segment(seg.index)
    x = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                          (PARITY_BATCH, cfg.image_size, cfg.image_size,
                           cfg.in_ch), jnp.float32)
    t = jnp.full((PARITY_BATCH,), float(seg.t_lo), jnp.float32)

    got = jax.block_until_ready(engine._forward(params, x, t, None))
    times = []
    for _ in range(TIMED_FORWARDS):
        t0 = time.perf_counter()
        jax.block_until_ready(engine._forward(params, x, t, None))
        times.append(time.perf_counter() - t0)
    # a fresh jit of the same forward: ops reads FORCE while tracing
    served_force, ops.FORCE = ops.FORCE, "xla"
    try:
        ref = jax.jit(lambda p, x, t: engine._apply(p, x, t, None,
                                                    engine.ctx))
        want = jax.block_until_ready(ref(params, x, t))
    finally:
        ops.FORCE = served_force
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    if not (jnp.isfinite(got).all() and jnp.isfinite(want).all()):
        fail("non-finite parity forward")
    return err, err / scale, scale, statistics.median(times)


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the repro package is not next to this script "
             f"({ROOT / 'src' / 'repro'} is missing)")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.common.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform!r} "
             f"({dev.device_kind}); this smoke run needs a TPU chip")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}; compile cache: {cache_dir}")

    from repro.configs.diffusion_presets import DIFFUSION_PRESETS
    from repro.launch.serve_diffusion import build_bank, check_conv_sites
    from repro.serving.obs import Observability

    cfg = DIFFUSION_PRESETS[PRESET]()
    t0 = time.perf_counter()
    sched, q_params, plan, bank = build_bank(cfg, T, seed=SEED,
                                             bank_cap=STEPS)
    print(f"bank set-up: {time.perf_counter() - t0:.2f}s "
          f"({PRESET}, T={T}, {bank.n_segments} routing segments)")

    obs = Observability()
    obs.install_kernels()
    try:
        engine, wall = serve(cfg, sched, bank, obs)
    finally:
        obs.uninstall_kernels()
    n_conv_packed, n_conv = check_conv_sites(q_params, bank, "absmax")

    events = obs.tracer.events()
    builds = [e["dur"] / 1e6 for e in spans(events, "bank_build")]
    print(f"segment builds: {len(builds)}, first {builds[0]:.2f}s, "
          f"median {statistics.median(builds):.2f}s, "
          f"total {sum(builds):.2f}s")
    for e in spans(events, "forward"):
        if e["args"].get("compiled"):
            print(f"bucket {e['args']['padded_rows']}: first forward "
                  f"(trace + compile + dispatch) {e['dur'] / 1e6:.2f}s")
    print(f"served {sum(WAVES)} requests x {STEPS} steps in waves "
          f"{WAVES}: {wall:.2f}s wall (smoke timing, not a benchmark)")
    stats = engine.stats()
    print(f"engine.stats(): {json.dumps(stats, sort_keys=True, default=float)}")
    counts = obs.kernel_profiler.route_counts()
    print(f"kernel routes: {json.dumps(counts, sort_keys=True)}")
    print(f"conv sites: {n_conv_packed}/{n_conv} packed")
    check_routes(counts, events, stats["bank_packed_sites"],
                 stats["compiled_forwards"])

    err, rel, scale, fwd_s = parity(engine, bank)
    print(f"served forward, bucket {PARITY_BATCH}, warm: median of "
          f"{TIMED_FORWARDS} {fwd_s * 1e3:.2f} ms (smoke timing, host clock)")
    print(f"parity, served forward vs ref oracles (batch {PARITY_BATCH}, "
          f"one segment): max abs err "
          f"{err:.3e}, max rel err {rel:.3e} (of max |ref| {scale:.3e}), "
          f"tolerance {PARITY_RTOL:.0e}")
    if not rel <= PARITY_RTOL:
        fail(f"Pallas forward differs from the reference: max rel err "
             f"{rel:.3e} > {PARITY_RTOL:.0e}")

    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
