"""Serving subsystem: step-wise samplers, weight bank, batching engine.

The bit-exactness tests pin the step-wise sampler refactor against inline
copies of the pre-refactor loops (the loop samplers are now thin drivers
over the eps-request state machine, so any drift here is a real change).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.tree import flatten_paths
from repro.configs.diffusion_presets import tiny_ddim
from repro.core import talora
from repro.core.qmodule import PackedW4, dequant_weight
from repro.diffusion.samplers import (ddim_sample, ddim_step,
                                      dpm_solver2_sample, plms_sample,
                                      sampler_advance, sampler_init,
                                      sampler_needed_t)
from repro.diffusion.schedule import make_schedule, sample_timesteps
from repro.nn.unet import io_sites, unet_apply, unet_init
from repro.quant.fakequant import (KIND_FP_SIGNED, KIND_FP_UNSIGNED,
                                   QuantizerParams)
from repro.serving import (DiffusionServingEngine, VirtualClock, WeightBank,
                           act_qps_from_plan, default_serving_plan,
                           segments_of)
from repro.serving.scheduler import ContinuousBatcher, GenRequest, RequestState

KEY = jax.random.PRNGKey(0)


def toy_eps_fn(x, tb):
    return 0.1 * x + 0.01 * jnp.sin(tb)[:, None, None, None]


# ---------------------------------------------------------------------------
# Step-wise sampler API reproduces the pre-refactor loops bit-exactly.
# (Reference implementations below are verbatim copies of the old loops.)
# ---------------------------------------------------------------------------


def _ref_ddim(eps_fn, sched, shape, key, *, steps, eta=0.0, collect_every=0):
    seq = sample_timesteps(sched.T, steps)
    key, k0 = jax.random.split(key)
    x = jax.random.normal(k0, shape)
    taps = []
    for i, t in enumerate(seq):
        t_prev = int(seq[i + 1]) if i + 1 < len(seq) else -1
        tb = jnp.full((shape[0],), t, jnp.float32)
        eps = eps_fn(x, tb)
        if collect_every and (i % collect_every == 0):
            taps.append((int(t), np.asarray(x)))
        key, kn = jax.random.split(key)
        noise = jax.random.normal(kn, shape) if eta > 0 else None
        x = ddim_step(sched, x, int(t), t_prev, eps, eta, noise)
    return x, taps


def _ref_plms(eps_fn, sched, shape, key, *, steps):
    seq = sample_timesteps(sched.T, steps)
    key, k0 = jax.random.split(key)
    x = jax.random.normal(k0, shape)
    old_eps = []
    for i, t in enumerate(seq):
        t_prev = int(seq[i + 1]) if i + 1 < len(seq) else -1
        tb = jnp.full((shape[0],), t, jnp.float32)
        eps = eps_fn(x, tb)
        if len(old_eps) == 0:
            eps_prime = eps
        elif len(old_eps) == 1:
            eps_prime = (3 * eps - old_eps[-1]) / 2
        elif len(old_eps) == 2:
            eps_prime = (23 * eps - 16 * old_eps[-1] + 5 * old_eps[-2]) / 12
        else:
            eps_prime = (55 * eps - 59 * old_eps[-1] + 37 * old_eps[-2]
                         - 9 * old_eps[-3]) / 24
        old_eps = (old_eps + [eps])[-3:]
        x = ddim_step(sched, x, int(t), t_prev, eps_prime)
    return x


def _ref_dpm(eps_fn, sched, shape, key, *, steps):
    seq = sample_timesteps(sched.T, steps)
    key, k0 = jax.random.split(key)
    x = jax.random.normal(k0, shape)

    def lam(t):
        ab = sched.alpha_bars[t]
        return 0.5 * jnp.log(ab / (1 - ab))

    def coeffs(t):
        ab = sched.alpha_bars[t]
        return jnp.sqrt(ab), jnp.sqrt(1 - ab)

    for i in range(len(seq) - 1):
        t, t_next = int(seq[i]), int(seq[i + 1])
        l_t, l_n = lam(t), lam(t_next)
        h = l_n - l_t
        l_mid = l_t + 0.5 * h
        lams = 0.5 * jnp.log(sched.alpha_bars / (1 - sched.alpha_bars))
        t_mid = int(jnp.argmin(jnp.abs(lams - l_mid)))
        a_t, s_t = coeffs(t)
        a_m, s_m = coeffs(t_mid)
        a_n, s_n = coeffs(t_next)
        tb = jnp.full((shape[0],), t, jnp.float32)
        eps1 = eps_fn(x, tb)
        u = (a_m / a_t) * x - s_m * jnp.expm1(0.5 * h) * eps1
        tbm = jnp.full((shape[0],), t_mid, jnp.float32)
        eps2 = eps_fn(u, tbm)
        x = (a_n / a_t) * x - s_n * jnp.expm1(h) * eps2
    t_last = int(seq[-1])
    tb = jnp.full((shape[0],), t_last, jnp.float32)
    x = ddim_step(sched, x, t_last, -1, eps_fn(x, tb))
    return x


@pytest.mark.parametrize("steps", [1, 7])
@pytest.mark.parametrize("eta", [0.0, 0.7])
def test_stepwise_ddim_bitexact(steps, eta):
    sched = make_schedule("linear", 100)
    shape = (2, 4, 4, 3)
    want, taps_w = _ref_ddim(toy_eps_fn, sched, shape, KEY, steps=steps,
                             eta=eta, collect_every=1)
    got, taps_g = ddim_sample(toy_eps_fn, sched, shape, KEY, steps=steps,
                              eta=eta, collect_every=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert [t for t, _ in taps_g] == [t for t, _ in taps_w]
    for (_, a), (_, b) in zip(taps_g, taps_w):
        np.testing.assert_array_equal(a, b)


def test_stepwise_plms_bitexact():
    sched = make_schedule("linear", 100)
    shape = (2, 4, 4, 3)
    want = _ref_plms(toy_eps_fn, sched, shape, KEY, steps=7)
    got = plms_sample(toy_eps_fn, sched, shape, KEY, steps=7)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("steps", [1, 2, 6])
def test_stepwise_dpm_bitexact(steps):
    sched = make_schedule("linear", 100)
    shape = (2, 4, 4, 3)
    want = _ref_dpm(toy_eps_fn, sched, shape, KEY, steps=steps)
    got = dpm_solver2_sample(toy_eps_fn, sched, shape, KEY, steps=steps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_step_machine_engine_drive_matches_loop():
    """Driving the state machine externally (engine-style) == loop driver."""
    sched = make_schedule("linear", 100)
    shape = (1, 4, 4, 3)
    st = sampler_init("dpm_solver2", sched, shape, KEY, steps=5)
    while not st.done:
        t = sampler_needed_t(st)
        tb = jnp.full((shape[0],), t, jnp.float32)
        sampler_advance(st, toy_eps_fn(st.eval_x, tb))
    want = dpm_solver2_sample(toy_eps_fn, sched, shape, KEY, steps=5)
    np.testing.assert_array_equal(np.asarray(st.x), np.asarray(want))


# ---------------------------------------------------------------------------
# Weight bank: segments, merge+pack, LRU.
# ---------------------------------------------------------------------------

T = 40


def _toy_bank(max_cached=4, lora_scale=0.1):
    key = jax.random.PRNGKey(1)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {"l0": {"w": jax.random.normal(k1, (8, 8))},
              "l1": {"w": jax.random.normal(k2, (8, 6))}}
    weights = {k: v for k, v in flatten_paths(params).items()}
    plan = default_serving_plan(weights)
    tcfg = talora.TALoRAConfig(hub_size=2, rank=2, t_emb_dim=16,
                               router_hidden=8)
    hubs = talora.init_lora_hub(k3, talora.lora_target_dims_from_weights(
        weights), tcfg)
    # randomize B so the merged delta is nonzero and differs per slot
    for name in hubs:
        hubs[name]["B"] = jax.random.normal(
            k4, hubs[name]["B"].shape) * lora_scale
    router = talora.init_router(k4, len(weights), tcfg)
    bank = WeightBank(params, plan, hubs, router, tcfg, T,
                      max_cached=max_cached)
    return bank, params, plan, hubs, router, tcfg


def test_segments_partition_schedule():
    bank, *_ = _toy_bank()
    assert bank.segments[0].t_lo == 0
    assert bank.segments[-1].t_hi == T - 1
    for a, b in zip(bank.segments, bank.segments[1:]):
        assert b.t_lo == a.t_hi + 1
        assert a.slots != b.slots  # maximal runs: adjacent segments differ
    for s in bank.segments:
        for t in range(s.t_lo, s.t_hi + 1):
            assert bank.segment_of(t) == s.index
            assert tuple(bank.signatures[t].tolist()) == s.slots


def test_segment_boundaries_match_allocation_histogram():
    """Fig. 7/9 histogram is constant inside every bank segment and equals
    the per-layer one-hot mean of the segment signature."""
    bank, params, plan, hubs, router, tcfg = _toy_bank()
    names = sorted(hubs)
    hist = np.asarray(talora.allocation_histogram(
        router, jnp.arange(T, dtype=jnp.float32), names, tcfg))
    for s in bank.segments:
        want = np.zeros((tcfg.hub_size,))
        for slot in s.slots:
            want[slot] += 1.0 / len(s.slots)
        for t in range(s.t_lo, s.t_hi + 1):
            np.testing.assert_allclose(hist[t], want, atol=1e-6)


def test_weight_bank_merges_and_packs_per_segment():
    bank, params, plan, hubs, router, tcfg = _toy_bank()
    p0 = bank.params_for_segment(0)
    flat0 = flatten_paths(p0)
    assert isinstance(flat0["l0/w"], PackedW4)
    assert isinstance(flat0["l1/w"], PackedW4)
    # decode ~= TALoRA-merged weight (within FP4 grid error)
    names = sorted(hubs)
    sels = {n: jax.nn.one_hot(bank.segments[0].slots[i], tcfg.hub_size)
            for i, n in enumerate(names)}
    merged = flatten_paths(talora.merge_into_tree(params, hubs, sels, tcfg))
    w = np.asarray(merged["l0/w"], np.float32)
    dq = np.asarray(dequant_weight(flat0["l0/w"], jnp.float32))
    scale = float(plan.sites["l0/w"].qp.maxval)
    assert np.abs(w.clip(-scale, scale) - dq).max() <= scale / 4  # E2M1 step
    # a site routed to a different slot packs different bytes; a site
    # routed to the same slot packs the same bytes
    other = next((s for s in bank.segments if s.slots != bank.segments[0].slots),
                 None)
    assert other is not None, "toy router collapsed to one signature"
    po = flatten_paths(bank.params_for_segment(other.index))
    for i, name in enumerate(names):
        same_slot = other.slots[i] == bank.segments[0].slots[i]
        assert np.array_equal(np.asarray(flat0[name].packed),
                              np.asarray(po[name].packed)) == same_slot, name


def test_weight_bank_lru_and_stats():
    bank, *_ = _toy_bank(max_cached=1)
    assert bank.n_segments >= 2, "toy router should produce several segments"
    bank.params_for_segment(0)
    bank.params_for_segment(0)
    assert (bank.hits, bank.misses) == (1, 1)
    bank.params_for_segment(1)          # evicts 0 (cap 1)
    assert bank.evictions == 1
    bank.params_for_segment(0)          # rebuilt -> miss
    assert (bank.hits, bank.misses) == (1, 3)
    assert 0.0 < bank.hit_rate < 1.0


def test_default_plan_and_act_qps_filter():
    w = {"a/w": jnp.ones((4, 4)), "io/w": jnp.ones((4, 4))}
    plan = default_serving_plan(w, io_sites={"io/w"})
    assert plan.sites["a/w"].qp.bits == 4
    assert plan.sites["io/w"].qp.bits == 8
    # act_qps: only per-tensor FP 4-bit activation sites pass the filter
    from repro.core.msfp import SiteInfo
    plan.sites["act_ok"] = SiteInfo(
        QuantizerParams(KIND_FP_UNSIGNED, 2, 1, 4, jnp.float32(3.0)),
        False, True, 0.0)
    plan.sites["act_vec"] = SiteInfo(
        QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.ones((4,))),
        False, False, 0.0)
    qps = act_qps_from_plan(plan)
    assert set(qps) == {"act_ok"}


def test_pack_param_tree_conv_layout_and_odd_width_fallback():
    key1, key2, key3 = jax.random.split(KEY, 3)
    params = {"c": {"w": jax.random.normal(key1, (3, 3, 4, 8))},
              "odd": {"w": jax.random.normal(key2, (3, 3, 4, 7))},
              "d": {"w": jax.random.normal(key3, (8, 8))}}
    from repro.serving.weight_bank import pack_param_tree
    plan = default_serving_plan(dict(flatten_paths(params)))
    tree, stats = pack_param_tree(params, plan)
    flat = flatten_paths(tree)
    # conv weights pack as (kh*kw*cin, cout/2) GEMM nibbles, HWIO shape kept
    assert isinstance(flat["c/w"], PackedW4)
    assert flat["c/w"].packed.shape == (36, 4)
    assert flat["c/w"].shape == (3, 3, 4, 8)
    assert dequant_weight(flat["c/w"], jnp.float32).shape == (3, 3, 4, 8)
    assert sorted(stats["packed"]) == ["c/w", "d/w"]
    # odd output width cannot nibble-pack -> bf16 fallback, forward stays total
    assert stats["fallback"] == ["odd/w"]
    assert flat["odd/w"].dtype == jnp.bfloat16


@pytest.mark.slow
def test_serve_forward_matches_fakequant_oracle_at_conv_sites(monkeypatch):
    """Packed serve-mode tiny-UNet forward == the fake-quant reference
    (FP4-grid weights + qdq acts at every planned site), with no PackedW4
    conv weight float-dequantized on the dispatch path.

    Regression: the pre-im2col serve path decoded conv packs to float and
    never quantized conv activations, so it matched the *unquantized*
    model at conv sites instead of the fake-quant one that calibration and
    TALoRA fine-tuning validated.
    """
    import repro.kernels.ops as ops
    from repro.common.tree import unflatten_paths
    from repro.quant.calibrate import QuantContext
    from repro.serving.weight_bank import pack_param_tree

    cfg = tiny_ddim(8)
    params = unet_init(KEY, cfg)
    weights = {k: v for k, v in flatten_paths(params).items()
               if k.endswith("/w") and v.ndim >= 2}
    plan = default_serving_plan(weights, io_sites=io_sites(params))
    packed, stats = pack_param_tree(params, plan)

    conv_sites = [k for k, v in flatten_paths(params).items()
                  if k.endswith("/w") and v.ndim == 4]
    non_io = sorted(set(conv_sites) - io_sites(params))
    assert non_io, "tiny UNet must have quantized conv sites"
    assert set(non_io) <= set(stats["packed"])
    assert set(conv_sites) & set(stats["fallback"]) <= io_sites(params)
    flat_packed = dict(flatten_paths(packed))
    assert all(flat_packed[k].packed.ndim == 2 for k in non_io)

    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(6.0))
    ctx = QuantContext("serve", act_qps={"*": act_qp})
    # Oracle: identical serve ctx over the *dequantized* dense weights —
    # i.e. fake-quant numerics (FP4-grid weights, qdq at every act site).
    dense = unflatten_paths({
        k: (dequant_weight(v, jnp.float32) if isinstance(v, PackedW4) else v)
        for k, v in flat_packed.items()})
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 8, 3))
    t = jnp.asarray([3.0, 17.0], jnp.float32)

    old = ops.FORCE
    ops.FORCE = "interpret"
    try:
        want = np.asarray(unet_apply(dense, x, t, cfg, ctx=ctx))

        def boom(*a, **k):
            raise AssertionError("packed serve forward decoded a conv "
                                 "weight / fell back to XLA")

        monkeypatch.setattr(ops._ref, "ref_w4a4_conv2d", boom)
        monkeypatch.setattr(ops._ref, "ref_w4_matmul", boom)
        monkeypatch.setattr(ops._ref, "ref_w4a4_matmul", boom)
        got = np.asarray(unet_apply(packed, x, t, cfg, ctx=ctx))
        monkeypatch.undo()

        plain = np.asarray(unet_apply(dense, x, t, cfg))  # no act quant
    finally:
        ops.FORCE = old
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=5e-3)
    # act quant is a real numerics effect: the silent full-precision-act
    # path (today's conv behavior) is measurably different
    assert not np.allclose(plain, want, atol=1e-4)


# ---------------------------------------------------------------------------
# Engine: admission/retirement, determinism, starvation guard.
# ---------------------------------------------------------------------------


def _stub_engine(max_batch, sched, bank, **kw):
    cfg = tiny_ddim(4)
    return DiffusionServingEngine(
        cfg, sched, bank, max_batch=max_batch,
        apply_fn=lambda params, x, tb, y, ctx: 0.1 * x, **kw)


def _single_segment_bank():
    params = {"l0": {"w": jnp.ones((4, 4))}}
    plan = default_serving_plan(flatten_paths(params))
    return WeightBank(params, plan, {}, None, None, T)


def test_engine_admission_and_retirement_order():
    sched = make_schedule("linear", T)
    bank = _single_segment_bank()
    assert bank.n_segments == 1
    eng = _stub_engine(2, sched, bank)
    for steps in (2, 2, 4, 1):
        eng.submit(steps=steps, seed=0)
    res = eng.run()
    # FIFO admission into 2 slots: rid 0,1 first; 2,3 only after both retire
    a = {rid: rs.admitted_at for rid, rs in res.items()}
    assert max(a[0], a[1]) <= min(a[2], a[3])
    # retirement order follows remaining work: 0,1 (2 evals) then 3 (1) then 2
    assert list(res.keys()) == [0, 1, 3, 2]
    assert [res[r].n_evals for r in (0, 1, 3, 2)] == [2, 2, 1, 4]


def test_engine_determinism_under_fixed_seeds():
    sched = make_schedule("linear", T)

    def run_once():
        bank, *_ = _toy_bank()
        eng = _stub_engine(3, sched, bank)
        for i in range(4):
            eng.submit(steps=3 + i % 2, seed=i, eta=0.5 * (i % 2),
                       sampler=("ddim", "plms")[i % 2])
        return {rid: np.asarray(rs.x0) for rid, rs in eng.run().items()}

    r1, r2 = run_once(), run_once()
    assert sorted(r1) == sorted(r2)
    for rid in r1:
        np.testing.assert_array_equal(r1[rid], r2[rid])


def test_scheduler_starvation_guard_and_grouping():
    sched = make_schedule("linear", T)
    b = ContinuousBatcher(max_batch=4, starvation_ticks=3)

    def mk(rid, tick):
        st = sampler_init("ddim", sched, (1, 2, 2, 3), KEY, steps=2)
        rs = RequestState(GenRequest(rid), st)
        rs.admitted_at = 0.0
        rs.last_advance_tick = tick
        b.inflight.append(rs)
        return rs

    a0, a1 = mk(0, tick=10), mk(1, tick=10)
    lone = mk(2, tick=5)   # hasn't advanced for 5 ticks
    groups = {7: [a0, a1], 9: [lone]}
    # starved request promotes its (smaller) group
    seg, members = b.select(groups, tick=10)
    assert seg == 9 and members == [lone]
    # without starvation the largest group wins
    lone.last_advance_tick = 10
    seg, members = b.select(groups, tick=10)
    assert seg == 7 and members == [a0, a1]


def test_engine_cfg_guidance_pairs_cond_uncond():
    sched = make_schedule("linear", T)
    bank = _single_segment_bank()
    cfg = dataclasses.replace(tiny_ddim(4), num_classes=5)
    calls = []

    def apply_fn(params, x, tb, y, ctx):
        calls.append((x.shape[0], y is not None))
        base = 0.1 * x
        if y is not None:
            base = base + 0.01 * y[:, None, None, None].astype(x.dtype)
        return base

    eng = DiffusionServingEngine(cfg, sched, bank, max_batch=4,
                                 apply_fn=apply_fn)
    eng.submit(steps=2, seed=0, y=3, guidance_scale=2.0)
    eng.submit(steps=2, seed=1)              # unconditional rider
    res = eng.run()
    assert len(res) == 2
    # each tick ran one uncond forward (guided pair + plain) and one cond
    sizes = sorted(c[0] for c in calls[:2])
    assert sizes == [1, 2]
    with pytest.raises(ValueError):
        eng.submit(steps=2, guidance_scale=1.0)   # guidance without label


def test_engine_buckets_pad_to_pow2_and_share_jit():
    """Distinct in-flight counts must share a power-of-two jit bucket
    (padded inputs, outputs masked by slicing) so the jit cache stays
    bounded under churny traffic."""
    sched = make_schedule("linear", T)
    bank = _single_segment_bank()
    sizes = []

    def apply_fn(params, x, tb, y, ctx):
        sizes.append(x.shape[0])
        return 0.1 * x + 0.01 * tb[:, None, None, None]

    cfg = tiny_ddim(4)
    eng = DiffusionServingEngine(cfg, sched, bank, max_batch=4,
                                 apply_fn=apply_fn)
    for steps in (3, 3, 3, 1):
        eng.submit(steps=steps, seed=0)
    res = eng.run()
    assert len(res) == 4
    # tick 1 runs all 4; ticks 2-3 run the remaining 3, padded into the
    # same 4-bucket. apply_fn runs under jit, so `sizes` records traces:
    # exactly one, at the padded bucket size — not one per batch size.
    assert sizes == [4]
    s = eng.stats()
    assert s["forwards"] == 3
    assert s["compiled_forwards"] == 1
    assert s["buckets"] == [4]
    assert s["padded_samples"] == 2
    assert [res[r].n_evals for r in range(4)] == [3, 3, 3, 1]


def test_engine_run_sleeps_to_arrival_instead_of_busy_polling():
    """While idle before the next arrival the driver sleeps once (up to
    the arrival, capped), not a 2 ms poll loop — and trace replay still
    admits strictly in arrival order."""
    sched = make_schedule("linear", T)
    bank = _single_segment_bank()
    eng = _stub_engine(2, sched, bank)
    arrivals = {0: 0.0, 1: 0.05, 2: 0.10}
    for rid, arr in arrivals.items():
        assert eng.submit(steps=1, seed=rid, arrival=arr) == rid
    res = eng.run()
    assert len(res) == 3
    admits = [res[r].admitted_at for r in (0, 1, 2)]
    assert admits == sorted(admits)
    for rid in (1, 2):
        assert res[rid].admitted_at >= arrivals[rid]
    # steps=1 requests retire instantly, so each inter-arrival gap is at
    # most one idle sleep (zero if a slow first jit eats the gap); the old
    # 2 ms busy-poll would have slept dozens of times
    assert eng.n_idle_sleeps <= 4
    assert eng.stats()["idle_sleeps"] == eng.n_idle_sleeps


def test_engine_idle_sleep_cap_zero_never_sleeps():
    """Regression: ``max_idle_sleep=0`` used to call ``time.sleep(0)``
    in a hot loop (wait capped at zero still entered the sleep branch,
    counting a bogus idle sleep per spin). A zero cap must mean "poll,
    never sleep" — the run completes and counts zero idle sleeps."""
    sched = make_schedule("linear", T)
    eng = _stub_engine(2, sched, _single_segment_bank())
    for rid, arr in enumerate((0.0, 0.02, 0.04)):
        eng.submit(steps=1, seed=rid, arrival=arr)
    res = eng.run(max_idle_sleep=0.0)
    assert len(res) == 3
    assert eng.n_idle_sleeps == 0
    assert eng.stats()["idle_sleeps"] == 0


def test_request_latency_none_for_expired():
    """Expired requests never ran: ``latency`` must stay None (keeping
    them out of completion percentiles) and ``expired_after_s`` records
    how long past arrival the scheduler held them before refusing."""
    sched = make_schedule("linear", T)
    eng = _stub_engine(2, sched, _single_segment_bank(),
                       clock=VirtualClock())
    dead = eng.submit(steps=1, seed=0, arrival=0.0, deadline=-1.0)
    ok = eng.submit(steps=1, seed=1, arrival=0.0)
    res = eng.run()
    assert res[dead].expired
    assert res[dead].latency is None
    assert res[dead].expired_after_s is not None
    assert res[dead].expired_after_s >= 0.0
    assert not res[ok].expired
    assert isinstance(res[ok].latency, float) and res[ok].latency >= 0.0
    assert res[ok].expired_after_s is None


# ---------------------------------------------------------------------------
# student_eps mixed-timestep guard (regression for t.reshape(-1)[0]).
# ---------------------------------------------------------------------------


def _tiny_bundle():
    from repro.diffusion.pipeline import QuantizedDiffusion

    cfg = tiny_ddim(8)
    params = unet_init(KEY, cfg)
    weights = {k: v for k, v in flatten_paths(params).items()
               if k.endswith("/w") and v.ndim >= 2}
    plan = default_serving_plan(weights, io_sites=io_sites(params))
    tcfg = talora.TALoRAConfig(hub_size=2, rank=2, t_emb_dim=16,
                               router_hidden=8)
    k1, k2, k3 = jax.random.split(KEY, 3)
    hubs = talora.init_lora_hub(k1, talora.lora_target_dims_from_weights(
        weights), tcfg)
    for name in hubs:
        hubs[name]["B"] = jax.random.normal(k3, hubs[name]["B"].shape) * 0.05
    router = talora.init_router(k2, len(weights), tcfg)
    sched = make_schedule("linear", T)
    return QuantizedDiffusion(cfg, sched, params, params, plan,
                              talora_cfg=tcfg, hubs=hubs, router=router)


@pytest.mark.slow
def test_student_eps_mixed_timesteps_routes_per_group():
    bundle = _tiny_bundle()
    x = jax.random.normal(KEY, (2, 8, 8, 3))
    # pick two timesteps with different routing signatures
    sig = np.asarray(talora.routing_signatures(
        bundle.router, jnp.arange(T), sorted(bundle.hubs),
        bundle.talora_cfg))
    t1 = 0
    t2 = next(t for t in range(1, T) if not np.array_equal(sig[t], sig[t1]))
    mixed = bundle.student_eps(x, jnp.asarray([t1, t2], jnp.float32))
    one = bundle.student_eps(x[:1], jnp.asarray([t1], jnp.float32))
    two = bundle.student_eps(x[1:], jnp.asarray([t2], jnp.float32))
    np.testing.assert_allclose(np.asarray(mixed[0]), np.asarray(one[0]),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mixed[1]), np.asarray(two[0]),
                               rtol=2e-4, atol=2e-5)
    # the old behavior (route everything for t[0]) is measurably different
    sels = talora.route(bundle.router, jnp.float32(t1),
                        sorted(bundle.hubs), bundle.talora_cfg)
    old = unet_apply(talora.merge_into_tree(bundle.q_params, bundle.hubs,
                                            sels, bundle.talora_cfg),
                     x, jnp.asarray([t1, t2], jnp.float32), bundle.cfg)
    assert not np.allclose(np.asarray(mixed[1]), np.asarray(old[1]),
                           atol=1e-6)


@pytest.mark.slow
def test_student_eps_traced_mixed_batch_raises():
    bundle = _tiny_bundle()
    x = jax.random.normal(KEY, (2, 8, 8, 3))
    with pytest.raises(ValueError, match="serving"):
        jax.jit(lambda x, t: bundle.student_eps(x, t))(
            x, jnp.asarray([1.0, 2.0]))
    # batch-1 tracing stays supported (scalar routing is unambiguous)
    out = jax.jit(lambda x, t: bundle.student_eps(x, t))(
        x[:1], jnp.asarray([1.0]))
    assert bool(jnp.isfinite(out).all())


# ---------------------------------------------------------------------------
# End-to-end acceptance: concurrent packed-path serving == single-request.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_end_to_end_packed_concurrent_matches_single():
    cfg = tiny_ddim(8)
    params = unet_init(KEY, cfg)
    weights = {k: v for k, v in flatten_paths(params).items()
               if k.endswith("/w") and v.ndim >= 2}
    plan = default_serving_plan(weights, io_sites=io_sites(params))
    tcfg = talora.TALoRAConfig(hub_size=2, rank=2, t_emb_dim=16,
                               router_hidden=8)
    k1, k2, k3 = jax.random.split(KEY, 3)
    hubs = talora.init_lora_hub(k1, talora.lora_target_dims_from_weights(
        weights), tcfg)
    for name in hubs:
        hubs[name]["B"] = jax.random.normal(k3, hubs[name]["B"].shape) * 0.05
    router = talora.init_router(k2, len(weights), tcfg)
    sched = make_schedule("linear", T)
    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(6.0))

    def make_engine(max_batch):
        bank = WeightBank(params, plan, hubs, router, tcfg, T,
                          max_cached=8)
        return DiffusionServingEngine(cfg, sched, bank,
                                      act_qps={"*": act_qp},
                                      max_batch=max_batch)

    jobs = [dict(steps=3, seed=0, sampler="ddim"),
            dict(steps=4, seed=1, sampler="ddim", eta=0.8),
            dict(steps=3, seed=2, sampler="plms"),
            dict(steps=2, seed=3, sampler="dpm_solver2")]
    eng = make_engine(max_batch=4)
    assert eng.ctx.mode == "serve"   # no fake-quant ctx on the serve path
    for j in jobs:
        eng.submit(**j)
    res = eng.run()
    assert len(res) == 4
    # forward really ran on packed integer weights
    flat = flatten_paths(eng.bank.params_for_segment(0))
    assert sum(isinstance(v, PackedW4) for v in flat.values()) > 20
    assert eng.stats()["bank_hit_rate"] > 0.0

    for rid, j in enumerate(jobs):
        single = make_engine(max_batch=1)
        single.submit(**j)
        ref = single.run()[0]
        assert res[rid].n_evals == ref.n_evals
        np.testing.assert_allclose(np.asarray(res[rid].x0),
                                   np.asarray(ref.x0),
                                   rtol=1e-4, atol=1e-4)
