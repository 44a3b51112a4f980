"""Launch-layer units: input specs, abstract quantization, grad accum."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.configs.shapes import SHAPES, ShapeSpec, cells, LONG_OK
from repro.core.qmodule import PackedW4
from repro.common.tree import flatten_paths
from repro.launch.steps import (abstract_params, input_specs,
                                make_train_step, quantize_abstract)
from repro.launch.dryrun import with_depth
from repro.models.lm import lm_init
from repro.optim.adam import AdamConfig, adam_init

KEY = jax.random.PRNGKey(0)


def test_cells_cover_40_minus_long_skips():
    from repro.configs.registry import ARCH_IDS
    cs = cells(ARCH_IDS)
    assert len(cs) == 10 * 4 - (10 - len(LONG_OK))
    assert ("mamba2-370m", "long_500k") in cs
    assert ("qwen1.5-0.5b", "long_500k") not in cs


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"],
                         ids=["default", "from_env"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says, set by nothing in code; else to a fixed, gitignored directory
    inside the checkout."""
    from pathlib import Path

    from repro.common import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
    got = compile_cache.setup_compile_cache()
    root = Path(__file__).resolve().parents[1]
    if env_dir is None:
        assert got == str(root / ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", got)]
        assert ".jax_cache/" in (root / ".gitignore").read_text().split("\n")
    else:
        assert got == env_dir and updates == []


def test_input_specs_shapes():
    cfg = get_config("llava-next-mistral-7b")
    sp = input_specs(cfg, SHAPES["prefill_32k"])
    assert sp["batch"]["tokens"].shape == (32, 32768)
    assert sp["batch"]["extra"].shape == (32, 576, 1024)
    spd = input_specs(cfg, SHAPES["decode_32k"])
    assert spd["token"].shape == (128, 1)
    # llava caches: (groups, B, S, kv, hd)
    k = spd["caches"]["blocks"][0]["k"]
    assert k.shape == (32, 128, 32768, 8, 128)


def test_decode_specs_windowed_cache_is_ring_sized():
    cfg = get_config("gemma3-27b")
    spd = input_specs(cfg, SHAPES["long_500k"])
    local_k = spd["caches"]["blocks"][0]["k"]      # window=1024 ring
    global_k = spd["caches"]["blocks"][5]["k"]     # global layer
    assert local_k.shape[2] == 1024
    assert global_k.shape[2] == 524288


def test_quantize_abstract_marks_only_big_weights():
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    ap = abstract_params(cfg)
    qt = quantize_abstract(ap)
    flat = jax.tree_util.tree_flatten_with_path(qt)[0]
    kinds = {type(l).__name__ for _, l in flat}
    # embeddings stay dense (io convention); matmuls become packed
    has_packed = any(isinstance(l, jax.ShapeDtypeStruct) is False
                     for _, l in flat)
    from repro.common.tree import flatten_paths as fp
    # embed stays a ShapeDtypeStruct
    assert isinstance(qt["embed"], jax.ShapeDtypeStruct)


def test_quantize_for_serving_per_channel_scales():
    """per_channel=True must produce channel-resolved scales for both 2D
    and stacked (scanned) weights, and stay numerically close to dense."""
    from repro.launch.steps import quantize_lm_for_serving

    key = jax.random.PRNGKey(0)
    w2d = jax.random.normal(key, (16, 8))
    w3d = jax.random.normal(key, (3, 16, 8))  # (groups, in, out)
    params = {"attn": {"wq": {"w": w2d}}, "blocks": [{"mlp": {"down": {"w": w3d}}}]}
    q = quantize_lm_for_serving(params, searched=False, per_channel=True)
    pq = q["attn"]["wq"]["w"]
    assert isinstance(pq, PackedW4) and pq.scale.shape == (8,)
    ps = q["blocks"][0]["mlp"]["down"]["w"]
    assert isinstance(ps, PackedW4) and ps.scale.shape == (3, 1, 8)
    # per-channel dequant error <= per-tensor dequant error (same format)
    from repro.core.qmodule import dequant_weight
    qt = quantize_lm_for_serving(params, searched=False, per_channel=False)
    err_pc = float(jnp.mean((dequant_weight(ps, jnp.float32) - w3d) ** 2))
    err_pt = float(jnp.mean((dequant_weight(
        qt["blocks"][0]["mlp"]["down"]["w"], jnp.float32) - w3d) ** 2))
    assert err_pc <= err_pt + 1e-9


def test_with_depth_preserves_period():
    cfg = get_config("gemma3-27b")
    c1 = with_depth(cfg, 1)
    assert c1.n_groups == 1 and c1.first_k_dense == cfg.first_k_dense
    assert c1.n_layers == cfg.first_k_dense + cfg.period


@pytest.mark.slow
def test_grad_accum_matches_single_step():
    cfg = get_config("smollm-135m", smoke=True)
    p = lm_init(KEY, cfg)
    acfg = AdamConfig(lr=1e-3, clip_norm=None)
    opt = adam_init(p, acfg)
    toks = jax.random.randint(KEY, (4, 16), 0, cfg.vocab)
    batch = {"tokens": toks}
    s1 = make_train_step(cfg, acfg, grad_accum=1)
    s2 = make_train_step(cfg, acfg, grad_accum=2)
    p1, _, m1 = jax.jit(s1)(p, opt, batch)
    p2, _, m2 = jax.jit(s2)(p, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-2)
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
    assert max(jax.tree.leaves(d)) < 5e-2
