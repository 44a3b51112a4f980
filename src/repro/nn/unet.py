"""DDPM/LDM-style UNet epsilon-predictor — the paper's model family.

Faithful to the DDIM (CIFAR/CelebA) and LDM (LSUN/ImageNet) backbones:
ResBlocks with timestep-embedding injection, spatial self-attention at
configured resolutions, down/upsampling, optional class conditioning.
Every conv/dense is a quant site; the SiLU between norm and conv is what
creates the paper's AALs.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.nn.embeddings import timestep_embedding
from repro.nn.layers import (conv2d_apply, conv2d_init, dense_apply,
                             dense_init, groupnorm_apply, groupnorm_init, silu)


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    image_size: int = 32
    in_ch: int = 3
    out_ch: int = 3
    ch: int = 128
    ch_mult: tuple = (1, 2, 2, 2)
    num_res_blocks: int = 2
    attn_resolutions: tuple = (16,)
    num_classes: int | None = None
    gn_groups: int = 32

    @property
    def temb_dim(self) -> int:
        return self.ch * 4


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _res_init(key, c_in, c_out, temb_dim, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "norm1": groupnorm_init(c_in, dtype),
        "conv1": conv2d_init(ks[0], c_in, c_out, 3, dtype=dtype),
        "temb": dense_init(ks[1], temb_dim, c_out, bias=True, dtype=dtype),
        "norm2": groupnorm_init(c_out, dtype),
        "conv2": conv2d_init(ks[2], c_out, c_out, 3, dtype=dtype, scale=1e-5),
    }
    if c_in != c_out:
        p["skip"] = conv2d_init(ks[3], c_in, c_out, 1, dtype=dtype)
    return p


def _attn_init(key, c, dtype):
    ks = jax.random.split(key, 4)
    return {
        "norm": groupnorm_init(c, dtype),
        "q": dense_init(ks[0], c, c, bias=True, dtype=dtype),
        "k": dense_init(ks[1], c, c, bias=True, dtype=dtype),
        "v": dense_init(ks[2], c, c, bias=True, dtype=dtype),
        "proj": dense_init(ks[3], c, c, bias=True, dtype=dtype, scale=1e-5),
    }


def unet_init(key, cfg: UNetConfig, dtype=jnp.float32) -> dict:
    keys = iter(jax.random.split(key, 4096))
    p: dict[str, Any] = {
        "temb0": dense_init(next(keys), cfg.ch, cfg.temb_dim, bias=True, dtype=dtype),
        "temb1": dense_init(next(keys), cfg.temb_dim, cfg.temb_dim, bias=True, dtype=dtype),
        "conv_in": conv2d_init(next(keys), cfg.in_ch, cfg.ch, 3, dtype=dtype),
    }
    if cfg.num_classes:
        p["class_emb"] = {"table": jax.random.normal(
            next(keys), (cfg.num_classes, cfg.temb_dim), dtype) * 0.02}

    res = cfg.image_size
    chans = [cfg.ch]
    c_cur = cfg.ch
    for i, mult in enumerate(cfg.ch_mult):
        c_out = cfg.ch * mult
        for j in range(cfg.num_res_blocks):
            p[f"down_{i}.res_{j}"] = _res_init(next(keys), c_cur, c_out,
                                               cfg.temb_dim, dtype)
            c_cur = c_out
            if res in cfg.attn_resolutions:
                p[f"down_{i}.attn_{j}"] = _attn_init(next(keys), c_cur, dtype)
            chans.append(c_cur)
        if i != len(cfg.ch_mult) - 1:
            p[f"down_{i}.downsample"] = conv2d_init(next(keys), c_cur, c_cur, 3,
                                                    dtype=dtype)
            res //= 2
            chans.append(c_cur)

    p["mid.res_0"] = _res_init(next(keys), c_cur, c_cur, cfg.temb_dim, dtype)
    p["mid.attn"] = _attn_init(next(keys), c_cur, dtype)
    p["mid.res_1"] = _res_init(next(keys), c_cur, c_cur, cfg.temb_dim, dtype)

    for i in reversed(range(len(cfg.ch_mult))):
        c_out = cfg.ch * cfg.ch_mult[i]
        for j in range(cfg.num_res_blocks + 1):
            c_skip = chans.pop()
            p[f"up_{i}.res_{j}"] = _res_init(next(keys), c_cur + c_skip, c_out,
                                             cfg.temb_dim, dtype)
            c_cur = c_out
            if res in cfg.attn_resolutions:
                p[f"up_{i}.attn_{j}"] = _attn_init(next(keys), c_cur, dtype)
        if i != 0:
            p[f"up_{i}.upsample"] = conv2d_init(next(keys), c_cur, c_cur, 3,
                                                dtype=dtype)
            res *= 2

    p["norm_out"] = groupnorm_init(c_cur, dtype)
    p["conv_out"] = conv2d_init(next(keys), c_cur, cfg.out_ch, 3, dtype=dtype,
                                scale=1e-5)
    return p


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------


def _res_apply(p, x, temb, cfg, *, ctx, site):
    h = silu(groupnorm_apply(p["norm1"], x, groups=cfg.gn_groups))
    h = conv2d_apply(p["conv1"], h, ctx=ctx, site=f"{site}/conv1")
    h = h + dense_apply(p["temb"], silu(temb), ctx=ctx,
                        site=f"{site}/temb")[:, None, None, :]
    h = silu(groupnorm_apply(p["norm2"], h, groups=cfg.gn_groups))
    h = conv2d_apply(p["conv2"], h, ctx=ctx, site=f"{site}/conv2")
    if "skip" in p:
        x = conv2d_apply(p["skip"], x, ctx=ctx, site=f"{site}/skip")
    return x + h


def _attn_apply(p, x, cfg, *, ctx, site):
    b, hh, ww, c = x.shape
    h = groupnorm_apply(p["norm"], x, groups=cfg.gn_groups).reshape(b, hh * ww, c)
    q = dense_apply(p["q"], h, ctx=ctx, site=f"{site}/q")
    k = dense_apply(p["k"], h, ctx=ctx, site=f"{site}/k")
    v = dense_apply(p["v"], h, ctx=ctx, site=f"{site}/v")
    f32 = jax.lax.Precision.HIGHEST
    w = jax.nn.softmax(jnp.einsum("bqc,bkc->bqk", q, k, precision=f32,
                                  preferred_element_type=jnp.float32)
                       * (c ** -0.5), axis=-1).astype(v.dtype)
    o = jnp.einsum("bqk,bkc->bqc", w, v, precision=f32)
    o = dense_apply(p["proj"], o, ctx=ctx, site=f"{site}/proj")
    return x + o.reshape(b, hh, ww, c)


def unet_apply(p: dict, x: jnp.ndarray, t: jnp.ndarray, cfg: UNetConfig, *,
               y: jnp.ndarray | None = None, ctx=None) -> jnp.ndarray:
    """x: (B,H,W,C) noisy image; t: (B,) timesteps -> predicted eps."""
    temb = timestep_embedding(t, cfg.ch)
    temb = dense_apply(p["temb0"], temb, ctx=ctx, site="temb0")
    temb = dense_apply(p["temb1"], silu(temb), ctx=ctx, site="temb1")
    if cfg.num_classes and y is not None:
        temb = temb + jnp.take(p["class_emb"]["table"], y, axis=0)

    h = conv2d_apply(p["conv_in"], x, ctx=ctx, site="conv_in")
    hs = [h]
    res = cfg.image_size
    for i in range(len(cfg.ch_mult)):
        for j in range(cfg.num_res_blocks):
            h = _res_apply(p[f"down_{i}.res_{j}"], h, temb, cfg, ctx=ctx,
                           site=f"down_{i}.res_{j}")
            if f"down_{i}.attn_{j}" in p:
                h = _attn_apply(p[f"down_{i}.attn_{j}"], h, cfg, ctx=ctx,
                                site=f"down_{i}.attn_{j}")
            hs.append(h)
        if i != len(cfg.ch_mult) - 1:
            h = conv2d_apply(p[f"down_{i}.downsample"], h, stride=2, ctx=ctx,
                             site=f"down_{i}.downsample")
            res //= 2
            hs.append(h)

    h = _res_apply(p["mid.res_0"], h, temb, cfg, ctx=ctx, site="mid.res_0")
    h = _attn_apply(p["mid.attn"], h, cfg, ctx=ctx, site="mid.attn")
    h = _res_apply(p["mid.res_1"], h, temb, cfg, ctx=ctx, site="mid.res_1")

    for i in reversed(range(len(cfg.ch_mult))):
        for j in range(cfg.num_res_blocks + 1):
            h = jnp.concatenate([h, hs.pop()], axis=-1)
            h = _res_apply(p[f"up_{i}.res_{j}"], h, temb, cfg, ctx=ctx,
                           site=f"up_{i}.res_{j}")
            if f"up_{i}.attn_{j}" in p:
                h = _attn_apply(p[f"up_{i}.attn_{j}"], h, cfg, ctx=ctx,
                                site=f"up_{i}.attn_{j}")
        if i != 0:
            b, hh, ww, c = h.shape
            h = jax.image.resize(h, (b, hh * 2, ww * 2, c), "nearest")
            h = conv2d_apply(p[f"up_{i}.upsample"], h, ctx=ctx,
                             site=f"up_{i}.upsample")
            res *= 2

    h = silu(groupnorm_apply(p["norm_out"], h, groups=cfg.gn_groups))
    return conv2d_apply(p["conv_out"], h, ctx=ctx, site="conv_out")


def io_sites(p: dict) -> set[str]:
    """Input/output layers the paper keeps at 8-bit."""
    return {"conv_in", "conv_in/w", "conv_out", "conv_out/w"}


def lora_target_sites(p: dict) -> dict[str, tuple[int, int]]:
    """LoRA dims for every conv/dense weight (paper: all quantized layers).

    Keys are '/'-joined weight paths (e.g. 'mid.attn/q/w'); convs use the
    flattened (kh*kw*cin, cout) factorization (see talora.merge_into_tree).
    """
    from repro.common.tree import flatten_paths
    from repro.core.talora import lora_target_dims_from_weights

    flat = {k: v for k, v in flatten_paths(p).items()
            if k.endswith("/w") and v.ndim >= 2}
    return lora_target_dims_from_weights(flat)
