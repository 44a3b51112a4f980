"""Functional NN layers with explicit param dicts and quantization hooks.

Every layer is an (init, apply) pair over plain dicts — no module framework,
so params are trivially shardable / checkpointable / scannable.

Quantization integrates via two hooks threaded through ``apply``:
  * ``ctx``  — a ``repro.quant.QuantContext``: ``ctx.act(site, x)`` observes
    or fake-quantizes the layer input (site = '/'-joined param path).
  * weights — a dense array (possibly already fake-quantized), or a
    ``PackedW4`` (serving form), dispatched here.

Dense-weight matmuls and convs run at f32 precision (HIGHEST): on the TPU
the default takes one bf16 pass over f32 operands.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.qmodule import PackedW4, w4_dense_xla
from repro.quant.calibrate import (QuantContext, OFF,  # noqa: F401
                                   resolve_act_qp)


def _maybe_quant_act(ctx: QuantContext | None, site: str | None, x):
    if ctx is None or site is None:
        return x
    return ctx.act(site, x)


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: float | None = None) -> dict:
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d_in)
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p: dict, x: jnp.ndarray, *, ctx: QuantContext | None = None,
                site: str | None = None, act_qp=None) -> jnp.ndarray:
    """``act_qp`` (a ``QuantizerParams``) requests serve-mode activation
    quantization: fused into the packed matmul kernel for PackedW4 weights,
    a standalone ``msfp_quantize`` pass for dense (bf16-fallback) weights —
    so serving matches the fake-quant oracle at every planned act site. A
    serve-mode ``ctx`` can supply it per site when the caller doesn't."""
    x = _maybe_quant_act(ctx, site, x)
    w = p["w"]
    if act_qp is None and ctx is not None:
        act_qp = ctx.serving_qp(site)  # site=None still gets the '*' qp
    if isinstance(w, PackedW4):
        from repro.kernels import ops  # late import; kernels depend on nn types
        y = ops.w4a4_matmul(x, w, act_qp)
    else:
        if act_qp is not None:
            from repro.kernels import ops
            x = ops.msfp_quantize(x, act_qp)
        y = jnp.matmul(x, w.astype(x.dtype), precision=lax.Precision.HIGHEST)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Conv2D (NHWC, HWIO) — the UNet's workhorse
# ---------------------------------------------------------------------------


def conv2d_init(key, c_in: int, c_out: int, kernel: int = 3, *,
                bias: bool = True, dtype=jnp.float32,
                scale: float | None = None) -> dict:
    fan_in = c_in * kernel * kernel
    scale = scale if scale is not None else 1.0 / jnp.sqrt(fan_in)
    p = {"w": jax.random.normal(key, (kernel, kernel, c_in, c_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((c_out,), dtype)
    return p


def conv2d_apply(p: dict, x: jnp.ndarray, *, stride: int = 1,
                 padding: str | Sequence = "SAME",
                 ctx: QuantContext | None = None,
                 site: str | None = None, act_qp=None) -> jnp.ndarray:
    """Mirrors ``dense_apply``'s serving contract: PackedW4 weights route
    through the W4A4 conv kernels (implicit GEMM where it fits, im2col
    fallback — never decode-then-XLA-conv; see ``ops.w4a4_conv2d``), and
    ``act_qp`` / serve-mode ``ctx.serving_qp`` quantizes the input either
    inside that kernel or, for dense-fallback weights, in a standalone
    pass — conv sites see the same numerics the fake-quant model did."""
    x = _maybe_quant_act(ctx, site, x)
    w = p["w"]
    if act_qp is None and ctx is not None:
        act_qp = ctx.serving_qp(site)
    if isinstance(w, PackedW4):
        from repro.kernels import ops
        y = ops.w4a4_conv2d(x, w, act_qp, stride=stride, padding=padding)
    else:
        if act_qp is not None:
            from repro.kernels import ops
            x = ops.msfp_quantize(x, act_qp)
        y = lax.conv_general_dilated(
            x, w.astype(x.dtype), window_strides=(stride, stride),
            padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(dim: int, dtype=jnp.float32) -> dict:
    return {"g": jnp.ones((dim,), dtype)}


def rmsnorm_apply(p: dict, x: jnp.ndarray, *, eps: float = 1e-6,
                  plus_one: bool = False) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    n = xf * lax.rsqrt(var + eps)
    g = p["g"].astype(jnp.float32)
    g = g + 1.0 if plus_one else g  # gemma convention stores g-1
    return (n * g).astype(x.dtype)


def layernorm_init(dim: int, dtype=jnp.float32) -> dict:
    return {"g": jnp.ones((dim,), dtype), "b": jnp.zeros((dim,), dtype)}


def layernorm_apply(p: dict, x: jnp.ndarray, *, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    n = (xf - mu) * lax.rsqrt(var + eps)
    return (n * p["g"].astype(jnp.float32)
            + p["b"].astype(jnp.float32)).astype(x.dtype)


def groupnorm_init(dim: int, dtype=jnp.float32) -> dict:
    return {"g": jnp.ones((dim,), dtype), "b": jnp.zeros((dim,), dtype)}


def groupnorm_apply(p: dict, x: jnp.ndarray, *, groups: int = 32,
                    eps: float = 1e-5) -> jnp.ndarray:
    """NHWC group norm."""
    b, h, w, c = x.shape
    g = min(groups, c)
    xf = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    mu = xf.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    n = ((xf - mu) * lax.rsqrt(var + eps)).reshape(b, h, w, c)
    return (n * p["g"] + p["b"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32) -> dict:
    return {"table": jax.random.normal(key, (vocab, dim), dtype) * 0.02}


def embed_apply(p: dict, ids: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["table"], ids, axis=0)


def embed_attend(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Tied-readout logits: x @ table.T."""
    return x @ p["table"].T.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def silu(x):
    return x * jax.nn.sigmoid(x)


ACTIVATIONS = {
    "silu": silu,
    "gelu": jax.nn.gelu,
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}
