"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Their dots and convs run at f32 precision (HIGHEST) on every backend, so
on the TPU they are the f32 truth that the kernels are held to, whatever
the ambient default matmul precision.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core.qmodule import (PackedW4, decode_codes, dequant_weight,
                                unpack_nibbles)
from repro.quant.fakequant import QuantizerParams, apply_qdq
from repro.quant.formats import FPFormat

KV4_FMT = FPFormat(2, 1, True)  # signed E2M1 for KV-cache values


def ref_msfp_qdq(x: jnp.ndarray, qp: QuantizerParams) -> jnp.ndarray:
    """Oracle for the fused fake-quant kernel."""
    return apply_qdq(x, qp)


def ref_w4_matmul(x: jnp.ndarray, pw: PackedW4,
                  dtype=jnp.bfloat16) -> jnp.ndarray:
    """Oracle for the packed-W4 matmul kernel: decode then dot."""
    codes = unpack_nibbles(pw.packed)
    w = decode_codes(codes, pw.fmt, pw.scale, pw.zero_point, jnp.float32)
    y = jnp.dot(x.astype(jnp.float32), w, precision=lax.Precision.HIGHEST)
    return y.astype(dtype)


def ref_w4a4_matmul(x: jnp.ndarray, pw: PackedW4, act_qp: QuantizerParams,
                    dtype=jnp.bfloat16) -> jnp.ndarray:
    """Oracle for the fused W4A4 kernel: qdq(x) through HBM, then matmul."""
    return ref_w4_matmul(apply_qdq(x, act_qp), pw, dtype)


def ref_w4a4_conv2d(x: jnp.ndarray, pw: PackedW4,
                    act_qp: QuantizerParams | None = None, *,
                    stride: tuple[int, int] = (1, 1), padding="SAME",
                    dtype=jnp.bfloat16) -> jnp.ndarray:
    """Oracle for the im2col conv route: qdq(x), decode W, XLA conv.

    Act quant precedes the conv's zero padding — the fake-quant model's
    order — which the fused route matches (signed snaps keep 0 at 0;
    unsigned acts are pre-quantized by the dispatcher).
    """
    if act_qp is not None:
        x = apply_qdq(x, act_qp)
    w = dequant_weight(pw, jnp.float32)   # reshaped back to HWIO
    y = lax.conv_general_dilated(
        x.astype(jnp.float32), w, window_strides=stride, padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    return y.astype(dtype)


def ref_kv4_encode(t: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for FP4 KV-cache encode: per-(…, head) absmax scale, E2M1.

    t: (..., hd) -> packed (..., hd/2) uint8, scale (...,) f16.
    """
    from repro.core.qmodule import encode_codes, pack_nibbles

    absmax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax, 1e-6)
    codes = encode_codes(t, KV4_FMT, scale[..., None])
    return pack_nibbles(codes), scale.astype(jnp.float16)


def ref_kv4_decode(packed: jnp.ndarray, scale: jnp.ndarray,
                   dtype=jnp.bfloat16) -> jnp.ndarray:
    codes = unpack_nibbles(packed)
    return decode_codes(codes, KV4_FMT, scale.astype(jnp.float32)[..., None],
                        0.0, dtype)
