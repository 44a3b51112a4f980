"""Jit'd wrappers around the Pallas kernels with XLA fallbacks.

Dispatch policy: on TPU the Pallas kernels run compiled; off the TPU the
fast XLA serving path (``kernels.xla_serve``) runs for real numerics,
while tests exercise the kernels in interpret mode against the ref
oracles. Set ``FORCE="pallas"`` / ``"xla"`` / ``"interpret"`` to override
(tests use it) — ``"xla"`` pins the *pure reference* oracles, bypassing
the fast serving path too. Interpret mode runs only when asked for:
``FORCE="pallas"`` off the TPU raises rather than interpreting.

Conv routing (``CONV_ROUTE``): the Pallas conv has two routes — the
implicit-GEMM kernel (no patch matrix; the default on compiled TPU for
the convs it can compile, see ``conv.implicit_supported``) and the im2col
+ fused-matmul route (the index-map oracle, and what interpret mode runs
by default so the golden replay trace keeps its pinned digest).
``"implicit"`` / ``"im2col"`` force a route; ``"auto"`` applies the
policy above.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.qmodule import PackedW4
from repro.kernels import ref as _ref
from repro.quant.fakequant import (KIND_FP_SIGNED, KIND_FP_UNSIGNED,
                                   KIND_INT_AFFINE, QuantizerParams)

FORCE: str | None = None
CONV_ROUTE: str = "auto"  # "auto" | "implicit" | "im2col"

# Profiling hook (serving/obs/kernel_profile installs it; ops never
# imports obs). When set, every dispatch decision routes through
# PROFILER.call(op, route_label, thunk, probe) — counted when tracing
# into a jit program, timed when eager. None costs one global read.
PROFILER = None


def _dispatch(op: str, route: str, thunk, probe=None):
    if PROFILER is None:
        return thunk()
    return PROFILER.call(op, route, thunk, probe=probe)


def _route_label() -> str:
    """Label for the Pallas branch: compiled vs interpret-mode."""
    return "interpret" if _interpret() else "pallas"


def _use_pallas() -> bool:
    if FORCE == "pallas" or FORCE == "interpret":
        return True
    if FORCE == "xla":
        return False
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Whether a Pallas branch runs interpreted; only called once
    ``_use_pallas()`` chose one."""
    if FORCE == "interpret":
        return True
    if jax.default_backend() == "tpu":
        return False
    raise RuntimeError(
        f"ops.FORCE={FORCE!r} compiles the Pallas kernels, which needs a "
        f"TPU, but jax.default_backend() is {jax.default_backend()!r}; "
        "set FORCE='interpret' to run the kernel code interpreted")


def _use_fast_xla() -> bool:
    """The fast XLA serving path: default (unforced) dispatch off-TPU."""
    return FORCE is None and jax.default_backend() != "tpu"


def msfp_quantize(x: jnp.ndarray, qp: QuantizerParams) -> jnp.ndarray:
    """Fused fake-quant (no STE — serving path; training uses quant.ste_qdq).

    The Pallas kernel takes per-tensor FP parameters; INT-affine and
    vector (per-channel) maxvals fall back to the XLA reference.
    """
    if _use_pallas() and qp.kind != 2 and jnp.ndim(qp.maxval) == 0:
        from repro.kernels.msfp_quant import msfp_qdq
        return _dispatch("msfp_quantize", _route_label(),
                         lambda: msfp_qdq(x, qp, interpret=_interpret()),
                         probe=x)
    if _use_fast_xla():
        from repro.kernels import xla_serve
        return _dispatch("msfp_quantize", "xla_fast",
                         lambda: xla_serve.fast_qdq(x, qp),  # bit-exact
                         probe=x)
    return _dispatch("msfp_quantize", "ref",
                     lambda: _ref.ref_msfp_qdq(x, qp), probe=x)


def _pallas_w4_ok(pw: PackedW4) -> bool:
    """The Pallas kernel covers the full MSFP format space (signed and
    unsigned ExMy, scalar or per-output-channel scale) for single 2D packs;
    stacked (scanned) packs with per-slice scales stay on the XLA path."""
    if jnp.ndim(pw.packed) != 2:
        return False
    if jnp.ndim(pw.scale) == 0:
        return True
    return (jnp.ndim(pw.scale) == 1
            and pw.scale.shape[0] == 2 * pw.packed.shape[-1])


def w4_matmul(x: jnp.ndarray, pw: PackedW4) -> jnp.ndarray:
    """x: (..., K) @ packed W4 (K, N/2-packed) -> (..., N)."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    if _use_pallas() and _pallas_w4_ok(pw):
        from repro.kernels.w4_matmul import w4_matmul_2d
        out = _dispatch(
            "w4_matmul", _route_label(),
            lambda: w4_matmul_2d(x2, pw.packed, pw.scale, pw.zero_point,
                                 exp_bits=pw.exp_bits, man_bits=pw.man_bits,
                                 signed=pw.signed, interpret=_interpret()),
            probe=x)
    elif _use_fast_xla() and jnp.ndim(pw.packed) == 2:
        from repro.kernels import xla_serve
        out = _dispatch("w4_matmul", "xla_fast",
                        lambda: xla_serve.w4_matmul(x2, pw, x.dtype),
                        probe=x)
    else:
        out = _dispatch("w4_matmul", "ref",
                        lambda: _ref.ref_w4_matmul(x2, pw, x.dtype),
                        probe=x)
    return out.reshape(*lead, out.shape[-1])


def w4a4_matmul(x: jnp.ndarray, pw: PackedW4,
                act_qp: QuantizerParams | None) -> jnp.ndarray:
    """Fused activation-quant + W4 matmul: qdq(x, act_qp) @ W in one kernel.

    Saves one full HBM round-trip over x versus msfp_quantize followed by
    w4_matmul. ``act_qp`` must be an FP (signed/unsigned) per-tensor
    quantizer; INT-affine activations fall back to qdq-then-matmul.
    """
    if act_qp is None:
        return w4_matmul(x, pw)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    if (_use_pallas() and _pallas_w4_ok(pw)
            and act_qp.kind != KIND_INT_AFFINE
            and jnp.ndim(act_qp.maxval) == 0):
        from repro.kernels.w4_matmul import w4a4_matmul_2d
        out = _dispatch(
            "w4a4_matmul", _route_label(),
            lambda: w4a4_matmul_2d(
                x2, pw.packed, pw.scale, pw.zero_point,
                act_qp.maxval, act_qp.zero_point,
                exp_bits=pw.exp_bits, man_bits=pw.man_bits,
                signed=pw.signed,
                act_exp_bits=act_qp.exp_bits, act_man_bits=act_qp.man_bits,
                act_signed=(act_qp.kind == KIND_FP_SIGNED),
                interpret=_interpret()),
            probe=x)
    elif _use_fast_xla() and act_qp.kind != KIND_INT_AFFINE:
        from repro.kernels import xla_serve
        out = _dispatch("w4a4_matmul", "xla_fast",
                        lambda: xla_serve.fused_matmul(x2, pw, act_qp,
                                                       x.dtype),
                        probe=x)
    else:
        out = _dispatch("w4a4_matmul", "ref",
                        lambda: _ref.ref_w4a4_matmul(x2, pw, act_qp,
                                                     x.dtype),
                        probe=x)
    return out.reshape(*lead, out.shape[-1])


def _normalize_stride(stride) -> tuple[int, int]:
    return (stride, stride) if isinstance(stride, int) else tuple(stride)


def _normalize_padding(padding):
    """Hashable (jit-static) padding spec."""
    if isinstance(padding, str):
        return padding
    return tuple(tuple(int(q) for q in p) for p in padding)


def _conv_route(x, pw, strides, pads, fused: bool, interpret: bool) -> str:
    """Pick the Pallas conv route. ``auto``: compiled TPU runs the
    implicit-GEMM kernel for every conv it can compile (unit stride,
    whole-slab blocks within the VMEM budget) and im2col otherwise;
    interpret mode keeps the im2col oracle route (the golden replay
    trace's digest is pinned to its accumulation order)."""
    if CONV_ROUTE in ("implicit", "im2col"):
        return CONV_ROUTE
    if interpret:
        return "im2col"
    from repro.kernels.conv import implicit_supported
    ok = implicit_supported(x.shape, pw.shape, strides, pads, fused=fused,
                            itemsize=x.dtype.itemsize)
    return "implicit" if ok else "im2col"


def w4a4_conv2d(x: jnp.ndarray, pw: PackedW4,
                act_qp: QuantizerParams | None = None, *,
                stride=1, padding="SAME") -> jnp.ndarray:
    """NHWC conv on a packed HWIO W4 weight.

    Pallas routes (see ``_conv_route``):
      * implicit GEMM — the index maps gather input slabs straight from
        the NHWC activation (no patch matrix). Signed *and* unsigned
        per-tensor FP act quantizers fuse: the in-kernel snap masks the
        pad positions back to exact zeros per tile, so the old
        pre-quantize-through-HBM round-trip only remains for INT-affine
        and per-channel act params.
      * im2col + fused matmul — the index-map oracle and VMEM-overflow
        fallback. Only signed per-tensor acts fuse here (SAME padding's
        zeros must survive the snap; unsigned grids map 0 to the
        zero-point), others pre-quantize.
    Off-TPU the fast XLA tap-loop (``xla_serve.implicit_conv``) serves
    unforced dispatch; ``FORCE="xla"`` pins the decode+conv oracle.
    """
    strides = _normalize_stride(stride)
    pads = _normalize_padding(padding)
    if _use_pallas() and len(pw.shape) == 4 and _pallas_w4_ok(pw):
        interpret = _interpret()
        route = _conv_route(x, pw, strides, pads, fused=act_qp is not None,
                            interpret=interpret)
        fusable = (KIND_FP_SIGNED, KIND_FP_UNSIGNED) if route == "implicit" \
            else (KIND_FP_SIGNED,)
        if act_qp is not None and not (act_qp.kind in fusable
                                       and jnp.ndim(act_qp.maxval) == 0):
            x = msfp_quantize(x, act_qp)
            act_qp = None
        if route == "implicit":
            from repro.kernels.conv import w4a4_conv2d_implicit
            return _dispatch(
                "w4a4_conv2d", f"{_route_label()}:implicit",
                lambda: w4a4_conv2d_implicit(x, pw, act_qp, stride=strides,
                                             padding=pads,
                                             interpret=interpret),
                probe=x)
        from repro.kernels.conv import w4a4_conv2d_im2col
        return _dispatch(
            "w4a4_conv2d", f"{_route_label()}:im2col",
            lambda: w4a4_conv2d_im2col(x, pw, act_qp, stride=strides,
                                       padding=pads, interpret=interpret),
            probe=x)
    fast = _use_fast_xla() and len(pw.shape) == 4 and _pallas_w4_ok(pw)
    fusable = (KIND_FP_SIGNED, KIND_FP_UNSIGNED) if fast \
        else (KIND_FP_SIGNED,)
    if act_qp is not None and not (act_qp.kind in fusable
                                   and jnp.ndim(act_qp.maxval) == 0):
        x = msfp_quantize(x, act_qp)
        act_qp = None
    if fast:
        from repro.kernels import xla_serve
        return _dispatch(
            "w4a4_conv2d", "xla_fast",
            lambda: xla_serve.implicit_conv(x, pw, act_qp, stride=strides,
                                            padding=pads, dtype=x.dtype),
            probe=x)
    return _dispatch(
        "w4a4_conv2d", "ref",
        lambda: _ref.ref_w4a4_conv2d(x, pw, act_qp, stride=strides,
                                     padding=pads, dtype=x.dtype),
        probe=x)


def kv4_encode(t: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """t: (..., hd) -> packed (..., hd/2) uint8 + scale (...,) f16."""
    lead = t.shape[:-1]
    hd = t.shape[-1]
    t2 = t.reshape(-1, hd)
    if _use_pallas():
        from repro.kernels.kv4 import kv4_encode_2d
        packed, scale = _dispatch(
            "kv4_encode", _route_label(),
            lambda: kv4_encode_2d(t2, interpret=_interpret()), probe=t)
    else:
        packed, scale = _dispatch("kv4_encode", "ref",
                                  lambda: _ref.ref_kv4_encode(t2), probe=t)
    return packed.reshape(*lead, hd // 2), scale.reshape(lead)


def kv4_decode(packed: jnp.ndarray, scale: jnp.ndarray,
               dtype=jnp.bfloat16) -> jnp.ndarray:
    lead = packed.shape[:-1]
    hh = packed.shape[-1]
    p2 = packed.reshape(-1, hh)
    s2 = scale.reshape(-1)
    if _use_pallas():
        from repro.kernels.kv4 import kv4_decode_2d
        out = _dispatch(
            "kv4_decode", _route_label(),
            lambda: kv4_decode_2d(p2, s2, dtype=dtype,
                                  interpret=_interpret()),
            probe=packed)
    else:
        out = _dispatch("kv4_decode", "ref",
                        lambda: _ref.ref_kv4_decode(p2, s2, dtype),
                        probe=packed)
    return out.reshape(*lead, 2 * hh)
