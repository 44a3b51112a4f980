"""Packed-W4 conv2d: implicit GEMM (no patch matrix) + im2col fallback.

Conv sites are the UNet's workhorse, and the serving path must give them
the same treatment dense sites get: packed nibbles decoded in VMEM, with
the MSFP activation snap fused into the matmul. Two routes:

**Implicit GEMM** (``w4a4_conv2d_implicit``, the fix for the patch-matrix
HBM round-trip): the unfold is folded into the kernel's ``BlockSpec``
index maps. The grid is (B, half, cout-blocks, cin-blocks); each program
receives the whole (padded) spatial slab of one batch element for one
cin block straight from the NHWC activation — the (B*OH*OW, kh*kw*cin)
patch matrix is never materialized in HBM. The kernel statically unrolls
the kh*kw taps as strided in-VMEM slices of the slab, accumulating
``slab[ki::sh, kj::sw, :] @ W[ki, kj]`` against the nibble pack reshaped
(kh*kw, cin, cout/2) — a free view of the flattened 2D pack. The MSFP
act snap runs once per (batch, cin-block) on the in-VMEM slab (snap-once
scratch, as in ``w4_matmul``), and per-tile iota masks restore exact
zeros at the SAME-padding / alignment-padding positions afterwards — so
*unsigned* activation grids (which map 0 to the zero-point) fuse too,
matching the oracle's quantize-then-pad order without the old
pre-quantize HBM pass.

**im2col fallback** (``w4a4_conv2d_im2col``): unfolds x into the patch
matrix and feeds the fused W4A4 matmul. Kept as the oracle for the
implicit route's index maps and as the compiled route for what the
implicit kernel cannot take (``implicit_supported``): strided convs, and
convs whose whole-slab blocks exceed the VMEM budget.

Zero-padding correctness (im2col route): SAME padding inserts exact
zeros into the patch matrix. A *signed* MSFP snap maps 0 -> 0, so fusing
the snap over patches equals quantize-then-pad (the fake-quant oracle's
order). Unsigned formats map 0 to the grid floor (the zero-point), so on
this route ``ops.w4a4_conv2d`` pre-quantizes x for those and runs the
plain packed matmul; the implicit route handles them in-kernel instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qmodule import PackedW4
from repro.kernels.msfp_quant import _qdq_block
from repro.kernels.w4_matmul import (_decode_block, _split_half_rows,
                                     lane_tile, mxu_dot, w4_matmul_2d,
                                     w4a4_matmul_2d)
from repro.quant.fakequant import KIND_FP_SIGNED, QuantizerParams
from repro.quant.formats import FPFormat


def conv_pads(h: int, w: int, kh: int, kw: int, stride: tuple[int, int],
              padding) -> tuple[tuple[int, int], tuple[int, int]]:
    """Resolve a conv padding spec ('SAME'/'VALID' or explicit pairs) to
    ((ph_lo, ph_hi), (pw_lo, pw_hi)) for the spatial dims."""
    if isinstance(padding, str):
        pads = lax.padtype_to_pads((h, w), (kh, kw), stride, padding)
    else:
        pads = [tuple(p) for p in padding]
    (p0, p1), (p2, p3) = pads
    return (int(p0), int(p1)), (int(p2), int(p3))


def im2col(x: jnp.ndarray, kh: int, kw: int, *, stride: tuple[int, int],
           padding) -> tuple[jnp.ndarray, tuple[int, int, int]]:
    """NHWC x -> (B*OH*OW, kh*kw*cin) patch matrix + (B, OH, OW).

    Patch columns are ordered (kh, kw, cin)-major — the flattening of an
    HWIO kernel's leading axes — so ``patches @ w.reshape(-1, cout)``
    equals ``conv_general_dilated(x, w)``.
    """
    b, h, w, c = x.shape
    sh, sw = stride
    (ph0, ph1), (pw0, pw1) = conv_pads(h, w, kh, kw, stride, padding)
    if ph0 or ph1 or pw0 or pw1:
        x = jnp.pad(x, ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0)))
    oh = (h + ph0 + ph1 - kh) // sh + 1
    ow = (w + pw0 + pw1 - kw) // sw + 1
    cols = []
    for i in range(kh):
        for j in range(kw):
            cols.append(x[:, i:i + sh * (oh - 1) + 1:sh,
                          j:j + sw * (ow - 1) + 1:sw, :])
    patches = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=-1)
    return patches.reshape(b * oh * ow, kh * kw * c), (b, oh, ow)


def w4a4_conv2d_im2col(x: jnp.ndarray, pw: PackedW4,
                       act_qp: QuantizerParams | None, *,
                       stride: tuple[int, int], padding,
                       interpret: bool = False) -> jnp.ndarray:
    """x: (B, H, W, cin) @ packed HWIO W4 -> (B, OH, OW, cout).

    ``act_qp`` (signed, per-tensor) fuses the MSFP act snap into the
    matmul kernel; None runs the plain packed matmul (caller pre-quantized
    or no act quant planned).
    """
    kh, kw, cin, cout = pw.shape
    assert x.shape[-1] == cin, (x.shape, pw.shape)
    patches, (b, oh, ow) = im2col(x, kh, kw, stride=stride, padding=padding)
    if act_qp is None:
        out = w4_matmul_2d(patches, pw.packed, pw.scale, pw.zero_point,
                           exp_bits=pw.exp_bits, man_bits=pw.man_bits,
                           signed=pw.signed, interpret=interpret)
    else:
        assert act_qp.kind == KIND_FP_SIGNED and jnp.ndim(act_qp.maxval) == 0
        out = w4a4_matmul_2d(
            patches, pw.packed, pw.scale, pw.zero_point,
            act_qp.maxval, act_qp.zero_point,
            exp_bits=pw.exp_bits, man_bits=pw.man_bits, signed=pw.signed,
            act_exp_bits=act_qp.exp_bits, act_man_bits=act_qp.man_bits,
            act_signed=True, interpret=interpret)
    return out.reshape(b, oh, ow, cout)


# ---------------------------------------------------------------------------
# Implicit GEMM: the unfold lives in the BlockSpec index maps.
# ---------------------------------------------------------------------------

# The scoped VMEM a Pallas kernel may use on TPU v5e unless it asks for
# more (found by compiling for v5e: a kernel that needs 13.2 MiB compiles
# without a limit, one that needs 17.9 MiB is refused). The implicit route
# takes a conv only while ``implicit_vmem_bytes`` stays within it.
IMPLICIT_VMEM_BUDGET = 16 * 1024 * 1024


def _conv_geometry(x_shape, kh, kw, stride, padding):
    """Static geometry: output size and the exact input span the taps read.

    ``hs = (oh-1)*sh + kh`` (and ``ws`` likewise) is the padded-input span
    the strided taps actually touch — it can be *smaller* than the padded
    input when the stride doesn't cover the tail, so the slab is sliced,
    never over-read.
    """
    _, h, w, _ = x_shape
    sh, sw = stride
    (ph0, ph1), (pw0, pw1) = conv_pads(h, w, kh, kw, stride, padding)
    oh = (h + ph0 + ph1 - kh) // sh + 1
    ow = (w + pw0 + pw1 - kw) // sw + 1
    hs = (oh - 1) * sh + kh
    ws = (ow - 1) * sw + kw
    return oh, ow, hs, ws, ph0, pw0


def implicit_vmem_bytes(x_shape, pw_shape, stride, padding, *,
                        fused: bool, itemsize: int = 4,
                        bc: int = 128, bn: int = 128) -> int:
    """Per-program VMEM bytes of ``w4a4_conv2d_implicit`` compiled for TPU.

    Counts what Mosaic holds at once: the input slab, packed-weight and
    output blocks, each double-buffered, with the slab's W dim padded to 8
    sublanes; the accumulator and snap-once scratch; and the body's
    temporaries — the decoded weight block (int32 codes and f32 values),
    the f32 tap accumulator, two tap windows, the snapped slab (two
    copies, four once cin spans several blocks), and for f32 operands
    what the f32-precision tap dots add (5.2-6.0 KiB per output row at
    the UNet widths). ``tools/vmem_fit.py`` prints it beside the smallest
    scoped-VMEM limit the v5e compiler accepts; it must never be below.
    """
    kh, kw, cin, cout = pw_shape
    oh, ow, hs, ws, _, _ = _conv_geometry(x_shape, kh, kw, stride, padding)
    bc = min(bc, cin)
    bn = lane_tile(max(cout // 2, 1), bn, interpret=False)
    cin_p = cin + (-cin) % bc
    ws8 = ws + (-ws) % 8
    mp = oh * ow + (-(oh * ow)) % 8
    slab = hs * ws8 * bc * itemsize
    blocks = 2 * (slab + kh * kw * bc * bn + mp * bn * itemsize)
    scratch = mp * bn * 4 + (hs * ws8 * cin_p * itemsize if fused else 0)
    temps = 2 * kh * kw * bc * bn * 4 + mp * bn * 4 + 2 * mp * bc * itemsize
    if fused:
        temps += (2 if cin_p == bc else 4) * hs * ws8 * bc * 4
    if itemsize == 4:
        temps += 12 * mp * bn * 4      # bf16 splits and partial products
    return blocks + scratch + temps


def implicit_supported(x_shape, pw_shape, stride, padding, *, fused: bool,
                       itemsize: int = 4) -> bool:
    """Whether the compiled implicit kernel can take this conv.

    Unit stride only: Mosaic lowers a strided in-VMEM tap slice to a
    gather and refuses it ("Only 2D gather is supported"). And the
    per-program footprint must fit ``IMPLICIT_VMEM_BUDGET``.
    """
    if tuple(stride) != (1, 1):
        return False
    return implicit_vmem_bytes(x_shape, pw_shape, stride, padding,
                               fused=fused, itemsize=itemsize
                               ) <= IMPLICIT_VMEM_BUDGET


def _implicit_kernel(x_ref, p_ref, s_ref, z_ref, amz_ref, o_ref, acc_ref,
                     *xq_ref, fmt: FPFormat, act_fmt: FPFormat | None,
                     act_signed: bool, kh, kw, sh, sw, oh, ow, nc, bc,
                     valid, mp):
    """One program: every tap's contribution of one cin block to one
    (batch, half, cout-block) output tile. Grid (B, 2, nj, nc), c innermost
    accumulating; the x slab arrives as a (1, hs, ws, bc) block gathered
    straight from the padded NHWC activation by the index map."""
    hh = pl.program_id(1)
    j = pl.program_id(2)
    c = pl.program_id(3)
    ph0, h, pw0, w, cin = valid

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if act_fmt is not None and xq_ref:
        xq = xq_ref[0]

        @pl.when((hh == 0) & (j == 0))
        def _snap():
            slab = _qdq_block(x_ref[0], amz_ref[0, 0], amz_ref[0, 1],
                              act_fmt, act_signed)
            if not act_signed:
                # Unsigned grids map 0 to the zero-point: restore exact
                # zeros at every padded position (SAME/alignment spatial
                # pad, cin alignment pad) so the taps and the zp rowsum
                # see quantize-then-pad — the fake-quant oracle's order.
                r = lax.broadcasted_iota(jnp.int32, slab.shape, 0)
                col = lax.broadcasted_iota(jnp.int32, slab.shape, 1)
                ch = lax.broadcasted_iota(jnp.int32, slab.shape, 2)
                ok = ((r >= ph0) & (r < ph0 + h)
                      & (col >= pw0) & (col < pw0 + w)
                      & (ch + c * bc < cin))
                slab = jnp.where(ok, slab, jnp.zeros_like(slab))
            xq[:, :, pl.ds(c * bc, bc)] = slab

        slab = xq[:, :, pl.ds(c * bc, bc)]
    else:
        slab = x_ref[0]

    shift = hh * 4
    codes = (p_ref[...].astype(jnp.int32) >> shift) & 0xF
    scale = s_ref[0, :] * (1.0 / fmt.base_max)
    wt = _decode_block(codes, fmt, scale[None, None, :]).astype(slab.dtype)

    acc = jnp.zeros((oh * ow, acc_ref.shape[1]), jnp.float32)
    for ki in range(kh):
        for kj in range(kw):
            xv = slab[ki:ki + sh * (oh - 1) + 1:sh,
                      kj:kj + sw * (ow - 1) + 1:sw, :]
            xv = xv.reshape(oh * ow, xv.shape[-1])
            acc += mxu_dot(xv, wt[ki * kw + kj])
            if not fmt.signed:
                rowsum = jnp.sum(xv.astype(jnp.float32), axis=1,
                                 keepdims=True)
                acc += rowsum * z_ref[0, :][None, :]
    if mp != oh * ow:
        acc = jnp.pad(acc, ((0, mp - oh * ow), (0, 0)))
    acc_ref[...] += acc

    @pl.when(c == nc - 1)
    def _flush():
        o_ref[...] = acc_ref[...][None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stride", "padding", "bc", "bn",
                                             "interpret"))
def w4a4_conv2d_implicit(x: jnp.ndarray, pw: PackedW4,
                         act_qp: QuantizerParams | None, *,
                         stride: tuple[int, int], padding,
                         bc: int = 128, bn: int = 128,
                         interpret: bool = False) -> jnp.ndarray:
    """Implicit-GEMM conv: x (B, H, W, cin) @ packed HWIO W4 -> NHWC out.

    No patch matrix: x is zero-padded once (spatial + cin/lane alignment)
    and the kernel's index maps hand each program the slab it gathers taps
    from. ``act_qp`` may be *signed or unsigned* per-tensor FP — the snap
    runs in-kernel with per-tile pad masking (see ``_implicit_kernel``).
    """
    kh, kw, cin, cout = pw.shape
    b, h, w, c = x.shape
    assert c == cin, (x.shape, pw.shape)
    sh, sw = stride
    oh, ow, hs, ws, ph0, pw0 = _conv_geometry(x.shape, kh, kw, stride,
                                              padding)
    bc = min(bc, cin)
    pc = (-cin) % bc
    nc = (cin + pc) // bc

    # Pad to the exact tap span (the span can undershoot the padded input
    # when the stride skips the tail — slice in that case), plus cin pad.
    xp = jnp.pad(x, ((0, 0), (ph0, max(0, hs - h - ph0)),
                     (pw0, max(0, ws - w - pw0)), (0, pc)))
    xp = xp[:, :hs, :ws, :]

    n_half = cout // 2
    bn = lane_tile(n_half, bn, interpret=interpret)
    pn = (-n_half) % bn
    nj = (n_half + pn) // bn
    packed3 = pw.packed.reshape(kh * kw, cin, n_half)
    if pc or pn:
        packed3 = jnp.pad(packed3, ((0, 0), (0, pc), (0, pn)))
    nh = n_half + pn

    sc = jnp.asarray(pw.scale, jnp.float32)
    sc = jnp.broadcast_to(sc.reshape(-1) if sc.ndim else sc, (cout,))
    zp = jnp.asarray(pw.zero_point, jnp.float32)
    zp = jnp.broadcast_to(zp.reshape(-1) if zp.ndim else zp, (cout,))
    s_op = _split_half_rows(sc, n_half, pn)
    z_op = _split_half_rows(zp, n_half, pn)

    fmt = FPFormat(pw.exp_bits, pw.man_bits, pw.signed)
    if act_qp is not None:
        act_fmt = act_qp.fmt
        act_signed = act_qp.kind == KIND_FP_SIGNED
        amz = jnp.stack([jnp.asarray(act_qp.maxval, jnp.float32),
                         jnp.asarray(act_qp.zero_point, jnp.float32)])
    else:
        act_fmt, act_signed = None, True
        amz = jnp.zeros((2,), jnp.float32)
    amz = amz.reshape(1, 2)

    mp = oh * ow + (-(oh * ow)) % 8
    scratch = [pltpu.VMEM((mp, bn), jnp.float32)]
    if act_fmt is not None:
        scratch.append(pltpu.VMEM((hs, ws, cin + pc), x.dtype))

    out = pl.pallas_call(
        functools.partial(
            _implicit_kernel, fmt=fmt, act_fmt=act_fmt,
            act_signed=act_signed, kh=kh, kw=kw, sh=sh, sw=sw, oh=oh,
            ow=ow, nc=nc, bc=bc, valid=(ph0, h, pw0, w, cin), mp=mp),
        grid=(b, 2, nj, nc),
        in_specs=[
            pl.BlockSpec((1, hs, ws, bc), lambda bi, hh, j, c: (bi, 0, 0, c)),
            pl.BlockSpec((kh * kw, bc, bn), lambda bi, hh, j, c: (0, c, j)),
            pl.BlockSpec((None, 1, bn), lambda bi, hh, j, c: (hh, 0, j)),
            pl.BlockSpec((None, 1, bn), lambda bi, hh, j, c: (hh, 0, j)),
            pl.BlockSpec((1, 2), lambda bi, hh, j, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, mp, bn),
                               lambda bi, hh, j, c: (bi, 0, hh * nj + j)),
        out_shape=jax.ShapeDtypeStruct((b, mp, 2 * nh), x.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(xp, packed3, s_op, z_op, amz)
    out = out[:, :oh * ow]
    if pn:
        out = jnp.concatenate([out[..., :n_half], out[..., nh:nh + n_half]],
                              axis=-1)
    else:
        out = out[..., :cout]
    return out.reshape(b, oh, ow, cout)
