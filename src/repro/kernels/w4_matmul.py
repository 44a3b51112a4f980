"""Pallas TPU kernels: packed-FP4 weight matmul with in-VMEM dequant.

y = x @ W where W is stored as packed nibbles (split-half layout:
packed[k, j] holds logical columns j (lo nibble) and j + N/2 (hi)).
HBM traffic for the weight is the *packed* bytes (K*N/2); nibbles are
expanded and decoded to bf16 inside VMEM, then fed to the MXU.

Covered format space (the full MSFP family):
  * signed ExMy, scalar or per-output-channel scale;
  * unsigned ExMy with zero-point: dequant is ``mag * scale + zp``. The
    additive zp never materializes in the weight tile — it contributes
    ``zp_n * sum_k x[i, k]`` to output (i, n), accumulated per k-block
    alongside the MXU dot (one VPU row-reduction per block).
  * fused W4A4 (``w4a4_matmul_2d``): the MSFP activation fake-quant snap
    (``msfp_quant._qdq_block``) is applied to the x tile in VMEM before
    the dot, removing the separate qdq kernel's HBM round-trip over x.

Grid: (M/bm, half, (N/2)/bn, K/bk) — the `half` axis selects the nibble
and addresses the corresponding output column block, so no lane interleave
is ever needed. K is the innermost (arbitrary) axis accumulating into an
f32 VMEM scratch. Scales/zero-points ride as a (2, 1, N/2) operand whose
leading (half) block dim is squeezed, blocked (1, bn) and indexed by the
(half, j) grid axes, so each program sees exactly the scales of the
columns it decodes.

TPU tiling: Mosaic needs the last two block dims divisible by (8, 128) or
equal to the array's, so the column tile ``bn`` is always a multiple of
128 lanes. A half narrower than that (N/2 = 64 at the 128-channel layers)
is zero-padded up to one lane tile; the MXU is 128 wide, so the padded
dot costs no more passes than the narrow one would. Interpret mode has no
such rule and keeps the narrow tile: the CPU dot rounds differently at
another width, and the golden replay digest is pinned to the narrow one.

Snap-once re-tiling: with M outermost, every (half, j) program for a fixed
row block i revisits the same x tiles, so the fused path snaps each
(bm, bk) x tile exactly once — on the first (h == 0, j == 0) sweep over
k-blocks — into a persistent (bm, K) VMEM scratch that later programs
read back. The old layout recomputed the snap per (half, j) program,
2 * N/(2*bn) times per tile. Falls back to per-program snapping when the
scratch would exceed ``XQ_VMEM_BUDGET`` (huge K). Accumulation order per
output tile is unchanged, so outputs are bit-identical either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.msfp_quant import _qdq_block
from repro.quant.formats import FPFormat


def _decode_block(codes, fmt: FPFormat, scale):
    """Nibble codes (already masked to 4 bits) -> f32 values * scale.

    ``scale`` broadcasts: a scalar (per-tensor) or a (1, bn) row
    (per-output-channel). Unsigned zero-points are handled by the caller
    via the rank-1 correction term, never here.
    """
    man = fmt.man_bits
    nbits = fmt.exp_bits + fmt.man_bits
    c = codes.astype(jnp.int32)
    if fmt.signed:
        sign = (c >> nbits) & 1
        c = c & ((1 << nbits) - 1)
    if fmt.exp_bits == 0:
        mag = c.astype(jnp.float32) / 2**man
    else:
        p = c >> man
        m = (c & (2**man - 1)).astype(jnp.float32)
        mag = jnp.where(p == 0, m / 2**man,
                        jnp.exp2((p - 1).astype(jnp.float32)) * (1 + m / 2**man))
    val = mag * scale
    if fmt.signed:
        val = jnp.where(sign == 1, -val, val)
    return val


def mxu_dot(x, w):
    """``x @ w`` accumulated in f32, at the operands' own precision.

    On the TPU a dot of f32 operands at the default precision takes one
    bf16 MXU pass, rounding x and the scaled weight to bf16; HIGHEST keeps
    the f32 the model computes in (Mosaic refuses it for bf16 operands,
    which need no more than the one pass). The CPU computes f32 dots at
    f32 either way, so interpret-mode outputs do not move.
    """
    prec = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    return jnp.dot(x, w, precision=prec, preferred_element_type=jnp.float32)


# Fused-path activation scratch cap: above this the snap-once (bm, K)
# buffer no longer fits comfortably alongside the operand tiles and the
# kernel reverts to per-program snapping (same outputs, more VPU work).
XQ_VMEM_BUDGET = 4 * 1024 * 1024


def _snap_tile(x, amz_ref, k, bk, k_valid, act_fmt, act_signed):
    """MSFP-snap one (bm, bk) activation tile in VMEM."""
    x = _qdq_block(x, amz_ref[0, 0], amz_ref[0, 1], act_fmt, act_signed)
    if not act_signed:
        # Unsigned act quant maps the zero-padded K rows to qdq(0) != 0
        # (the grid floor is the zero-point); zero them back so neither
        # the dot nor the zp rowsum sees phantom rows.
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(col + k * bk < k_valid, x, jnp.zeros_like(x))
    return x


def _kernel(x_ref, p_ref, s_ref, z_ref, amz_ref, o_ref, acc_ref, *xq_ref,
            fmt: FPFormat, nk: int, k_valid: int, act_fmt: FPFormat | None,
            act_signed: bool, bk: int):
    h = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if act_fmt is not None and xq_ref:
        # Snap-once: the first (h, j) = (0, 0) sweep over k writes the
        # snapped tiles into the persistent (bm, K) scratch; every later
        # (h, j) program for this row block reads them back.
        xq = xq_ref[0]

        @pl.when((h == 0) & (j == 0))
        def _snap():
            xq[:, pl.ds(k * bk, bk)] = _snap_tile(
                x_ref[...], amz_ref, k, bk, k_valid, act_fmt, act_signed)

        x = xq[:, pl.ds(k * bk, bk)]
    elif act_fmt is not None:
        # Fallback (scratch over budget): snap per program, old behavior.
        x = _snap_tile(x_ref[...], amz_ref, k, bk, k_valid, act_fmt,
                       act_signed)
    else:
        x = x_ref[...]

    shift = h * 4
    codes = (p_ref[...].astype(jnp.int32) >> shift) & 0xF
    scale = s_ref[0, :] * (1.0 / fmt.base_max)          # (bn,) per-channel
    w = _decode_block(codes, fmt, scale[None, :]).astype(x.dtype)
    acc_ref[...] += mxu_dot(x, w)
    if not fmt.signed:
        # zp contributes zp_n * sum_k x_ik; accumulate the block's rowsum.
        rowsum = jnp.sum(x.astype(jnp.float32), axis=1, keepdims=True)
        acc_ref[...] += rowsum * z_ref[0, :][None, :]

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


LANES = 128


def lane_tile(n_half: int, bn: int = LANES, *, interpret: bool) -> int:
    """Column tile for one nibble half. Compiled: ``bn`` capped at the
    half's width rounded up to whole lane tiles (a multiple of 128).
    Interpret: ``bn`` capped at the half's own width."""
    if interpret:
        return min(bn, n_half)
    return min(bn, n_half + (-n_half) % LANES)


def _split_half_rows(vec: jnp.ndarray, n_half: int, pad: int) -> jnp.ndarray:
    """(N,) channel vector -> (2, 1, N/2 [+pad]) rows matching the nibble
    halves; the unit middle dim makes a (1, bn) block tile-legal."""
    op = jnp.stack([vec[:n_half], vec[n_half:]])
    if pad:
        op = jnp.pad(op, ((0, 0), (0, pad)))
    return op[:, None, :]


def _w4_call(x, packed, scale, zero_point, act_mz, *, fmt: FPFormat,
             act_fmt: FPFormat | None, act_signed: bool,
             bm: int, bn: int, bk: int, interpret: bool) -> jnp.ndarray:
    m, k = x.shape
    k2, n_half = packed.shape
    assert k == k2, (x.shape, packed.shape)
    n = 2 * n_half
    bm = min(bm, m)
    bn = lane_tile(n_half, bn, interpret=interpret)
    bk = min(bk, k)
    pm, pk, pn = (-m) % bm, (-k) % bk, (-n_half) % bn
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        packed = jnp.pad(packed, ((0, pk), (0, pn)))
    mm, kk = x.shape
    nh = packed.shape[1]
    nk = kk // bk

    # Normalize scale / zero_point to per-channel rows in split-half layout;
    # padded columns get scale 0 so their (sliced-off) outputs stay finite.
    sc = jnp.asarray(scale, jnp.float32)
    sc = jnp.broadcast_to(sc.reshape(-1) if sc.ndim else sc, (n,))
    zp = jnp.asarray(zero_point, jnp.float32)
    zp = jnp.broadcast_to(zp.reshape(-1) if zp.ndim else zp, (n,))
    s_op = _split_half_rows(sc, n_half, pn)
    z_op = _split_half_rows(zp, n_half, pn)
    amz = jnp.stack([jnp.asarray(act_mz[0], jnp.float32),
                     jnp.asarray(act_mz[1], jnp.float32)]).reshape(1, 2)

    # Snap-once scratch: one (bm, K) activation buffer, persistent across
    # the sequential grid so all (half, j) programs of a row block share it.
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    snap_once = (act_fmt is not None
                 and bm * kk * x.dtype.itemsize <= XQ_VMEM_BUDGET)
    if snap_once:
        scratch.append(pltpu.VMEM((bm, kk), x.dtype))

    out = pl.pallas_call(
        functools.partial(_kernel, fmt=fmt, nk=nk, k_valid=k,
                          act_fmt=act_fmt, act_signed=act_signed, bk=bk),
        grid=(mm // bm, 2, nh // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, h, j, kb: (i, kb)),
            pl.BlockSpec((bk, bn), lambda i, h, j, kb: (kb, j)),
            pl.BlockSpec((None, 1, bn), lambda i, h, j, kb: (h, 0, j)),
            pl.BlockSpec((None, 1, bn), lambda i, h, j, kb: (h, 0, j)),
            pl.BlockSpec((1, 2), lambda i, h, j, kb: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda i, h, j, kb: (i, h * (nh // bn) + j)),
        out_shape=jax.ShapeDtypeStruct((mm, 2 * nh), x.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, packed, s_op, z_op, amz)
    out = out[:m]
    if pn:
        # Column pad puts the hi half at offset nh, not n_half: re-join.
        out = jnp.concatenate([out[:, :n_half], out[:, nh:nh + n_half]],
                              axis=1)
    else:
        out = out[:, :n]
    return out


def pick_tiles(m: int, k: int, n: int, *, bm: int = 128, bn: int = 128,
               bk: int = 512) -> dict:
    """The (clamped) tile sizes the compiled ``_w4_call`` uses at this shape.

    The bench records these per row so wall-clock numbers stay comparable
    across PRs that change the tiling."""
    return {"bm": min(bm, m), "bn": lane_tile(n // 2, bn, interpret=False),
            "bk": min(bk, k)}


@functools.partial(jax.jit, static_argnames=("exp_bits", "man_bits", "signed",
                                             "bm", "bn", "bk", "interpret"))
def w4_matmul_2d(x: jnp.ndarray, packed: jnp.ndarray, scale: jnp.ndarray,
                 zero_point: jnp.ndarray | float = 0.0,
                 *, exp_bits: int, man_bits: int, signed: bool = True,
                 bm: int = 128, bn: int = 128, bk: int = 512,
                 interpret: bool = False) -> jnp.ndarray:
    """x: (M, K) bf16/f32; packed: (K, N/2) uint8 -> (M, N) x.dtype.

    ``scale`` (grid maxval) and ``zero_point`` are scalars or (N,) vectors
    (per-output-channel). ``zero_point`` is only meaningful for unsigned
    formats (``signed=False``).
    """
    fmt = FPFormat(exp_bits, man_bits, signed)
    return _w4_call(x, packed, scale, zero_point, (0.0, 0.0), fmt=fmt,
                    act_fmt=None, act_signed=True, bm=bm, bn=bn, bk=bk,
                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "exp_bits", "man_bits", "signed", "act_exp_bits", "act_man_bits",
    "act_signed", "bm", "bn", "bk", "interpret"))
def w4a4_matmul_2d(x: jnp.ndarray, packed: jnp.ndarray, scale: jnp.ndarray,
                   zero_point: jnp.ndarray | float,
                   act_maxval: jnp.ndarray, act_zero_point: jnp.ndarray,
                   *, exp_bits: int, man_bits: int, signed: bool,
                   act_exp_bits: int, act_man_bits: int, act_signed: bool,
                   bm: int = 128, bn: int = 128, bk: int = 512,
                   interpret: bool = False) -> jnp.ndarray:
    """Fused act-quant + W4 matmul: qdq(x) @ dequant(packed) in one pass.

    Equivalent to ``msfp_qdq(x, act_qp)`` followed by ``w4_matmul_2d`` but
    without writing/re-reading the quantized activations through HBM.
    ``act_maxval`` / ``act_zero_point`` are the searched per-tensor MSFP
    activation parameters.
    """
    fmt = FPFormat(exp_bits, man_bits, signed)
    act_fmt = FPFormat(act_exp_bits, act_man_bits, act_signed)
    return _w4_call(x, packed, scale, zero_point,
                    (act_maxval, act_zero_point), fmt=fmt, act_fmt=act_fmt,
                    act_signed=act_signed, bm=bm, bn=bn, bk=bk,
                    interpret=interpret)
