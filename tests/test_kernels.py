"""Per-kernel allclose sweeps (interpret mode) vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.ops as ops
from repro.core.qmodule import pack_weight
from repro.kernels import ref
from repro.quant.fakequant import (KIND_FP_SIGNED, KIND_FP_UNSIGNED,
                                   QuantizerParams)


@pytest.fixture(autouse=True)
def force_interpret():
    old = ops.FORCE
    ops.FORCE = "interpret"
    yield
    ops.FORCE = old


QDQ_CASES = [(KIND_FP_SIGNED, 2, 1), (KIND_FP_SIGNED, 1, 2),
             (KIND_FP_SIGNED, 3, 0), (KIND_FP_SIGNED, 0, 3),
             (KIND_FP_UNSIGNED, 2, 2), (KIND_FP_UNSIGNED, 3, 1),
             (KIND_FP_UNSIGNED, 1, 3)]
SHAPES = [(8, 32), (100, 300), (1, 128), (257, 511), (4, 7, 64)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("kind,e,m", QDQ_CASES)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_msfp_qdq_kernel_matches_ref(kind, e, m, shape, rng):
    qp = QuantizerParams(kind, e, m, 4, jnp.float32(2.3),
                         jnp.float32(-0.15 if kind == KIND_FP_UNSIGNED else 0.0))
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    out = ops.msfp_quantize(x, qp)
    want = ref.ref_msfp_qdq(x, qp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_msfp_qdq_kernel_dtypes(dtype, rng):
    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(1.7))
    x = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32)).astype(dtype)
    out = ops.msfp_quantize(x, qp)
    want = ref.ref_msfp_qdq(x, qp)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=1e-2)


@pytest.mark.parametrize("m,k,n", [(7, 96, 64), (128, 256, 128), (1, 64, 32),
                                   (33, 130, 66)])
@pytest.mark.parametrize("fmt", [(2, 1), (1, 2), (3, 0)], ids=str)
def test_w4_matmul_kernel_matches_ref(m, k, n, fmt, rng):
    e, mm = fmt
    qp = QuantizerParams(KIND_FP_SIGNED, e, mm, 4, jnp.float32(2.5))
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    pw = pack_weight(w, qp)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)).astype(jnp.bfloat16)
    out = ops.w4_matmul(x, pw)
    want = ref.ref_w4_matmul(x, pw, jnp.bfloat16)
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-1, rtol=2e-2)


# ---------------------------------------------------------------------------
# full-format-space W4 paths: per-channel scale, unsigned+zp, fused W4A4
# ---------------------------------------------------------------------------


def _pack_per_channel(w, e, m, rng):
    mv = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-6).astype(jnp.float32)
    qp = QuantizerParams(KIND_FP_SIGNED, e, m, 4, mv)
    return pack_weight(w, qp)


def _pack_unsigned(w, e, m, zp=-0.15):
    mv = jnp.float32(float(jnp.max(w - zp)))
    qp = QuantizerParams(KIND_FP_UNSIGNED, e, m, 4, mv, jnp.float32(zp))
    return pack_weight(w, qp)


@pytest.mark.parametrize("m,k,n", [(7, 96, 64), (33, 130, 66), (257, 511, 64),
                                   (33, 257, 514)])
@pytest.mark.parametrize("fmt", [(2, 1), (1, 2)], ids=str)
def test_w4_matmul_per_channel_matches_ref(m, k, n, fmt, rng):
    e, mm = fmt
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    pw = _pack_per_channel(w, e, mm, rng)
    assert pw.scale.shape == (n,)
    # small-magnitude x keeps f32 dot-reassociation noise under the atol
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)) * 0.02
    out = ops.w4_matmul(x, pw)
    want = ref.ref_w4_matmul(x, pw, jnp.float32)
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-6, rtol=5e-4)


@pytest.mark.parametrize("m,k,n", [(7, 96, 64), (33, 130, 66), (257, 511, 64)])
@pytest.mark.parametrize("fmt", [(2, 2), (1, 3), (0, 4)], ids=str)
def test_w4_matmul_unsigned_zp_matches_ref(m, k, n, fmt, rng):
    e, mm = fmt
    # SiLU-like AAL weights: mostly positive with a shallow negative tail.
    w = jnp.asarray(np.abs(rng.normal(size=(k, n))).astype(np.float32) - 0.15)
    pw = _pack_unsigned(w, e, mm)
    assert not pw.signed
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)) * 0.02
    out = ops.w4_matmul(x, pw)
    want = ref.ref_w4_matmul(x, pw, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-6, rtol=5e-4)


@pytest.mark.parametrize("m,k,n", [(7, 96, 64), (33, 130, 66), (257, 511, 64)])
@pytest.mark.parametrize("act_kind,act_e,act_m",
                         [(KIND_FP_SIGNED, 2, 1), (KIND_FP_UNSIGNED, 2, 2)])
def test_w4a4_fused_matches_qdq_then_matmul(m, k, n, act_kind, act_e, act_m,
                                            rng):
    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.5))
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    pw = pack_weight(w, qp)
    act_qp = QuantizerParams(
        act_kind, act_e, act_m, 4, jnp.float32(2.3),
        jnp.float32(-0.15 if act_kind == KIND_FP_UNSIGNED else 0.0))
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)) * 0.02
    out = ops.w4a4_matmul(x, pw, act_qp)
    want = ref.ref_w4a4_matmul(x, pw, act_qp, jnp.float32)
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-6, rtol=5e-4)


def test_w4a4_fused_unsigned_act_with_padded_k(rng):
    """K > bk-multiple forces zero-padding of x; unsigned act quant maps
    those zeros to qdq(0) != 0, which must not leak into the dot or the
    weight zero-point rowsum correction (regression)."""
    m, k, n = 5, 600, 32  # bk=512 -> padded to 1024: 424 phantom K rows
    wu = jnp.abs(jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))) - 0.15
    pw = _pack_unsigned(wu, 2, 2)
    act_qp = QuantizerParams(KIND_FP_UNSIGNED, 2, 2, 4, jnp.float32(2.3),
                             jnp.float32(-0.15))
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)) * 0.02
    out = ops.w4a4_matmul(x, pw, act_qp)
    want = ref.ref_w4a4_matmul(x, pw, act_qp, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=5e-4)


def test_w4a4_fused_per_channel_unsigned_weight_bf16(rng):
    """The full stack at once: unsigned per-channel weights, fused act
    quant, bf16 activations, odd/padded shapes."""
    k, n = 130, 66
    # O(1)-scaled data keeps outputs within bf16 ulp ~4e-3 of the oracle.
    w = jnp.asarray(np.abs(rng.normal(size=(k, n))).astype(np.float32)
                    * 0.1 - 0.01)
    mv = jnp.maximum(jnp.max(w + 0.01, axis=0), 1e-6).astype(jnp.float32)
    qp = QuantizerParams(KIND_FP_UNSIGNED, 2, 2, 4, mv,
                         jnp.broadcast_to(jnp.float32(-0.01), mv.shape))
    pw = pack_weight(w, qp)
    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(1.0))
    x = jnp.asarray(rng.normal(size=(29, k)).astype(np.float32) * 0.3
                    ).astype(jnp.bfloat16)
    out = ops.w4a4_matmul(x, pw, act_qp)
    want = ref.ref_w4a4_matmul(x, pw, act_qp, jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-2, rtol=2e-2)


@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_w4_matmul_per_channel_dtypes(dtype, rng):
    w = jnp.asarray(rng.normal(size=(96, 64)).astype(np.float32)) * 0.1
    pw = _pack_per_channel(w, 2, 1, rng)
    x = jnp.asarray(rng.normal(size=(17, 96)).astype(np.float32)
                    * 0.3).astype(dtype)
    out = ops.w4_matmul(x, pw)
    want = ref.ref_w4_matmul(x, pw, dtype)
    assert out.dtype == dtype
    atol = 1e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=atol, rtol=2e-2)


def test_w4_dispatch_covers_full_format_space(monkeypatch, rng):
    """Vector-scale and unsigned PackedW4 must hit the Pallas kernel, not
    the XLA decode-then-dot fallback."""

    def boom(*a, **k):
        raise AssertionError("w4_matmul fell back to the XLA path")

    monkeypatch.setattr(ops._ref, "ref_w4_matmul", boom)
    monkeypatch.setattr(ops._ref, "ref_w4a4_matmul", boom)
    x = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))

    pc = _pack_per_channel(w, 2, 1, rng)
    assert ops.w4_matmul(x, pc).shape == (4, 16)

    un = _pack_unsigned(jnp.abs(w) - 0.1, 2, 2)
    assert ops.w4_matmul(x, un).shape == (4, 16)

    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0))
    assert ops.w4a4_matmul(x, pc, act_qp).shape == (4, 16)

    # stacked packs (scanned layers) are the documented remaining fallback
    monkeypatch.undo()
    from repro.core.qmodule import PackedW4
    stacked = PackedW4(jnp.zeros((2, 32, 8), jnp.uint8),
                       jnp.ones((2, 1, 1)), jnp.zeros((2, 1, 1)),
                       2, 1, True, (2, 32, 16))
    assert not ops._pallas_w4_ok(stacked)


def test_dense_apply_serve_ctx_routes_to_fused_kernel(monkeypatch, rng):
    """A serve-mode QuantContext must hand packed dense layers their
    activation params so they take the fused W4A4 path."""
    from repro.nn.layers import dense_apply
    from repro.quant.calibrate import QuantContext

    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0))
    w = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    pw = pack_weight(w, QuantizerParams(KIND_FP_SIGNED, 2, 1, 4,
                                        jnp.float32(2.5)))
    x = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    seen = {}
    real = ops.w4a4_matmul

    def spy(x_, pw_, act_qp_):
        seen["act_qp"] = act_qp_
        return real(x_, pw_, act_qp_)

    monkeypatch.setattr(ops, "w4a4_matmul", spy)
    ctx = QuantContext("serve", act_qps={"*": qp})
    out = dense_apply({"w": pw}, x, ctx=ctx, site="mlp/down")
    assert out.shape == (4, 16)
    assert seen["act_qp"] is qp
    # off-mode ctx leaves act_qp unset -> plain w4 path
    seen.clear()
    dense_apply({"w": pw}, x, ctx=QuantContext("off"), site="mlp/down")
    assert seen["act_qp"] is None


def test_mlp_apply_act_qps_threading(monkeypatch, rng):
    """Explicit act_qps mapping (site-keyed with '*' fallback) reaches the
    fused kernel through mlp_apply's dense call sites."""
    from repro.nn.mlp import mlp_apply

    d, f = 16, 32
    qp_down = QuantizerParams(KIND_FP_UNSIGNED, 2, 2, 4, jnp.float32(2.0),
                              jnp.float32(-0.15))
    qp_any = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(3.0))
    wqp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0))
    p = {name: {"w": pack_weight(
            jnp.asarray(rng.normal(size=shape).astype(np.float32)), wqp)}
         for name, shape in (("gate", (d, f)), ("up", (d, f)),
                             ("down", (f, d)))}
    calls = []
    real = ops.w4a4_matmul

    def spy(x_, pw_, act_qp_):
        calls.append(act_qp_)
        return real(x_, pw_, act_qp_)

    monkeypatch.setattr(ops, "w4a4_matmul", spy)
    x = jnp.asarray(rng.normal(size=(3, d)).astype(np.float32))
    out = mlp_apply(p, x, "swiglu", site="mlp",
                    act_qps={"mlp/down": qp_down, "*": qp_any})
    assert out.shape == (3, d)
    assert calls == [qp_any, qp_any, qp_down]  # gate, up, down


# ---------------------------------------------------------------------------
# im2col conv route: packed HWIO convs through the fused W4A4 matmul
# ---------------------------------------------------------------------------


def _pack_conv(w4d, e=2, m=1):
    mv = jnp.maximum(jnp.max(jnp.abs(w4d)).astype(jnp.float32), 1e-6)
    return pack_weight(w4d, QuantizerParams(KIND_FP_SIGNED, e, m, 4, mv))


@pytest.mark.parametrize("kernel,stride,padding",
                         [(3, 1, "SAME"), (3, 2, "SAME"), (1, 1, "SAME"),
                          (1, 2, "SAME"), (3, 1, "VALID"), (3, 2, "VALID")])
def test_w4a4_conv2d_matches_ref_and_xla_conv(kernel, stride, padding, rng):
    """Interpret-mode conv route vs the jnp oracle AND vs lax.conv on the
    dequantized (reshaped-back-to-HWIO) weights."""
    from jax import lax

    from repro.core.qmodule import dequant_weight
    from repro.quant.fakequant import apply_qdq

    cin, cout = 6, 10
    w = jnp.asarray(rng.normal(size=(kernel, kernel, cin, cout))
                    .astype(np.float32))
    pw = _pack_conv(w)
    # conv weights pack as their 2D GEMM flattening, original shape kept
    assert pw.packed.shape == (kernel * kernel * cin, cout // 2)
    assert pw.shape == w.shape
    x = jnp.asarray(rng.normal(size=(2, 9, 9, cin)).astype(np.float32)) * 0.3
    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(1.0))
    out = ops.w4a4_conv2d(x, pw, act_qp, stride=stride, padding=padding)
    want = ref.ref_w4a4_conv2d(x, pw, act_qp, stride=(stride, stride),
                               padding=padding, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=5e-4)
    want_xla = lax.conv_general_dilated(
        apply_qdq(x, act_qp), dequant_weight(pw, jnp.float32),
        (stride, stride), padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_xla),
                               atol=2e-5, rtol=5e-4)


def test_w4a4_conv2d_unsigned_act_same_padding(rng):
    """Unsigned act grids map 0 to the zero-point, so the dispatcher must
    pre-quantize x (quantize-then-pad order) rather than snap the zero-
    padded patch entries in-kernel — SAME padding is the regression."""
    w = jnp.asarray(np.abs(rng.normal(size=(3, 3, 6, 8))).astype(np.float32))
    pw = _pack_conv(w)
    x = jnp.asarray(rng.normal(size=(1, 7, 7, 6)).astype(np.float32)) * 0.3
    act_qp = QuantizerParams(KIND_FP_UNSIGNED, 2, 2, 4, jnp.float32(1.5),
                             jnp.float32(-0.15))
    out = ops.w4a4_conv2d(x, pw, act_qp, stride=1, padding="SAME")
    want = ref.ref_w4a4_conv2d(x, pw, act_qp, stride=(1, 1), padding="SAME",
                               dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=5e-4)


def test_w4a4_conv2d_vector_act_maxval_falls_back(rng):
    """A per-channel (vector-maxval) act quantizer can't ride the per-
    tensor Pallas snap; the pre-quantize pass must degrade to the XLA
    ref instead of crashing (regression: msfp_quantize Pallas gating)."""
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32))
    pw = _pack_conv(w)
    x = jnp.asarray(rng.normal(size=(1, 5, 5, 4)).astype(np.float32)) * 0.3
    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4,
                             jnp.full((4,), 1.0, jnp.float32))
    out = ops.w4a4_conv2d(x, pw, act_qp, stride=1, padding="SAME")
    want = ref.ref_w4a4_conv2d(x, pw, act_qp, stride=(1, 1), padding="SAME",
                               dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=5e-4)


def test_w4a4_conv2d_per_channel_scale_and_bf16(rng):
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 6)).astype(np.float32)) * 0.1
    mv = jnp.maximum(jnp.max(jnp.abs(w), axis=(0, 1, 2)), 1e-6)
    pw = pack_weight(w, QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, mv))
    assert pw.scale.shape == (6,)
    x = jnp.asarray(rng.normal(size=(2, 5, 5, 4)).astype(np.float32)
                    * 0.3).astype(jnp.bfloat16)
    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(1.0))
    out = ops.w4a4_conv2d(x, pw, act_qp, stride=1, padding="SAME")
    want = ref.ref_w4a4_conv2d(x, pw, act_qp, stride=(1, 1), padding="SAME",
                               dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-2, rtol=2e-2)


def test_w4a4_conv2d_dispatch_never_decodes(monkeypatch, rng):
    """Packed conv weights (scalar or per-channel scale, signed act fused
    or None) must hit the Pallas im2col route, not the decode-then-conv
    oracle fallback."""

    def boom(*a, **k):
        raise AssertionError("w4a4_conv2d fell back to decode-then-conv")

    monkeypatch.setattr(ops._ref, "ref_w4a4_conv2d", boom)
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(1, 6, 6, 4)).astype(np.float32))
    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0))
    pw = _pack_conv(w)
    assert ops.w4a4_conv2d(x, pw, act_qp).shape == (1, 6, 6, 8)
    assert ops.w4a4_conv2d(x, pw, None, stride=2).shape == (1, 3, 3, 8)
    mv = jnp.maximum(jnp.max(jnp.abs(w), axis=(0, 1, 2)), 1e-6)
    pc = pack_weight(w, QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, mv))
    assert ops.w4a4_conv2d(x, pc, act_qp).shape == (1, 6, 6, 8)


def test_conv2d_apply_serve_ctx_routes_to_conv_kernel(monkeypatch, rng):
    """A serve-mode QuantContext hands packed conv layers their act params
    and routes through ops.w4a4_conv2d — never dequant + XLA conv."""
    from repro.nn.layers import conv2d_apply
    from repro.quant.calibrate import QuantContext

    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0))
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32))
    pw = _pack_conv(w)
    x = jnp.asarray(rng.normal(size=(2, 6, 6, 4)).astype(np.float32))
    seen = {}
    real = ops.w4a4_conv2d

    def spy(x_, pw_, act_qp_, **kw):
        seen["act_qp"] = act_qp_
        return real(x_, pw_, act_qp_, **kw)

    monkeypatch.setattr(ops, "w4a4_conv2d", spy)
    ctx = QuantContext("serve", act_qps={"*": qp})
    out = conv2d_apply({"w": pw}, x, ctx=ctx, site="res/conv1")
    assert out.shape == (2, 6, 6, 8)
    assert seen["act_qp"] is qp
    seen.clear()
    conv2d_apply({"w": pw}, x, ctx=QuantContext("off"), site="res/conv1")
    assert seen["act_qp"] is None


def test_unpacked_sites_quantize_acts_in_serve_mode(monkeypatch, rng):
    """bf16-fallback dense/conv sites must still quantize their input in
    serve mode (standalone msfp pass) so serving matches the fake-quant
    oracle at every planned act site (regression: they skipped it)."""
    from repro.nn.layers import conv2d_apply, dense_apply
    from repro.quant.calibrate import QuantContext
    from repro.quant.fakequant import apply_qdq

    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0))
    calls = []
    real = ops.msfp_quantize

    def spy(x_, qp_):
        calls.append(qp_)
        return real(x_, qp_)

    monkeypatch.setattr(ops, "msfp_quantize", spy)
    ctx = QuantContext("serve", act_qps={"*": qp})
    xd = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    wd = jnp.asarray(rng.normal(size=(8, 6)).astype(np.float32))
    out = dense_apply({"w": wd}, xd, ctx=ctx, site="io/head")
    assert calls == [qp]
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(apply_qdq(xd, qp) @ wd),
                               atol=1e-6)
    calls.clear()
    xc = jnp.asarray(rng.normal(size=(1, 5, 5, 3)).astype(np.float32))
    wc = jnp.asarray(rng.normal(size=(3, 3, 3, 7)).astype(np.float32))
    conv2d_apply({"w": wc}, xc, ctx=ctx, site="conv_in")  # odd cout: dense
    assert calls == [qp]
    # no ctx / off mode: the plain unquantized path is untouched
    calls.clear()
    dense_apply({"w": wd}, xd)
    conv2d_apply({"w": wc}, xc, ctx=QuantContext("off"), site="conv_in")
    assert calls == []


def test_w4_matmul_3d_input(rng):
    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(1.0))
    w = jnp.asarray(rng.normal(size=(32, 48)).astype(np.float32))
    pw = pack_weight(w, qp)
    x = jnp.asarray(rng.normal(size=(2, 5, 32)).astype(np.float32))
    out = ops.w4_matmul(x, pw)
    assert out.shape == (2, 5, 48)


@pytest.mark.parametrize("shape", [(16, 64), (3, 5, 8, 128), (1, 1, 2, 64)],
                         ids=str)
def test_kv4_roundtrip_and_ref_match(shape, rng):
    t = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    packed, scale = ops.kv4_encode(t)
    back = ops.kv4_decode(packed, scale, jnp.float32)
    pr, sr = ref.ref_kv4_encode(t.reshape(-1, shape[-1]))
    assert bool(jnp.all(packed.reshape(-1, shape[-1] // 2) == pr))
    np.testing.assert_allclose(
        np.asarray(back),
        np.asarray(ref.ref_kv4_decode(pr, sr, jnp.float32)).reshape(shape),
        atol=1e-6)
    # E2M1 with per-head scale: bounded relative error
    rel = float(jnp.max(jnp.abs(back - t)) / jnp.max(jnp.abs(t)))
    assert rel < 0.25


def test_kv4_zero_row():
    t = jnp.zeros((4, 64))
    packed, scale = ops.kv4_encode(t)
    back = ops.kv4_decode(packed, scale, jnp.float32)
    np.testing.assert_allclose(np.asarray(back), 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# Implicit-GEMM conv kernel (interpret-mode parity for the new index maps)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel,stride,padding",
                         [(3, (1, 1), "SAME"), (3, (2, 2), "SAME"),
                          (1, (1, 1), "SAME"), (5, (2, 1), "VALID"),
                          (3, (1, 1), ((2, 1), (0, 3)))])
@pytest.mark.parametrize("act", ["none", "signed", "unsigned"])
def test_implicit_conv_kernel_parity(kernel, stride, padding, act, rng):
    """The implicit-GEMM kernel's index maps (whole-slab gather, tap
    unroll, pad re-masking) vs the jnp oracle on odd shapes, strides,
    SAME/VALID and explicit pad pairs."""
    from repro.kernels.conv import w4a4_conv2d_implicit

    cin, cout = 6, 10
    w = jnp.asarray(rng.normal(size=(kernel, kernel, cin, cout))
                    .astype(np.float32)) * 0.3
    pw = _pack_conv(w)
    x = jnp.asarray(rng.normal(size=(2, 9, 7, cin)).astype(np.float32)) * 0.4
    act_qp = {"none": None,
              "signed": QuantizerParams(KIND_FP_SIGNED, 2, 1, 4,
                                        jnp.float32(1.2)),
              "unsigned": QuantizerParams(KIND_FP_UNSIGNED, 2, 2, 4,
                                          jnp.float32(1.5),
                                          jnp.float32(-0.15))}[act]
    out = w4a4_conv2d_implicit(x, pw, act_qp, stride=stride, padding=padding,
                               interpret=True)
    want = ref.ref_w4a4_conv2d(x, pw, act_qp, stride=stride, padding=padding,
                               dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=5e-4)


def test_implicit_conv_kernel_per_channel_bf16(rng):
    from repro.kernels.conv import w4a4_conv2d_implicit

    w = jnp.asarray(rng.normal(size=(3, 3, 4, 6)).astype(np.float32)) * 0.1
    mv = jnp.maximum(jnp.max(jnp.abs(w), axis=(0, 1, 2)), 1e-6)
    pw = pack_weight(w, QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, mv))
    x = jnp.asarray(rng.normal(size=(2, 5, 5, 4)).astype(np.float32)
                    * 0.3).astype(jnp.bfloat16)
    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(1.0))
    out = w4a4_conv2d_implicit(x, pw, act_qp, stride=(1, 1), padding="SAME",
                               interpret=True)
    want = ref.ref_w4a4_conv2d(x, pw, act_qp, stride=(1, 1), padding="SAME",
                               dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-2, rtol=2e-2)


def test_conv_route_forced_implicit_is_used(monkeypatch, rng):
    """CONV_ROUTE="implicit" must run the implicit kernel (and never the
    im2col route or the decode oracle), even in interpret mode."""
    import repro.kernels.conv as conv_mod

    monkeypatch.setattr(ops, "CONV_ROUTE", "implicit")
    monkeypatch.setattr(ops._ref, "ref_w4a4_conv2d",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("decode fallback")))
    monkeypatch.setattr(conv_mod, "w4a4_conv2d_im2col",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("im2col route")))
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(1, 6, 6, 4)).astype(np.float32))
    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0))
    out = ops.w4a4_conv2d(x, _pack_conv(w), act_qp)
    assert out.shape == (1, 6, 6, 8)


def test_conv_route_interpret_default_stays_im2col(monkeypatch, rng):
    """Unforced interpret-mode dispatch keeps the im2col route — the
    golden replay trace's digest is pinned to its accumulation order."""
    import repro.kernels.conv as conv_mod

    called = {}
    real = conv_mod.w4a4_conv2d_im2col

    def spy(*a, **k):
        called["im2col"] = True
        return real(*a, **k)

    monkeypatch.setattr(conv_mod, "w4a4_conv2d_im2col", spy)
    monkeypatch.setattr(conv_mod, "w4a4_conv2d_implicit",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("implicit under interpret auto")))
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(1, 6, 6, 4)).astype(np.float32))
    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0))
    assert ops.CONV_ROUTE == "auto"
    ops.w4a4_conv2d(x, _pack_conv(w), act_qp)
    assert called.get("im2col")


# ---------------------------------------------------------------------------
# Fused matmul: ragged K with unsigned formats; snap-once re-tiling
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wfmt", [(2, 2), (3, 1)], ids=str)
@pytest.mark.parametrize("k", [600, 96])
def test_w4_matmul_ragged_k_unsigned_weight(wfmt, k, rng):
    """K % bk != 0 (600 vs the 512 K-tile) with unsigned weight formats:
    the zero-point K-padding correction must count only valid rows."""
    from repro.kernels.w4_matmul import w4_matmul_2d

    e, mm = wfmt
    w = jnp.asarray(np.abs(rng.normal(size=(k, 66))).astype(np.float32))
    qp = QuantizerParams(KIND_FP_UNSIGNED, e, mm, 4, jnp.float32(2.2),
                         jnp.float32(0.4))
    pw = pack_weight(w, qp)
    x = jnp.asarray(rng.normal(size=(33, k)).astype(np.float32))
    out = w4_matmul_2d(x, pw.packed, pw.scale, pw.zero_point,
                       exp_bits=e, man_bits=mm, signed=False, interpret=True)
    want = ref.ref_w4_matmul(x, pw, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=5e-4)


@pytest.mark.parametrize("act_kind", [KIND_FP_SIGNED, KIND_FP_UNSIGNED])
def test_w4a4_fused_ragged_k_unsigned_act(act_kind, rng):
    from repro.kernels.w4_matmul import w4a4_matmul_2d

    k = 600
    w = jnp.asarray(rng.normal(size=(k, 66)).astype(np.float32))
    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.5))
    pw = pack_weight(w, qp)
    act_qp = QuantizerParams(act_kind, 2, 1, 4, jnp.float32(3.0),
                             jnp.float32(-0.2))
    x = jnp.asarray(rng.normal(size=(17, k)).astype(np.float32))
    out = w4a4_matmul_2d(
        x, pw.packed, pw.scale, pw.zero_point, act_qp.maxval,
        act_qp.zero_point, exp_bits=2, man_bits=1, signed=True,
        act_exp_bits=2, act_man_bits=1,
        act_signed=(act_kind == KIND_FP_SIGNED), interpret=True)
    want = ref.ref_w4a4_matmul(x, pw, act_qp, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=5e-4)


def test_snap_once_retiling_matches_per_program_snap(monkeypatch, rng):
    """The persistent-VMEM snap-once path (one snap per (i, k) tile) must
    be bit-identical to snapping in every (h, j) program — same tiles,
    same accumulation order."""
    import repro.kernels.w4_matmul as wm

    k = 600
    w = jnp.asarray(rng.normal(size=(k, 66)).astype(np.float32))
    pw = pack_weight(w, QuantizerParams(KIND_FP_SIGNED, 2, 1, 4,
                                        jnp.float32(2.5)))
    act_qp = QuantizerParams(KIND_FP_UNSIGNED, 2, 1, 4, jnp.float32(3.0),
                             jnp.float32(-0.2))
    x = jnp.asarray(rng.normal(size=(17, k)).astype(np.float32))

    def run():
        return wm.w4a4_matmul_2d(
            x, pw.packed, pw.scale, pw.zero_point, act_qp.maxval,
            act_qp.zero_point, exp_bits=2, man_bits=1, signed=True,
            act_exp_bits=2, act_man_bits=1, act_signed=False, interpret=True)

    snap_once = run()
    monkeypatch.setattr(wm, "XQ_VMEM_BUDGET", 0)   # disable the scratch
    per_program = run()
    assert jnp.array_equal(snap_once, per_program)


# ---------------------------------------------------------------------------
# Fast XLA serving path (kernels.xla_serve)
# ---------------------------------------------------------------------------


XS_FMTS = [(KIND_FP_SIGNED, 2, 1), (KIND_FP_SIGNED, 3, 0),
           (KIND_FP_SIGNED, 1, 2), (KIND_FP_SIGNED, 0, 3),
           (KIND_FP_UNSIGNED, 2, 2), (KIND_FP_UNSIGNED, 3, 1)]


@pytest.mark.parametrize("kind,e,m", XS_FMTS)
def test_fast_qdq_equals_oracle(kind, e, m, rng):
    """Bitcast-octave snap == transcendental oracle, including octave
    boundaries and zeros/huge/tiny, f32 and bf16, scalar + per-channel."""
    from repro.kernels import xla_serve

    qp = QuantizerParams(kind, e, m, 4, jnp.float32(2.3), jnp.float32(-0.15))
    x = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32)) * 3
    adv = jnp.asarray(np.array(
        [0.0, -0.0, 1.0, np.nextafter(2.0, 0), np.nextafter(2.0, 3),
         -6.0, 6.0, 1e-30, -1e-30, 3.3e38, 0.49999997, 0.5] * 4,
        np.float32)).reshape(4, 12)
    for inp in (x, adv, x.astype(jnp.bfloat16)):
        want = ref.ref_msfp_qdq(inp, qp)
        got = xla_serve.fast_qdq(inp, qp)
        assert got.dtype == inp.dtype
        assert jnp.array_equal(want, got), (kind, e, m, inp.dtype)
    mv = jnp.abs(jnp.asarray(rng.normal(size=(128,)).astype(np.float32))) + .5
    qpc = QuantizerParams(kind, e, m, 4, mv, jnp.float32(0.1))
    assert jnp.array_equal(ref.ref_msfp_qdq(x, qpc),
                           xla_serve.fast_qdq(x, qpc))


def test_fast_qdq_high_exp_formats_fall_back_to_ref(monkeypatch, rng):
    """E4+ octaves hit XLA CPU's inexact exp2 in the *reference*; the
    fast path must route them to the reference, not disagree with it."""
    from repro.kernels import xla_serve

    called = {}
    real = ref.ref_msfp_qdq

    def spy(*a, **k):
        called["ref"] = True
        return real(*a, **k)

    monkeypatch.setattr(xla_serve._ref, "ref_msfp_qdq", spy)
    qp = QuantizerParams(KIND_FP_SIGNED, 4, 0, 5, jnp.float32(2.0e4))
    x = jnp.asarray(rng.normal(size=(32, 32)).astype(np.float32)) * 1e4
    assert jnp.array_equal(xla_serve.fast_qdq(x, qp), real(x, qp))
    assert called.get("ref")


def test_fast_decode_equals_decode_codes():
    from repro.core.qmodule import decode_codes
    from repro.kernels import xla_serve
    from repro.quant.formats import FPFormat

    for e, m, signed in [(2, 1, True), (3, 0, True), (1, 2, True),
                         (0, 3, True), (2, 1, False), (3, 0, False),
                         (2, 2, False), (0, 4, False)]:
        fmt = FPFormat(e, m, signed)
        codes = jnp.arange(2 ** min(e + m + signed, 4), dtype=jnp.uint8)
        for sc in (0.7, 2.0, 1e-3, 137.0):
            want = decode_codes(codes, fmt, jnp.float32(sc), 0.3, jnp.float32)
            got = xla_serve.fast_decode(codes, fmt, jnp.float32(sc), 0.3,
                                        jnp.float32)
            assert jnp.array_equal(want, got), (e, m, signed, sc)


def test_serve_dequant_matches_dequant_weight(rng):
    from repro.core.qmodule import dequant_weight
    from repro.kernels import xla_serve

    w = jnp.asarray(rng.normal(size=(3, 3, 6, 10)).astype(np.float32)) * 0.3
    mv = jnp.maximum(jnp.max(jnp.abs(w), axis=(0, 1, 2)), 1e-6)
    for qp in (QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(0.9)),
               QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, mv),
               QuantizerParams(KIND_FP_UNSIGNED, 2, 2, 4, jnp.float32(0.9),
                               jnp.float32(-0.4))):
        pw = pack_weight(w, qp)
        assert jnp.array_equal(dequant_weight(pw, jnp.float32),
                               xla_serve.serve_dequant(pw, jnp.float32))


def test_xla_serve_matmuls_bit_identical_for_f32(rng):
    """f32 in, f32 out: same snap, same decode, same per-column
    accumulation order as the oracles — equality, not allclose."""
    from repro.kernels import xla_serve

    k, n = 384, 66
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(4.0))
    for qp in (QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0)),
               QuantizerParams(KIND_FP_UNSIGNED, 2, 2, 4, jnp.float32(2.0),
                               jnp.float32(-1.0))):
        pw = pack_weight(w, qp)
        x = jnp.asarray(rng.normal(size=(32, k)).astype(np.float32))
        assert jnp.array_equal(xla_serve.w4_matmul(x, pw, jnp.float32),
                               ref.ref_w4_matmul(x, pw, jnp.float32))
        assert jnp.array_equal(
            xla_serve.fused_matmul(x, pw, act_qp, jnp.float32),
            ref.ref_w4a4_matmul(x, pw, act_qp, jnp.float32))


def test_xla_serve_fused_bf16_close_to_oracle(rng):
    """bf16 in: the snapped activation stays f32 through the dot (the
    oracle re-rounds to bf16) — within one bf16 ulp relative."""
    from repro.kernels import xla_serve

    k, n = 384, 66
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    pw = pack_weight(w, QuantizerParams(KIND_FP_SIGNED, 2, 1, 4,
                                        jnp.float32(2.0)))
    act_qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(4.0))
    x = jnp.asarray(rng.normal(size=(32, k)).astype(np.float32)) \
        .astype(jnp.bfloat16)
    got = xla_serve.fused_matmul(x, pw, act_qp, jnp.bfloat16)
    want = ref.ref_w4a4_matmul(x, pw, act_qp, jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-2, rtol=2e-2)


@pytest.mark.parametrize("stride,padding",
                         [((1, 1), "SAME"), ((2, 2), "SAME"),
                          ((2, 1), "VALID"), ((1, 1), ((2, 1), (0, 3)))])
@pytest.mark.parametrize("act", ["none", "signed", "unsigned"])
def test_xla_serve_implicit_conv_parity(stride, padding, act, rng):
    from repro.kernels import xla_serve

    w = jnp.asarray(rng.normal(size=(3, 3, 6, 10)).astype(np.float32)) * 0.3
    pw = _pack_conv(w)
    x = jnp.asarray(rng.normal(size=(2, 9, 7, 6)).astype(np.float32)) * 0.4
    act_qp = {"none": None,
              "signed": QuantizerParams(KIND_FP_SIGNED, 2, 1, 4,
                                        jnp.float32(1.2)),
              "unsigned": QuantizerParams(KIND_FP_UNSIGNED, 2, 2, 4,
                                          jnp.float32(1.5),
                                          jnp.float32(-0.15))}[act]
    out = xla_serve.implicit_conv(x, pw, act_qp, stride=stride,
                                  padding=padding, dtype=jnp.float32)
    want = ref.ref_w4a4_conv2d(x, pw, act_qp, stride=stride, padding=padding,
                               dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=5e-4)


def test_force_xla_pins_pure_reference(monkeypatch, rng):
    """FORCE="xla" must never touch the fast serving path — it is the
    oracle escape hatch."""
    import repro.kernels.xla_serve as xla_serve

    ops.FORCE = "xla"
    for name in ("fast_qdq", "fused_matmul", "w4_matmul", "implicit_conv"):
        monkeypatch.setattr(xla_serve, name,
                            lambda *a, _n=name, **k: (_ for _ in ()).throw(
                                AssertionError(f"fast path {_n} under xla")))
    w = jnp.asarray(rng.normal(size=(96, 64)).astype(np.float32))
    pw = pack_weight(w, QuantizerParams(KIND_FP_SIGNED, 2, 1, 4,
                                        jnp.float32(2.0)))
    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0))
    x = jnp.asarray(rng.normal(size=(8, 96)).astype(np.float32))
    assert jnp.array_equal(ops.msfp_quantize(x, qp), ref.ref_msfp_qdq(x, qp))
    assert jnp.array_equal(ops.w4_matmul(x, pw),
                           ref.ref_w4_matmul(x, pw, x.dtype))
    assert jnp.array_equal(ops.w4a4_matmul(x, pw, qp),
                           ref.ref_w4a4_matmul(x, pw, qp, x.dtype))
    wc = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32))
    xc = jnp.asarray(rng.normal(size=(1, 6, 6, 4)).astype(np.float32))
    assert jnp.array_equal(
        ops.w4a4_conv2d(xc, _pack_conv(wc), qp),
        ref.ref_w4a4_conv2d(xc, _pack_conv(wc), qp, dtype=xc.dtype))


@pytest.mark.parametrize("op", ["qdq", "w4", "w4a4", "conv"])
def test_force_pallas_off_tpu_raises(op, rng):
    """Compiled Pallas needs a TPU: off it, FORCE="pallas" is an error,
    never a quiet switch to interpret mode."""
    ops.FORCE = "pallas"
    assert jax.default_backend() != "tpu"
    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0))
    w = jnp.asarray(rng.normal(size=(96, 64)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(8, 96)).astype(np.float32))
    wc = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32))
    xc = jnp.asarray(rng.normal(size=(1, 6, 6, 4)).astype(np.float32))
    call = {"qdq": lambda: ops.msfp_quantize(x, qp),
            "w4": lambda: ops.w4_matmul(x, pack_weight(w, qp)),
            "w4a4": lambda: ops.w4a4_matmul(x, pack_weight(w, qp), qp),
            "conv": lambda: ops.w4a4_conv2d(xc, _pack_conv(wc), qp)}[op]
    with pytest.raises(RuntimeError, match="needs a TPU"):
        call()


@pytest.mark.parametrize("stride,cin,want", [((1, 1), 128, "implicit"),
                                             ((2, 2), 128, "im2col"),
                                             ((1, 1), 4096, "im2col")],
                         ids=["unit_stride", "strided", "over_budget"])
def test_conv_route_auto_compiled(stride, cin, want):
    """Compiled auto routing sends to im2col what the implicit kernel
    cannot compile: strided taps, and slabs over the VMEM budget."""
    from repro.core.qmodule import PackedW4
    from repro.kernels.conv import implicit_supported

    x = jax.ShapeDtypeStruct((4, 32, 32, cin), jnp.float32)
    pw = PackedW4(None, None, None, 2, 1, True, (3, 3, cin, 128))
    assert ops._conv_route(x, pw, stride, ((1, 1), (1, 1)), fused=True,
                           interpret=False) == want
    assert implicit_supported(x.shape, pw.shape, stride, ((1, 1), (1, 1)),
                              fused=True) == (want == "implicit")


@pytest.mark.parametrize("n_half,interpret,want", [
    (64, True, 64), (64, False, 128), (128, False, 128), (384, False, 128),
    (320, True, 128), (32, False, 128)])
def test_lane_tile(n_half, interpret, want):
    """Compiled column tiles are whole 128-lane tiles (Mosaic's block
    rule); interpret mode keeps the half's own narrower width."""
    from repro.kernels.w4_matmul import lane_tile

    assert lane_tile(n_half, interpret=interpret) == want


def _dot_precisions(jaxpr) -> list:
    """(lhs dtype, precision) of every dot_general, nested jaxprs too."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append((eqn.invars[0].aval.dtype, eqn.params["precision"]))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    found += _dot_precisions(sub)
    return found


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("kernel", ["w4a4_matmul", "implicit_conv"])
def test_compiled_kernel_dots_keep_f32(kernel, dtype):
    """On the TPU a dot of f32 operands at the default precision is one
    bf16 MXU pass. The compiled kernels' f32 dots ask for HIGHEST; bf16
    dots keep the default, the one pass Mosaic accepts for them."""
    from repro.core.qmodule import PackedW4
    from repro.kernels.conv import w4a4_conv2d_implicit
    from repro.kernels.w4_matmul import w4a4_matmul_2d

    act = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(6.0))
    if kernel == "w4a4_matmul":
        fn = lambda x, p, s: w4a4_matmul_2d(  # noqa: E731
            x, p, s, 0.0, 6.0, 0.0, exp_bits=2, man_bits=1, signed=True,
            act_exp_bits=2, act_man_bits=1, act_signed=True)
        args = ((8, 64), (64, 64), (128,))
    else:
        fn = lambda x, p, s: w4a4_conv2d_implicit(  # noqa: E731
            x, PackedW4(p, s, jnp.float32(0.0), 2, 1, True, (3, 3, 8, 128)),
            act, stride=(1, 1), padding="SAME")
        args = ((1, 4, 4, 8), (72, 64), (128,))
    jaxpr = jax.make_jaxpr(fn)(jnp.ones(args[0], dtype),
                               jnp.ones(args[1], jnp.uint8),
                               jnp.ones(args[2], jnp.float32))
    dots = _dot_precisions(jaxpr.jaxpr)
    assert dots
    want = (jax.lax.Precision.HIGHEST,) * 2 if dtype == jnp.float32 else None
    for lhs, precision in dots:
        assert lhs == dtype and precision == want, (lhs, precision)


def test_default_cpu_dispatch_routes_to_fast_path(monkeypatch, rng):
    """Unforced off-TPU dispatch serves via xla_serve (matmul, fused,
    conv, qdq) — the reference oracles are for tests, not serving."""
    import repro.kernels.xla_serve as xla_serve

    ops.FORCE = None
    assert jax.default_backend() != "tpu"
    seen = set()
    for name in ("fast_qdq", "fused_matmul", "w4_matmul", "implicit_conv"):
        real = getattr(xla_serve, name)

        def spy(*a, _n=name, _real=real, **k):
            seen.add(_n)
            return _real(*a, **k)

        monkeypatch.setattr(xla_serve, name, spy)
    w = jnp.asarray(rng.normal(size=(96, 64)).astype(np.float32))
    pw = pack_weight(w, QuantizerParams(KIND_FP_SIGNED, 2, 1, 4,
                                        jnp.float32(2.0)))
    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(2.0))
    x = jnp.asarray(rng.normal(size=(8, 96)).astype(np.float32))
    ops.msfp_quantize(x, qp)
    ops.w4_matmul(x, pw)
    ops.w4a4_matmul(x, pw, qp)
    wc = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32))
    xc = jnp.asarray(rng.normal(size=(1, 6, 6, 4)).astype(np.float32))
    ops.w4a4_conv2d(xc, _pack_conv(wc), qp)
    assert seen == {"fast_qdq", "fused_matmul", "w4_matmul", "implicit_conv"}
