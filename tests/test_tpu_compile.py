"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Interpret mode runs the kernel code but not Mosaic's lowering rules: tile
alignment, supported in-VMEM ops and the scoped VMEM limit are only
checked by compiling for the chip. The TPU compiler is installed here and
compiles for a chip that is described, not attached, so these tests run
on the CPU. Shapes are those the engine dispatches for ``ddim-cifar10``
(32 px, ch 128, ch_mult (1, 2, 2, 2)) at its largest bucket, batch 4.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.qmodule import PackedW4
from repro.kernels import ops
from repro.kernels.conv import (implicit_vmem_bytes, w4a4_conv2d_im2col,
                                w4a4_conv2d_implicit)
from repro.kernels.msfp_quant import msfp_qdq_2d
from repro.kernels.w4_matmul import (XQ_VMEM_BUDGET, w4_matmul_2d,
                                     w4a4_matmul_2d)
from repro.quant.fakequant import (KIND_FP_SIGNED, KIND_FP_UNSIGNED,
                                   QuantizerParams)

B = 4   # the engine's largest bucket at max_batch 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the TPU library writes no log files for a described chip
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache out.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _act(signed: bool) -> QuantizerParams:
    if signed:
        return QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(6.0))
    return QuantizerParams(KIND_FP_UNSIGNED, 2, 2, 4, jnp.float32(6.0),
                           jnp.float32(-0.3))


def _conv_args(spec, h, cin, cout, k=3):
    return (spec((B, h, h, cin), jnp.float32),
            spec((k * k * cin, cout // 2), jnp.uint8),
            spec((cout,), jnp.float32))


def _pw(packed, scale, k, cin, cout):
    return PackedW4(packed, scale, jnp.float32(0.0), 2, 1, True,
                    (k, k, cin, cout))


@pytest.mark.parametrize("m,k,n", [
    (B, 512, 128),                          # level-0 temb
    (B * 256, 256, 256),                    # 16 px attention
    (128, XQ_VMEM_BUDGET // (128 * 4), 256)],  # snap-once scratch at its cap
    ids=["N128", "N256", "xq_budget"])
def test_w4a4_matmul_compiles(spec, m, k, n):
    _compile(lambda x, p, s: w4a4_matmul_2d(
        x, p, s, 0.0, 6.0, 0.0, exp_bits=2, man_bits=1, signed=True,
        act_exp_bits=2, act_man_bits=1, act_signed=True),
        spec((m, k), jnp.float32), spec((k, n // 2), jnp.uint8),
        spec((n,), jnp.float32))


def test_w4_matmul_compiles(spec):
    _compile(lambda x, p, s: w4_matmul_2d(x, p, s, exp_bits=2, man_bits=1,
                                          signed=True),
             spec((B, 512), jnp.float32), spec((512, 256), jnp.uint8),
             spec((512,), jnp.float32))


@pytest.mark.parametrize("signed", [True, False], ids=["signed", "unsigned"])
def test_implicit_conv_compiles(spec, signed):
    act = _act(signed)
    _compile(lambda x, p, s: w4a4_conv2d_implicit(
        x, _pw(p, s, 3, 256, 256), act, stride=(1, 1), padding="SAME"),
        *_conv_args(spec, 16, 256, 256))


@pytest.mark.parametrize("stride,want", [(2, "im2col"), (1, "implicit")],
                         ids=["downsample", "level0"])
def test_conv_route_compiles(spec, stride, want):
    """What ``ops._conv_route`` picks for a 32x32x128 conv compiles: the
    stride-2 downsample goes to im2col (Mosaic refuses strided in-VMEM tap
    slices), the stride-1 128 -> 128 conv stays implicit."""
    x, p, s = _conv_args(spec, 32, 128, 128)
    act = _act(True)
    route = ops._conv_route(x, _pw(p, s, 3, 128, 128), (stride, stride),
                            "SAME", fused=True, interpret=False)
    assert route == want
    fn = w4a4_conv2d_implicit if route == "implicit" else w4a4_conv2d_im2col
    _compile(lambda x, p, s: fn(x, _pw(p, s, 3, 128, 128), act,
                                stride=(stride, stride), padding="SAME"),
             x, p, s)


@pytest.mark.parametrize("h,cin,cout", [(32, 128, 128), (32, 256, 128),
                                        (16, 256, 256), (4, 512, 256)])
def test_implicit_vmem_estimate_covers_compiler(spec, h, cin, cout):
    """``implicit_vmem_bytes`` gates the implicit route against the scoped
    VMEM limit; the kernel must compile when given exactly that many
    bytes, so the estimate never under-counts (``tools/vmem_fit.py``
    prints the margin at every UNet shape)."""
    from tools.vmem_fit import compiles

    est = implicit_vmem_bytes((B, h, h, cin), (3, 3, cin, cout), (1, 1),
                              "SAME", fused=True)
    assert compiles(spec, h, cin, cout, True, est)


@pytest.mark.parametrize("c", [3, 128], ids=["conv_in", "conv_out"])
def test_msfp_qdq_compiles(spec, c):
    """The act snap ahead of the two 8-bit io convs."""
    _compile(lambda x: msfp_qdq_2d(x, 6.0, 0.0, exp_bits=2, man_bits=1,
                                   signed=True),
             spec((B * 32 * 32, c), jnp.float32))
