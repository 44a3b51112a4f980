"""Compare ``conv.implicit_vmem_bytes`` with what the v5e compiler needs.

For each implicit-GEMM conv shape of ``ddim-cifar10`` (and one 64 px
shape past the budget), finds by bisection the smallest scoped-VMEM limit
under which the kernel compiles for a described TPU v5e, and prints it
beside the estimate that gates the implicit route. No chip is needed: the
TPU compiler compiles for a described topology on the CPU.

    JAX_PLATFORMS=cpu PYTHONPATH=src python tools/vmem_fit.py

Run it after changing the implicit kernel's body or blocks; an estimate
below the need lets ``ops._conv_route`` pick a kernel the compiler
refuses (``tests/test_tpu_compile.py`` checks the same at four shapes).
Takes a few minutes.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental import topologies
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from repro.core.qmodule import PackedW4
from repro.kernels import conv
from repro.quant.fakequant import KIND_FP_SIGNED, QuantizerParams

B = 4
MIB = 1 << 20
# (spatial, cin, cout) of the stride-1 3x3 convs, plus one past the budget
SHAPES = [(32, 128, 128), (32, 256, 128), (32, 384, 128), (16, 128, 256),
          (16, 256, 256), (16, 512, 256), (8, 256, 256), (8, 512, 256),
          (4, 256, 256), (4, 512, 256), (64, 128, 128)]


def compiles(spec, h, cin, cout, fused: bool, limit: int) -> bool:
    """Whether the batch-B implicit conv compiles under a scoped-VMEM
    ``limit`` of bytes; ``spec(shape, dtype)`` places its operands."""
    real = pl.pallas_call

    def limited(*a, **k):
        return real(*a, **k, compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=limit))

    act = (QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(6.0))
           if fused else None)
    fn = jax.jit(lambda x, p, s: conv.w4a4_conv2d_implicit.__wrapped__(
        x, PackedW4(p, s, jnp.float32(0.0), 2, 1, True, (3, 3, cin, cout)),
        act, stride=(1, 1), padding="SAME"))
    pl.pallas_call = limited
    try:
        fn.lower(spec((B, h, h, cin), jnp.float32),
                 spec((9 * cin, cout // 2), jnp.uint8),
                 spec((cout,), jnp.float32)).compile()
        return True
    except jax.errors.JaxRuntimeError as e:
        if "vmem" not in str(e).lower():
            raise
        return False
    finally:
        pl.pallas_call = real


def smallest_limit(spec, h, cin, cout, fused: bool) -> int:
    lo, hi = 0, 100 * MIB
    while hi - lo > 16 * 1024:
        mid = (lo + hi) // 2 // 1024 * 1024
        lo, hi = (lo, mid) if compiles(spec, h, cin, cout, fused, mid) \
            else (mid, hi)
    return hi


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    print(f"budget {conv.IMPLICIT_VMEM_BUDGET / MIB:.2f} MiB")
    for h, cin, cout in SHAPES:
        for fused in (True, False):
            est = conv.implicit_vmem_bytes((B, h, h, cin), (3, 3, cin, cout),
                                           (1, 1), "SAME", fused=fused)
            need = smallest_limit(spec, h, cin, cout, fused)
            print(f"{h}x{h}x{cin}->{cout} fused={fused}: "
                  f"need {need / MIB:.2f} MiB, estimate {est / MIB:.2f} MiB, "
                  f"estimate/need {est / need:.2f}"
                  + ("" if est >= need else "  UNDER-COUNTS"), flush=True)


if __name__ == "__main__":
    main()
