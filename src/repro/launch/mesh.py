"""Production meshes.

Single pod:  (16, 16)      ("data", "model")   = 256 chips (v5e pod)
Multi-pod:   (2, 16, 16)   ("pod", "data", "model") = 512 chips

The ``pod`` axis carries only data parallelism (gradient all-reduce and
optional ZeRO sharding of optimizer state) — never per-layer tensor
collectives, so cross-pod traffic stays on the DCN-friendly path.

Defined as functions (not module constants) so importing this module never
touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.

Axes are ``Auto``: GSPMD propagates shardings from the inputs and the
models' ``shard_hint`` constraints. Install one with ``jax.set_mesh``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    model = min(model, n)
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_chip_count(mesh) -> int:
    n = 1
    for s in mesh.axis_sizes:
        n *= s
    return n
