"""Mesh-agnostic activation sharding hints.

Model code annotates activations with *logical* specs (axis-name strings);
``shard_hint`` filters them against the ambient mesh (axes that exist,
divisibility) so the same model runs on 1 CPU device, a 16x16 pod, or the
2x16x16 multi-pod mesh without edits.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

# Logical data-parallel axes. ``DP`` is a *sentinel* resolved at trace
# time against ``_DP_AXES`` so model modules that imported it by value
# still honor set_dp_axes() — the small-model pure-DP mode (dpall) extends
# batch sharding over the model axis and the activation hints must agree
# with the input shardings or GSPMD inserts reshards.
DP = "__dp__"
MODEL = "model"
_DP_AXES: tuple = ("pod", "data")


def set_dp_axes(axes: tuple) -> None:
    global _DP_AXES
    _DP_AXES = tuple(axes)


def _expand(entry):
    if entry == DP:
        return _DP_AXES
    if isinstance(entry, tuple):
        out = []
        for e in entry:
            out.extend(_DP_AXES if e == DP else (e,))
        return tuple(out)
    return entry


def ambient_mesh():
    """The mesh ``jax.set_mesh`` installed, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _filter_entry(entry, dim: int, axis_sizes: dict[str, int]):
    if entry is None:
        return None
    names = (entry,) if isinstance(entry, str) else tuple(entry)
    kept = []
    prod = 1
    for n in names:
        if n in axis_sizes and dim % (prod * axis_sizes[n]) == 0:
            kept.append(n)
            prod *= axis_sizes[n]
    if not kept:
        return None
    return kept[0] if len(kept) == 1 else tuple(kept)


def logical_spec(shape: tuple, entries: tuple) -> P:
    """Resolve logical entries against the ambient mesh; P() if no mesh."""
    mesh = ambient_mesh()
    if mesh is None:
        return P()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    resolved = [_filter_entry(_expand(e), shape[i], sizes)
                for i, e in enumerate(entries)]
    return P(*resolved)


def shard_hint(x, *entries):
    """with_sharding_constraint against the ambient mesh; no-op without one.

    entries: per-dim logical axis name(s) or None, e.g.
    ``shard_hint(h, DP, None, None)`` for (batch, seq, d_model).
    """
    mesh = ambient_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, logical_spec(x.shape, entries))
