"""Token-choice top-k MoE with fixed expert capacity (sort-based dispatch).

Dispatch avoids the GShard (T, E, C) one-hot (which materializes at
65k x 384 x 1700 for kimi-scale inputs): instead we sort the (T*k)
token-expert assignments by expert id, compute each entry's position
within its expert segment with a cummax trick, and scatter into a dense
(E, C, d) buffer. Combine is the inverse gather, weighted by router probs.

Sharding: experts live on the ``model`` mesh axis (expert parallelism);
the scatter/gather across the token <-> expert resharding lowers to
all-to-all-style collectives under GSPMD. Capacity overflows drop (standard
for fixed-capacity MoE); capacity_factor sizes the buffer.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.nn.layers import dense_apply, dense_init
from repro.nn.mlp import ACTIVATIONS


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                    # per-expert hidden
    n_experts: int
    top_k: int
    n_shared: int = 0            # shared (always-on) experts
    capacity_factor: float = 1.25
    act: str = "silu"
    router_dtype: str = "float32"


def moe_init(key, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    s_in = 1.0 / jnp.sqrt(d)
    s_out = 1.0 / jnp.sqrt(f)
    p = {
        "router": dense_init(ks[0], d, e, dtype=jnp.float32),
        "w_gate": jax.random.normal(ks[1], (e, d, f), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (e, d, f), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (e, f, d), dtype) * s_out,
    }
    if cfg.n_shared:
        from repro.nn.mlp import glu_mlp_init
        p["shared"] = glu_mlp_init(ks[4], d, f * cfg.n_shared, dtype)
    return p


def capacity(n_tokens: int, cfg: MoEConfig) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, ((c + 7) // 8) * 8)  # pad to a lane-friendly multiple


def _positions_in_segment(sorted_ids: jnp.ndarray) -> jnp.ndarray:
    """For a sorted id vector, the rank of each entry within its id run."""
    n = sorted_ids.shape[0]
    idx = jnp.arange(n)
    is_start = jnp.concatenate([jnp.ones((1,), bool),
                                sorted_ids[1:] != sorted_ids[:-1]])
    seg_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    return idx - seg_start


def moe_apply(p: dict, x: jnp.ndarray, cfg: MoEConfig, *, ctx=None,
              site: str | None = None) -> jnp.ndarray:
    """x: (..., d) -> (..., d). Flattens leading dims into tokens."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    k = cfg.top_k
    e = cfg.n_experts
    c = capacity(t, cfg)

    # Router (fp32 for numerics; kept unquantized like the paper's sensitive layers)
    logits = dense_apply(p["router"], xt.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_ids = jax.lax.top_k(probs, k)                     # (T,k)
    gate_w = (gate_w / jnp.clip(gate_w.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)

    # ---- dispatch: sort (T*k) assignments by expert ----
    flat_e = gate_ids.reshape(-1)                                  # (T*k,)
    flat_w = gate_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)
    se, sw, st = flat_e[order], flat_w[order], flat_tok[order]
    pos = _positions_in_segment(se)
    keep = pos < c
    dst = jnp.where(keep, se * c + pos, e * c)                     # sentinel row

    xq = ctx.act(f"{site}/experts", xt) if (ctx is not None and site) else xt
    buf = jnp.zeros((e * c + 1, d), xq.dtype).at[dst].set(xq[st])
    hidden = buf[:-1].reshape(e, c, d)

    # ---- expert FFN (batched over experts; experts shard on 'model') ----
    def w(name):
        from repro.core.qmodule import PackedW4, dequant_weight
        wt = p[name]
        if isinstance(wt, PackedW4):  # W4 serving: dequant per expert block
            return dequant_weight(wt, hidden.dtype)
        return wt.astype(hidden.dtype)

    act = ACTIVATIONS[cfg.act]
    g = jnp.einsum("ecd,edf->ecf", hidden, w("w_gate"))
    u = jnp.einsum("ecd,edf->ecf", hidden, w("w_up"))
    h = act(g) * u
    if ctx is not None and site:
        h = ctx.act(f"{site}/down", h)
    out_e = jnp.einsum("ecf,efd->ecd", h, w("w_down"))

    # ---- combine: gather back and weight ----
    flat_out = out_e.reshape(e * c, d)
    gathered = jnp.where(keep[:, None], flat_out[jnp.clip(dst, 0, e * c - 1)], 0)
    contrib = gathered * sw[:, None]
    yt = jnp.zeros((t, d), x.dtype).at[st].add(contrib)

    if "shared" in p:
        from repro.nn.mlp import glu_mlp_apply
        yt = yt + glu_mlp_apply(p["shared"], xt, act=cfg.act, ctx=ctx,
                                site=f"{site}/shared" if site else None)
    return yt.reshape(*lead, d)


def _dispatch_local(xt, probs, cfg: MoEConfig, c: int):
    """Sort-based dispatch of LOCAL tokens into a (E, c, d) buffer.

    Returns (hidden, combine_meta) where combine_meta re-gathers outputs."""
    t, d = xt.shape
    k, e = cfg.top_k, cfg.n_experts
    gate_w, gate_ids = jax.lax.top_k(probs, k)
    gate_w = (gate_w / jnp.clip(gate_w.sum(-1, keepdims=True), 1e-9)).astype(xt.dtype)
    flat_e = gate_ids.reshape(-1)
    flat_w = gate_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)
    se, sw, st = flat_e[order], flat_w[order], flat_tok[order]
    pos = _positions_in_segment(se)
    keep = pos < c
    dst = jnp.where(keep, se * c + pos, e * c)
    buf = jnp.zeros((e * c + 1, d), xt.dtype).at[dst].set(xt[st])
    return buf[:-1].reshape(e, c, d), (keep, dst, st, sw)


def _combine_local(out_e, meta, t: int, d: int, dtype):
    keep, dst, st, sw = meta
    e_c = out_e.shape[0] * out_e.shape[1]
    flat_out = out_e.reshape(e_c, -1)
    gathered = jnp.where(keep[:, None], flat_out[jnp.clip(dst, 0, e_c - 1)], 0)
    return jnp.zeros((t, d), dtype).at[st].add(gathered * sw[:, None])


def moe_apply_ep(p: dict, x: jnp.ndarray, cfg: MoEConfig, *,
                 model_axis: str = "model", ctx=None,
                 site: str | None = None) -> jnp.ndarray:
    """Expert-parallel MoE via shard_map (the §Perf fix for the baseline's

    global-argsort dispatch, which GSPMD lowers to TB-scale sort
    collectives). Each data shard sorts/buckets its LOCAL tokens, then a
    single tiled all-to-all over the ``model`` axis reshards
    (E, C_local, d) -> (E_local, mp*C_local, d); experts compute locally;
    the inverse all-to-all + local gather combines. Collective volume is
    2x the dispatched activations — the textbook EP lower bound.
    """
    from jax.sharding import PartitionSpec as P

    from repro.common.sharding import ambient_mesh

    mesh = ambient_mesh()
    if mesh is None or model_axis not in mesh.axis_names:
        return moe_apply(p, x, cfg, ctx=ctx, site=site)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    mp = sizes[model_axis]
    if cfg.n_experts % mp != 0:
        return moe_apply(p, x, cfg, ctx=ctx, site=site)
    dp_axes = tuple(a for a in mesh.axis_names if a != model_axis)

    lead = x.shape[:-1]
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    # Tokens shard over EVERY mesh axis for the dispatch (model included) —
    # dispatching on dp-only shards would replicate the sort/scatter across
    # the model axis (the refuted first attempt in §Perf iteration B1).
    tok_axes = (*dp_axes, model_axis)
    n_shards = 1
    for a in tok_axes:
        n_shards *= sizes[a]
    if t % n_shards != 0:
        return moe_apply(p, x, cfg, ctx=ctx, site=site)
    t_local = t // n_shards
    c_l = capacity(t_local, cfg)

    def local_fn(xt_l, router_w, w_gate_l, w_up_l, w_down_l):
        xt_l = xt_l.reshape(-1, d)  # (T_l, d)
        logits = xt_l.astype(jnp.float32) @ router_w
        probs = jax.nn.softmax(logits, axis=-1)
        hidden, meta = _dispatch_local(xt_l, probs, cfg, c_l)  # (E, c_l, d)
        # (E, c_l, d) -> (E/mp, mp*c_l, d)
        hidden = jax.lax.all_to_all(hidden, model_axis, split_axis=0,
                                    concat_axis=1, tiled=True)
        act = ACTIVATIONS[cfg.act]
        g = jnp.einsum("ecd,edf->ecf", hidden, w_gate_l.astype(hidden.dtype))
        u = jnp.einsum("ecd,edf->ecf", hidden, w_up_l.astype(hidden.dtype))
        out_e = jnp.einsum("ecf,efd->ecd", act(g) * u,
                           w_down_l.astype(hidden.dtype))
        out_e = jax.lax.all_to_all(out_e, model_axis, split_axis=1,
                                   concat_axis=0, tiled=True)  # (E, c_l, d)
        return _combine_local(out_e, meta, xt_l.shape[0], d, xt_l.dtype)

    in_specs = (P(tok_axes, None), P(None, None), P(model_axis, None, None),
                P(model_axis, None, None), P(model_axis, None, None))
    sharded = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                            out_specs=P(tok_axes, None), check_vma=False)

    yt = sharded(xt, p["router"]["w"].astype(jnp.float32), p["w_gate"],
                 p["w_up"], p["w_down"])

    if "shared" in p:
        from repro.nn.mlp import glu_mlp_apply
        yt = yt + glu_mlp_apply(p["shared"], xt, act=cfg.act, ctx=ctx,
                                site=f"{site}/shared" if site else None)
    return yt.reshape(*lead, d)


def aux_load_balance_loss(logits: jnp.ndarray, gate_ids: jnp.ndarray,
                          cfg: MoEConfig) -> jnp.ndarray:
    """Switch-style load-balance auxiliary loss (used by train recipes)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = probs.mean(axis=0)
    one_hot = jax.nn.one_hot(gate_ids[..., 0], cfg.n_experts)
    ce = one_hot.mean(axis=0)
    return cfg.n_experts * jnp.sum(me * ce)
