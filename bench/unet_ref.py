"""UNet weights made from a seed, and the plain reference of the served forward.

Nothing here imports the program under test. The weights are the
benchmark's: one jitted call makes them on the device from the seed, in
the nested layout the program's UNet reads (``"down_0.res_0/conv1/w"``),
and the reference makes them again from the same seed when it checks a
run. The reference is a straightforward float32 ``jax.numpy`` UNet
(DDPM/LDM family: ResBlocks with a timestep embedding, single-head
self-attention at the listed resolutions, nearest-neighbour upsampling,
optional class-label embedding added to the timestep embedding) that
applies the configuration's quantization recipe:

* every conv and dense input is snapped to the activation format (signed
  ExMy at a fixed grid maximum);
* every weight site is first merged with its TALoRA adapter for the
  segment, ``W + A[slot] @ B[slot] * alpha / rank``;
* non-io weights are then snapped to the weight format, with the grid
  maximum the absolute maximum of the un-merged weight (per tensor);
* io weights (``io_sites``) are rounded to bfloat16.

``precision="f32"`` runs every dot and conv at float32 (HIGHEST);
``"bf16"`` rounds their operands to bfloat16 and accumulates in float32,
one MXU pass on the TPU: that is the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------


def sub_seed(seed: int, what: str) -> np.random.SeedSequence:
    """An independent stream per use of the run's seed (any size of int)."""
    return np.random.SeedSequence([int(seed), *map(ord, what)])


def jax_key(seed: int, what: str) -> jax.Array:
    """A threefry key from 64 bits of ``sub_seed(seed, what)``."""
    data = sub_seed(seed, what).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")


# ---------------------------------------------------------------------------
# parameter layout
# ---------------------------------------------------------------------------


def param_specs(m: dict) -> list[tuple[str, tuple, str]]:
    """(path, shape, kind) for every leaf of the UNet, in a fixed order.

    ``m`` is the configuration's ``model`` block. Kinds: ``w`` weight
    (fan-in scaled normal), ``b`` bias, ``g`` norm gain, ``table`` class
    embedding.
    """
    ch, temb = m["ch"], 4 * m["ch"]
    specs: list[tuple[str, tuple, str]] = []

    def dense(p, d_in, d_out):
        specs.extend([(f"{p}/w", (d_in, d_out), "w"), (f"{p}/b", (d_out,), "b")])

    def conv(p, c_in, c_out, k=3):
        specs.extend([(f"{p}/w", (k, k, c_in, c_out), "w"),
                      (f"{p}/b", (c_out,), "b")])

    def norm(p, c):
        specs.extend([(f"{p}/g", (c,), "g"), (f"{p}/b", (c,), "b")])

    def res(p, c_in, c_out):
        norm(f"{p}/norm1", c_in)
        conv(f"{p}/conv1", c_in, c_out)
        dense(f"{p}/temb", temb, c_out)
        norm(f"{p}/norm2", c_out)
        conv(f"{p}/conv2", c_out, c_out)
        if c_in != c_out:
            conv(f"{p}/skip", c_in, c_out, 1)

    def attn(p, c):
        norm(f"{p}/norm", c)
        for n in ("q", "k", "v", "proj"):
            dense(f"{p}/{n}", c, c)

    dense("temb0", ch, temb)
    dense("temb1", temb, temb)
    conv("conv_in", m["in_ch"], ch)
    if m.get("num_classes"):
        specs.append(("class_emb/table", (m["num_classes"], temb), "table"))
    res_px, chans, c = m["image_size"], [ch], ch
    levels = len(m["ch_mult"])
    for i, mult in enumerate(m["ch_mult"]):
        for j in range(m["num_res_blocks"]):
            res(f"down_{i}.res_{j}", c, ch * mult)
            c = ch * mult
            if res_px in m["attn_resolutions"]:
                attn(f"down_{i}.attn_{j}", c)
            chans.append(c)
        if i != levels - 1:
            conv(f"down_{i}.downsample", c, c)
            res_px //= 2
            chans.append(c)
    res("mid.res_0", c, c)
    attn("mid.attn", c)
    res("mid.res_1", c, c)
    for i in reversed(range(levels)):
        for j in range(m["num_res_blocks"] + 1):
            c_skip = chans.pop()
            res(f"up_{i}.res_{j}", c + c_skip, ch * m["ch_mult"][i])
            c = ch * m["ch_mult"][i]
            if res_px in m["attn_resolutions"]:
                attn(f"up_{i}.attn_{j}", c)
        if i != 0:
            conv(f"up_{i}.upsample", c, c)
            res_px *= 2
    norm("norm_out", c)
    conv("conv_out", c, m["out_ch"])
    return specs


def weight_sites(m: dict) -> list[str]:
    """Every conv and dense weight, sorted: the TALoRA sites, one routing
    column each, in the order of the routing signatures."""
    return sorted(p for p, shape, kind in param_specs(m)
                  if kind == "w" and len(shape) >= 2)


def nest(flat: dict) -> dict:
    """``{"a/b/c": x}`` -> ``{"a": {"b": {"c": x}}}``."""
    root: dict = {}
    for path, v in flat.items():
        node = root
        *head, leaf = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[leaf] = v
    return root


def flat(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


# ---------------------------------------------------------------------------
# weights and adapters, made on the device from the seed
# ---------------------------------------------------------------------------


# Random weights as after training, the same for every configuration: conv
# and dense weights N(0, 1/fan_in), biases and norm offsets BIAS_SCALE *
# N(0, 1), norm gains 1 + NORM_SCALE * N(0, 1); a trained adapter's merged
# update ADAPTER_SCALE of its weight's scale.
BIAS_SCALE = 0.1
NORM_SCALE = 0.1
ADAPTER_SCALE = 0.1


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, layout: tuple) -> tuple[dict, dict]:
    specs, rank, hub = layout
    keys = jax.random.split(key, 2 * len(specs))
    params, hubs = {}, {}
    for n, (path, shape, kind) in enumerate(specs):
        z = jax.random.normal(keys[2 * n], shape, jnp.float32)
        if kind == "w":
            d_in = math.prod(shape[:-1])
            params[path] = z / math.sqrt(d_in)
            ka, kb = jax.random.split(keys[2 * n + 1])
            # a trained adapter: A ~ N(0, 1/rank), and B such that the
            # merged update is ADAPTER_SCALE of the weight's own scale
            hubs[path] = {
                "A": jax.random.normal(ka, (hub, d_in, rank)) / math.sqrt(rank),
                "B": jax.random.normal(kb, (hub, rank, shape[-1]))
                * (ADAPTER_SCALE / math.sqrt(d_in)),
            }
        elif kind == "g":
            params[path] = 1.0 + NORM_SCALE * z
        elif kind == "table":
            params[path] = z
        else:
            params[path] = BIAS_SCALE * z
    return params, hubs


def make_weights(seed: int, cfg: dict) -> tuple[dict, dict]:
    """(params, hubs): the nested UNet tree and ``{site: {"A", "B"}}``."""
    tl = cfg["talora"]
    layout = (tuple(param_specs(cfg["model"])), tl["rank"], tl["hub_size"])
    params, hubs = _init(jax_key(seed, "weights"), layout)
    return nest(params), hubs


def signatures(seed: int, cfg: dict) -> np.ndarray:
    """(T, n_sites) routing slot per timestep and site: ``segments``
    contiguous equal ranges of T, slots drawn from the seed, adjacent
    ranges made to differ so that each range is a segment of its own."""
    T, n_seg = cfg["T"], cfg["talora"]["segments"]
    n_sites = len(weight_sites(cfg["model"]))
    rng = np.random.default_rng(sub_seed(seed, "routing"))
    rows = rng.integers(0, cfg["talora"]["hub_size"], (n_seg, n_sites))
    for s in range(1, n_seg):
        if np.array_equal(rows[s], rows[s - 1]):
            rows[s, 0] = 1 - rows[s, 0]
    bounds = segment_bounds(cfg)
    sig = np.zeros((T, n_sites), np.int32)
    for s, (lo, hi) in enumerate(bounds):
        sig[lo:hi + 1] = rows[s]
    return sig


def segment_bounds(cfg: dict) -> list[tuple[int, int]]:
    """Inclusive [lo, hi] timestep range of each pinned segment."""
    T, n = cfg["T"], cfg["talora"]["segments"]
    edges = [round(T * s / n) for s in range(n + 1)]
    return [(edges[s], edges[s + 1] - 1) for s in range(n)]


# ---------------------------------------------------------------------------
# the quantization recipe
# ---------------------------------------------------------------------------


def fp_base_max(exp_bits: int, man_bits: int) -> float:
    return float(2 ** (2**exp_bits - 2) * (2.0 - 2.0**-man_bits))


def grid(exp_bits: int, man_bits: int) -> np.ndarray:
    """The non-negative values of a signed ExMy format at unit scale: the
    mantissa steps of each octave up to the format's maximum."""
    base_max = fp_base_max(exp_bits, man_bits)
    vals = set()
    for octave in range(2**exp_bits - 1):
        step = 2.0 ** (octave - man_bits)
        lo = 0.0 if octave == 0 else 2.0**octave
        vals.update(v for v in np.arange(lo, 2.0 ** (octave + 1) + step, step)
                    if v <= base_max)
    return np.asarray(sorted(vals), np.float64)


# A value within this share of a midpoint between two grid values may be
# snapped to either: two sound float32 computations of it can differ there
# in their last bits.
TIE = 2.0**-20


def _scaled(x, fmt: dict, maxval):
    g = grid(fmt["exp_bits"], fmt["man_bits"])
    scale = jnp.asarray(maxval, jnp.float32) / float(g[-1])
    return g, scale, jnp.abs(x) / jnp.maximum(scale, 1e-30)


def snap(x, fmt: dict, maxval, mode: str = "nearest"):
    """Signed ExMy quantize-dequantize at grid maximum ``maxval``: |x| over
    the scale is compared with each midpoint between two grid values and
    takes the grid value on its side (so it is exact: no rounding on the
    way), clipped at the maximum. ``mode="other"`` takes the grid value on
    the other side of a midpoint that lies within ``TIE`` of it."""
    g, scale, y = _scaled(x, fmt, maxval)
    q = jnp.zeros_like(y)
    for lo, hi in zip(g[:-1], g[1:]):
        mid = (lo + hi) / 2
        up = y > mid
        if mode == "other":
            up = up ^ (jnp.abs(y - mid) <= TIE * mid)
        q = q + jnp.where(up, hi - lo, 0.0)
    return jnp.sign(x) * q * scale


def at_midpoint(x, fmt: dict, maxval):
    """Where ``snap`` may take either grid value: within ``TIE`` of a
    midpoint."""
    g, _, y = _scaled(x, fmt, maxval)
    near = jnp.zeros(y.shape, bool)
    for lo, hi in zip(g[:-1], g[1:]):
        mid = (lo + hi) / 2
        near = near | (jnp.abs(y - mid) <= TIE * mid)
    return near


def _dot(a, b, precision: str):
    if precision == "bf16":
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(a, b, precision=F32)


def _einsum(spec, a, b, precision: str):
    if precision == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=F32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _served_weights(params, hubs, slots, recipe: tuple, precision: str):
    io, wfmt, alpha_over_rank = recipe
    wfmt = dict(wfmt)
    out = flat(params)
    ties = {}
    for n, site in enumerate(sorted(hubs)):
        w = out[site]
        a = hubs[site]["A"][slots[n]]
        b = hubs[site]["B"][slots[n]]
        # the adapter's product is rounded before it is added, as written;
        # the barrier keeps the compiler from fusing the add into the dot
        delta = lax.optimization_barrier(_dot(a, b, precision))
        merged = w + delta.reshape(w.shape) * alpha_over_rank
        if site.split("/")[0] in io:
            # rounded to bfloat16 for certain: XLA may drop a round trip of
            # converts to bfloat16 and back (excess precision)
            out[site] = lax.reduce_precision(merged, exponent_bits=8,
                                             mantissa_bits=7)
        else:
            maxval = jnp.maximum(jnp.max(jnp.abs(w)), 1e-8)
            out[site] = snap(merged, wfmt, maxval)
            ties[site] = at_midpoint(merged, wfmt, maxval)
    return nest(out), ties


def served_weights(params, hubs, slots, cfg: dict, precision: str = "f32"):
    """(weights, ties): the weights one segment serves, merged with the
    adapters the segment's ``slots`` select, then quantized by the recipe;
    and, by site, where a quantized weight lay at a midpoint
    (``at_midpoint``)."""
    q, tl = cfg["quant"], cfg["talora"]
    recipe = (tuple(q["io_sites"]), tuple(sorted(q["weight_format"].items())),
              tl["alpha"] / tl["rank"])
    return _served_weights(params, hubs, jnp.asarray(slots), recipe,
                           precision)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _group_norm(p, x, groups, eps=1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(b, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    return ((xg - mu) * lax.rsqrt(var + eps)).reshape(b, h, w, c) * p["g"] + p["b"]


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _timestep_embedding(t, dim, max_period=10_000.0):
    half = dim // 2
    freqs = jnp.exp(-np.log(max_period) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    args = t.astype(jnp.float32)[:, None] * freqs
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def forward(p, x, t, y, cfg: dict, precision: str = "f32", tap=None,
            mode: str = "nearest"):
    """eps = UNet(x, t[, y]) with every conv/dense input snapped to the
    activation format (``mode`` as in ``snap``); ``p`` from
    ``served_weights``.

    ``tap(site, h)``, where given, sees each site's input ``h`` before it is
    snapped (``site`` is the weight's path less ``/w``, as
    ``"down_0.res_0/conv1"``) and returns what the site takes in its place:
    ``h`` itself to record it, a served input to hold the reference to the
    served forward site by site."""
    m, q = cfg["model"], cfg["quant"]
    afmt, amax = q["act_format"], q["act_format"]["maxval"]
    groups = m.get("gn_groups", 32)

    def weights(site):
        node = p
        for k in site.split("/"):
            node = node[k]
        return node

    def act(site, h):
        return snap(h if tap is None else tap(site, h), afmt, amax, mode)

    def dense(site, h):
        pp = weights(site)
        return _dot(act(site, h), pp["w"], precision) + pp["b"]

    def conv(site, h, stride=1):
        pp = weights(site)
        lhs, w = act(site, h), pp["w"]
        if precision == "bf16":
            lhs, w = lhs.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        out = lax.conv_general_dilated(
            lhs, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=None if precision == "bf16" else F32,
            preferred_element_type=jnp.float32)
        return out + pp["b"]

    def res(site, h, temb):
        r = conv(f"{site}/conv1",
                 _silu(_group_norm(weights(f"{site}/norm1"), h, groups)))
        r = r + dense(f"{site}/temb", _silu(temb))[:, None, None, :]
        r = conv(f"{site}/conv2",
                 _silu(_group_norm(weights(f"{site}/norm2"), r, groups)))
        if "skip" in weights(site):
            h = conv(f"{site}/skip", h)
        return h + r

    def attn(site, h):
        b, hh, ww, c = h.shape
        n = _group_norm(weights(f"{site}/norm"), h, groups).reshape(b, hh * ww, c)
        qq, kk, vv = (dense(f"{site}/{k}", n) for k in ("q", "k", "v"))
        w = jax.nn.softmax(_einsum("bqc,bkc->bqk", qq, kk, precision)
                           * (c ** -0.5), axis=-1)
        o = _einsum("bqk,bkc->bqc", w, vv, precision)
        return h + dense(f"{site}/proj", o).reshape(b, hh, ww, c)

    temb = dense("temb0", _timestep_embedding(t, m["ch"]))
    temb = dense("temb1", _silu(temb))
    if y is not None:
        temb = temb + p["class_emb"]["table"][y]
    h = conv("conv_in", x)
    hs = [h]
    levels = len(m["ch_mult"])
    for i in range(levels):
        for j in range(m["num_res_blocks"]):
            h = res(f"down_{i}.res_{j}", h, temb)
            if f"down_{i}.attn_{j}" in p:
                h = attn(f"down_{i}.attn_{j}", h)
            hs.append(h)
        if i != levels - 1:
            h = conv(f"down_{i}.downsample", h, stride=2)
            hs.append(h)
    h = res("mid.res_0", h, temb)
    h = attn("mid.attn", h)
    h = res("mid.res_1", h, temb)
    for i in reversed(range(levels)):
        for j in range(m["num_res_blocks"] + 1):
            h = res(f"up_{i}.res_{j}", jnp.concatenate([h, hs.pop()], axis=-1),
                    temb)
            if f"up_{i}.attn_{j}" in p:
                h = attn(f"up_{i}.attn_{j}", h)
        if i != 0:
            h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
            h = conv(f"up_{i}.upsample", h)
    h = _silu(_group_norm(p["norm_out"], h, groups))
    return conv("conv_out", h)


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------


def alpha_bars(cfg: dict) -> np.ndarray:
    """Cumulative alpha products of the configuration's linear schedule."""
    s = cfg["schedule"]
    betas = np.linspace(s["beta_start"], s["beta_end"], cfg["T"],
                        dtype=np.float64)
    return np.cumprod(1.0 - betas).astype(np.float32)


def ddim_timesteps(T: int, steps: int) -> np.ndarray:
    """DDIM's uniform-stride subsequence of [0, T), descending."""
    seq = np.linspace(0, T - 1, steps).round().astype(np.int64)
    return np.unique(seq)[::-1].copy()


def ddim_step(ab: np.ndarray, x, t: int, t_prev: int, eps):
    """Deterministic DDIM (eta 0) update x_t -> x_{t_prev} of one state,
    in the precision of ``x`` and ``eps`` (host arrays); t_prev -1 is x_0."""
    ab_t = float(ab[t])
    ab_p = float(ab[t_prev]) if t_prev >= 0 else 1.0
    x0 = (x - math.sqrt(1 - ab_t) * eps) / math.sqrt(ab_t)
    return math.sqrt(ab_p) * x0 + math.sqrt(max(1 - ab_p, 0.0)) * eps


def eps_coefficient(ab: np.ndarray, t: int, t_prev: int) -> float:
    """d x_{t_prev} / d eps of ``ddim_step``: how an error in eps shows in
    the next state."""
    ab_t = float(ab[t])
    ab_p = float(ab[t_prev]) if t_prev >= 0 else 1.0
    return math.sqrt(1 - ab_p) - math.sqrt(ab_p) * math.sqrt(1 - ab_t) / math.sqrt(ab_t)
