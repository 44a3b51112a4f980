"""The comparison that decides ``correct``: what the window served against
the plain reference (``unet_ref``).

The window keeps every state x_0 (the noise) .. x_n (the sample) of a
seeded sample of its requests, and for each step the sizes of the batch
it was served in. After the window the harness reads from the program
the eps of each such step, on the compiled forward the window drove for
that batch size, in batches of that size. For a seeded sample of these
forward rows it reads them from the same forward with every weight
site's input as an output too (the probe), and it reads the weights the
bank serves. The probe is a program of its own; where XLA fuses it
otherwise than the window's forward its last bits differ, and its eps
with them (``probe_gap``, logged). Two numbers follow, each the worst over
what was compared:

* ``step_gap_max``: the served x_{i+1} against the reference's DDIM update
  of the served x_i with the program's eps (the guidance combination
  ``eps_u + s * (eps_c - eps_u)`` for a guided request), as an error in
  eps: the gap beyond float32 rounding (``ROUNDING``) over the update's eps
  coefficient, a share of eps by norm. It holds the sampler step, the
  engine's batching and the guidance combination to the eps that the
  compiled forward gives.
* ``site_gap_max``: each site's input as served against the reference's,
  site by site, and the eps: the reference takes each site's served input
  in place of its own (teacher forcing), so that it recomputes every
  stretch between two sites, and the last (the probe's eps), from what the
  program had there. Each gap is a share of the served value by norm. It
  holds the kernels, the merged and packed weights of every segment
  sampled, and the float ops between the sites to the reference.

Site by site, and not eps against eps: activations are snapped to a
4-bit grid at every site, so two sound float32 computations that differ in
a last bit put some activation on the other side of a grid midpoint, and
through the ~100 sites of a forward that grows to a gap of some tenths in
eps, as large as one that bfloat16 makes. Compared before the snap, with
each stretch starting from the served input, a gap stays at the rounding
of that stretch, save where a value lies at a midpoint to within float32
rounding (``ref.TIE``) and the program snapped it to the other side. Such
a value moves its neighbourhood at the next sites by about a thousandth.
So a weight at a midpoint takes the value the bank serves (the reference
reads which, and no more), and the reference reads each site twice, once
with every activation on its nearest side and once with those at a
midpoint on the other, and takes the smaller gap at each element.

The control puts the reference one precision lower (bfloat16 operands, a
bfloat16 state) in the program's place: its eps, its DDIM step from each
served x_i, its weights and its own site inputs. It is held to the same
numbers.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

import unet_ref as ref

NUMBERS = ("step_gap_max", "site_gap_max")
# the slack of a float32 DDIM update, per element, as a share of |x_{i+1}|
# + |x_i|: four units in the last place
ROUNDING = 4 * float(np.finfo(np.float32).eps)


@dataclasses.dataclass(frozen=True)
class Row:
    """One row of a forward that a tracked step needed."""

    seg: int            # routing segment of t
    t: int
    y: int | None       # the label of a labelled pass
    n_part: int         # rows of the partition it was served in
    x: object           # (1, H, W, C), the served state


@dataclasses.dataclass(frozen=True)
class Step:
    traj: int
    i: int
    t: int
    t_prev: int
    guidance: float
    rows: tuple         # Row indices: (plain,) or (unlabelled, labelled)


def layout(cfg: dict, trajectories: list[dict]) -> tuple[list[Step], list[Row]]:
    """The steps of ``trajectories`` and the forward rows each needed.

    Each trajectory is ``{"steps": sampler steps asked for, "xs": [x_0 ..
    x_n], "parts": [(unlabelled rows, labelled rows) of the tick of each
    step], "y": label or None, "guidance": s}``."""
    bounds = ref.segment_bounds(cfg)
    steps, rows = [], []
    for j, tr in enumerate(trajectories):
        seq, xs = ref.ddim_timesteps(cfg["T"], tr["steps"]), tr["xs"]
        if not len(xs) == len(tr["parts"]) + 1 == len(seq) + 1:
            raise ValueError(f"trajectory of {len(xs) - 1} states and "
                             f"{len(tr['parts'])} batches for {len(seq)} "
                             f"timesteps")
        guided = tr["guidance"] > 0 and tr["y"] is not None
        for i, t in enumerate(seq):
            t_prev = int(seq[i + 1]) if i + 1 < len(seq) else -1
            seg = next(s for s, (lo, hi) in enumerate(bounds) if lo <= t <= hi)
            n_free, n_labelled = tr["parts"][i]
            idx = []
            for y in ([None, tr["y"]] if guided else [tr["y"]]):
                idx.append(len(rows))
                rows.append(Row(seg, int(t), y,
                                n_free if y is None else n_labelled, xs[i]))
            steps.append(Step(j, i, int(t), t_prev, float(tr["guidance"]),
                              tuple(idx)))
    return steps, rows


def batch(rows: list[Row], idx: list[int], size: int):
    """(x, t, y) of ``rows[idx]``, padded to ``size`` rows with copies of
    the first, as the engine pads; y is None for unlabelled rows."""
    idx = list(idx) + [idx[0]] * (size - len(idx))
    x = jnp.concatenate([rows[k].x for k in idx])
    t = jnp.asarray([rows[k].t for k in idx], jnp.float32)
    y = (None if rows[idx[0]].y is None
         else jnp.asarray([rows[k].y for k in idx], jnp.int32))
    return x, t, y


def site_sample(cfg: dict, seed: int, rows: list[Row]) -> set[int]:
    """The rows compared site by site: up to ``max_batch`` of each segment
    and labelling, drawn from the seed."""
    rng = np.random.default_rng(ref.sub_seed(seed, "site-check"))
    groups: dict[tuple[int, bool], list[int]] = {}
    for k, r in enumerate(rows):
        groups.setdefault((r.seg, r.y is not None), []).append(k)
    out: set[int] = set()
    for _, idx in sorted(groups.items()):
        out.update(rng.choice(idx, size=min(cfg["max_batch"], len(idx)),
                              replace=False).tolist())
    return out


# ---------------------------------------------------------------------------
# the reference, jitted
# ---------------------------------------------------------------------------


def _freeze(obj):
    if isinstance(obj, dict):
        return ("__dict__",) + tuple((k, _freeze(v)) for k, v in sorted(obj.items()))
    if isinstance(obj, list):
        return ("__list__",) + tuple(_freeze(v) for v in obj)
    return obj


def _thaw(obj):
    if isinstance(obj, tuple) and obj and obj[0] == "__dict__":
        return {k: _thaw(v) for k, v in obj[1:]}
    if isinstance(obj, tuple) and obj and obj[0] == "__list__":
        return [_thaw(v) for v in obj[1:]]
    return obj


def _row_norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim))))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _recorded(w, x, t, y, cfg_key, precision):
    """eps and every site's input."""
    seen = {}

    def tap(site, h):
        seen[site] = h
        return h

    eps = ref.forward(w, x, t, y, _thaw(cfg_key), precision, tap=tap)
    return eps, seen


@functools.partial(jax.jit, static_argnums=(5,))
def _forced(w, x, t, y, served, cfg_key):
    """Each site's gap per row, and the eps's, from each site's served
    input: at each element the smaller of two readings, one with every
    activation snapped to its nearest grid value, one with those at a
    midpoint snapped to the other."""
    cfg = _thaw(cfg_key)
    diffs = []
    for mode in ("nearest", "other"):
        d = {}

        def tap(site, h):
            d[site] = h - served[site]
            return served[site]

        eps = ref.forward(w, x, t, y, cfg, "f32", tap=tap, mode=mode)
        d["eps"] = eps - served["eps"]
        diffs.append(d)
    return {site: _row_norm(jnp.minimum(jnp.abs(diffs[0][site]),
                                        jnp.abs(diffs[1][site])))
            / jnp.maximum(_row_norm(served[site]), 1e-30)
            for site in diffs[0]}


@jax.jit
def _settle_ties(w, ties, served):
    """The reference's weights, with the bank's value where a weight lay at
    a midpoint; and the largest gap elsewhere, as a share of the largest
    weight of the site."""
    out, gap = dict(w), jnp.float32(0.0)
    for site, tie in ties.items():
        s = served[site].reshape(w[site].shape).astype(jnp.float32)
        out[site] = jnp.where(tie, s, w[site])
        gap = jnp.maximum(gap, jnp.max(jnp.where(tie, 0.0, jnp.abs(s - w[site])))
                          / jnp.maximum(jnp.max(jnp.abs(w[site])), 1e-30))
    return out, gap


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def _host(a) -> np.ndarray:
    return np.asarray(a, np.float64)


def _combined(step: Step, eps: list) -> np.ndarray:
    e = [_host(eps[r]) for r in step.rows]
    return e[0] if len(e) == 1 else e[0] + step.guidance * (e[1] - e[0])


def _step_gaps(cfg, steps, rows, trajectories, eps, nxt) -> np.ndarray:
    ab = ref.alpha_bars(cfg)
    gaps = np.zeros(len(steps))
    for k, s in enumerate(steps):
        e = _combined(s, eps)
        x = _host(rows[s.rows[0]].x)
        want = ref.ddim_step(ab, x, s.t, s.t_prev, e)
        got = _host(nxt[k] if nxt is not None
                    else trajectories[s.traj]["xs"][s.i + 1])
        # a float32 update is exact to a few units in the last place of
        # its operands and its result; what lies within that is not a gap
        slack = ROUNDING * (np.abs(want) + np.abs(x))
        off = np.maximum(np.abs(got - want) - slack, 0.0)
        coef = abs(ref.eps_coefficient(ab, s.t, s.t_prev))
        gaps[k] = np.linalg.norm(off) / coef / max(np.linalg.norm(e), 1e-30)
    return np.where(np.isfinite(gaps), gaps, np.inf)


def compare(cfg: dict, seed: int, trajectories: list[dict],
            outputs: dict) -> dict:
    """The numbers, and what each was read from.

    ``outputs`` is what the program (or the control) computed: ``eps``,
    one (1, H, W, C) per forward row of ``layout``; ``seen`` and
    ``probe_eps``, for each row of ``site_sample``, its site inputs by
    site name and its eps from the probe, each with a leading axis of 1;
    ``weights``, for each segment of those rows, the weights the bank
    serves by site (``"<site>/w"``); and, for the control only, ``next``,
    the x_{i+1} it served for each step."""
    steps, rows = layout(cfg, trajectories)
    eps = outputs["eps"]
    step_gaps = _step_gaps(cfg, steps, rows, trajectories, eps,
                           outputs.get("next"))

    params, hubs = ref.make_weights(seed, cfg)
    sig = ref.signatures(seed, cfg)
    bounds = ref.segment_bounds(cfg)
    cfg_key = _freeze(cfg)
    size = cfg["max_batch"]
    site_worst: dict[str, float] = {}
    weight_gap, n_ties = 0.0, 0
    probe_gap = max((float(np.linalg.norm(_host(e) - _host(eps[k]))
                           / max(np.linalg.norm(_host(eps[k])), 1e-30))
                     for k, e in outputs["probe_eps"].items()), default=0.0)
    for seg in sorted({rows[k].seg for k in outputs["seen"]}):
        w, ties = ref.served_weights(params, hubs, sig[bounds[seg][0]], cfg)
        flat_w, gap = _settle_ties(ref.flat(w), ties, outputs["weights"][seg])
        weight_gap = max(weight_gap, float(gap))
        n_ties += int(sum(jnp.sum(t) for t in ties.values()))
        w = ref.nest(flat_w)
        for labelled in (False, True):
            idx = sorted(k for k in outputs["seen"] if rows[k].seg == seg
                         and (rows[k].y is not None) == labelled)
            for lo in range(0, len(idx), size):
                part = idx[lo:lo + size]
                pad = part + [part[0]] * (size - len(part))
                served = {site: jnp.concatenate([outputs["seen"][k][site]
                                                 for k in pad])
                          for site in outputs["seen"][part[0]]}
                served["eps"] = jnp.concatenate([outputs["probe_eps"][k]
                                                 for k in pad])
                gaps = _forced(w, *batch(rows, part, size), served, cfg_key)
                for site, g in gaps.items():
                    g = np.asarray(g[:len(part)], np.float64)
                    g = np.where(np.isfinite(g), g, np.inf)
                    site_worst[site] = max(site_worst.get(site, 0.0),
                                           float(g.max()))
        del w, flat_w
    worst = sorted(site_worst.items(), key=lambda kv: -kv[1])
    return {"step_gap_max": float(step_gaps.max(initial=0.0)),
            "site_gap_max": worst[0][1] if worst else 0.0,
            "steps": len(steps),
            "rows_probed": len(outputs["seen"]),
            "step_gap_median": (float(np.median(step_gaps))
                                if len(steps) else 0.0),
            "worst_sites": worst[:3],
            "weight_gap": weight_gap,
            "weights_at_midpoint": n_ties,
            "probe_gap": probe_gap}


def control_outputs(cfg: dict, seed: int, trajectories: list[dict]) -> dict:
    """The control's ``outputs`` for ``compare``: the bfloat16 reference in
    the program's place, on the served states."""
    steps, rows = layout(cfg, trajectories)
    params, hubs = ref.make_weights(seed, cfg)
    sig = ref.signatures(seed, cfg)
    bounds = ref.segment_bounds(cfg)
    cfg_key = _freeze(cfg)
    block = cfg["max_batch"]
    ab = ref.alpha_bars(cfg)
    sample = site_sample(cfg, seed, rows)
    eps, seen, probe_eps, weights = [None] * len(rows), {}, {}, {}
    for seg in sorted({r.seg for r in rows}):
        w, _ = ref.served_weights(params, hubs, sig[bounds[seg][0]], cfg,
                                  "bf16")
        if any(rows[k].seg == seg for k in sample):
            weights[seg] = ref.flat(w)
        for labelled in (False, True):
            idx = [k for k, r in enumerate(rows)
                   if r.seg == seg and (r.y is not None) == labelled]
            for lo in range(0, len(idx), block):
                part = idx[lo:lo + block]
                out, rec = _recorded(w, *batch(rows, part, block), cfg_key,
                                     "bf16")
                for p, k in enumerate(part):
                    eps[k] = out[p:p + 1]
                    if k in sample:
                        probe_eps[k] = eps[k]
                        seen[k] = {site: h[p:p + 1] for site, h in rec.items()}
        del w
    # the state kept at the control's precision
    nxt = [ref.ddim_step(ab, _host(rows[s.rows[0]].x), s.t, s.t_prev,
                         _combined(s, eps)).astype(jnp.bfloat16)
           for s in steps]
    return {"eps": eps, "seen": seen, "probe_eps": probe_eps,
            "weights": weights, "next": nxt}
