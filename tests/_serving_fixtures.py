"""Shared fixtures for the scheduler/serving test suites.

One place to build the toy weight banks and scheduler request states the
SLO-scheduling and invariant suites drive, so WeightBank/RequestState
constructor changes land in a single helper instead of drifting across
test files. All helpers are deterministic (fixed keys/seeds).
"""
import jax
import jax.numpy as jnp

from repro.common.tree import flatten_paths
from repro.core import talora
from repro.diffusion.samplers import sampler_init
from repro.diffusion.schedule import make_schedule
from repro.serving import (GenRequest, RequestState, WeightBank,
                           default_serving_plan)

T = 40
SCHED = make_schedule("linear", T)


def single_segment_bank():
    """Trivial bank: one segment, no TALoRA routing."""
    params = {"l0": {"w": jnp.ones((4, 4))}}
    plan = default_serving_plan(flatten_paths(params))
    return WeightBank(params, plan, {}, None, None, T)


def multi_segment_bank(max_cached=8, lock_factory=None):
    """Toy TALoRA bank whose untrained router fragments [0, T) into
    several routing segments (the suites assert >= 2). ``lock_factory``
    passes through to WeightBank — the lockcheck suites install
    order-tracking locks through it.

    Key 0 fragments [0, 40) into 11 segments under JAX's partitionable
    threefry stream (the default since JAX 0.5). Key 1 gave 12 under the
    old stream but only 7 under the new one, too coarse for the
    tight_deadlines fifo-vs-slo discriminator to separate the policies."""
    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {"l0": {"w": jax.random.normal(k1, (8, 8))},
              "l1": {"w": jax.random.normal(k2, (8, 6))}}
    weights = dict(flatten_paths(params))
    plan = default_serving_plan(weights)
    tcfg = talora.TALoRAConfig(hub_size=2, rank=2, t_emb_dim=16,
                               router_hidden=8)
    hubs = talora.init_lora_hub(k3, talora.lora_target_dims_from_weights(
        weights), tcfg)
    router = talora.init_router(k4, len(weights), tcfg)
    return WeightBank(params, plan, hubs, router, tcfg, T,
                      max_cached=max_cached, lock_factory=lock_factory)


def mk_inflight(b, rid, *, steps=1, deadline=None, last_tick=0,
                guidance_scale=0.0):
    """Append a ready-to-schedule RequestState to batcher ``b``."""
    st = sampler_init("ddim", SCHED, (1, 2, 2, 3), jax.random.PRNGKey(rid),
                      steps=steps)
    rs = RequestState(GenRequest(rid, steps=steps, deadline=deadline,
                                 guidance_scale=guidance_scale, y=0), st)
    rs.admitted_at = 0.0
    rs.last_advance_tick = last_tick
    b.inflight.append(rs)
    return rs
