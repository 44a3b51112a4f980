"""Diffusion serving engine: continuous-batched denoising on packed W4A4.

One engine *tick*:

  1. admit arrived requests into free in-flight slots (priority desc,
     then FIFO; due requests past their deadline are expired instead —
     see ``scheduler.ContinuousBatcher.admit``),
  2. group in-flight requests by the weight-bank segment of the timestep
     each sampler needs next, pick one group (scheduler policy),
  3. fetch that segment's pre-merged, pre-packed weights from the bank
     (LRU — the common case is a hit, since consecutive sampler steps
     stay inside a routing segment),
  4. run ONE batched model forward per class-conditioning partition
     (per-sample ``t``; CFG-guided requests contribute a cond + uncond
     pair and are recombined as ``eps_u + s * (eps_c - eps_u)``) — batches
     pad to power-of-two buckets (outputs masked by slicing) so the jit
     cache stays bounded under churny in-flight counts,
  5. advance each request's sampler state; retire finished requests.

The forward runs under a *serve-mode* ``QuantContext`` — activation
quantization happens inside the fused W4A4 kernel for packed dense sites
and there is no fake-quant anywhere on this path; weights are real packed
uint8 nibbles end-to-end (``kernels/ops`` dispatch).

The engine exposes callback hooks for the traffic subsystem
(``serving/traffic``): ``on_submit`` (trace capture), ``on_complete`` /
``on_expire`` (closed-loop generators, SLO metrics), ``on_tick_end``
(queue-depth / cache time series). After each tick it prefetches the
weight-bank segments that in-flight samplers will need next, so a
segment boundary crossing finds its merged+packed weights already built
(``stats()['prefetch_hits']``). Under a wall clock the prefetch is
*asynchronous* — the bank's background thread merges/packs the next
segment while the current segment's forwards run; under a
``VirtualClock`` it stays synchronous so replay digests are
deterministic.

``policy="slo"`` switches group selection from largest-group-wins to the
slack-aware scheduler (EDF pressure weighted against segment-switch
cost, with group-splitting preemption — see ``scheduler``); the engine
feeds the scheduler's ``CostModel`` with observed forward and
segment-build durations measured on the engine clock.

Passing an enabled ``serving.obs.Observability`` turns on structured
telemetry: request lifecycle events and the tick's phase spans on the
engine clock (``tick`` > ``admit``, ``schedule``, ``bank_fetch``,
``forward`` > per partition ``batch``, ``dispatch``, ``unbatch``, then
``advance`` and ``prefetch``; see ``serving.obs``), per-tick counter
tracks, JAX's compiles as ``compile`` spans on a wall clock, and
propagation of the obs bundle into the scheduler and weight bank (their
decision/build spans land in the same trace). With the default
``NULL_OBS`` every instrumentation point is one ``obs.enabled`` branch —
the serving path is unchanged.

Two scheduler counters are always on: ``inflight_request_ticks`` sums
the requests in flight at each selection, ``served_request_ticks`` those
the selection served; their difference is request-ticks spent waiting in
flight for another segment's or a later bucket's turn.
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro.diffusion.samplers import (sampler_advance, sampler_init,
                                      sampler_needed_t)
from repro.diffusion.schedule import NoiseSchedule
from repro.nn.unet import UNetConfig, unet_apply
from repro.quant.calibrate import QuantContext
from repro.serving.obs import NULL_OBS, NULL_SPAN, Observability
from repro.serving.scheduler import (ContinuousBatcher, GenRequest,
                                     RequestState, bucket_of)
from repro.serving.traffic.metrics import percentile
from repro.serving.weight_bank import WeightBank

# role of one eval item in its request: plain, or half of a CFG pair
_PLAIN, _UNCOND, _COND = 0, 1, 2


class VirtualClock:
    """Deterministic replay clock: time only moves when the idle driver
    advances it to the next arrival, never during compute. Trace replay
    under a virtual clock admits/batches identically across runs and
    machines (the CI determinism check), at the cost of wall-latency
    metrics — latencies read ~0 and deadlines never expire, so use the
    default wall clock when measuring SLOs."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def now(self) -> float:
        return self.t

    def advance_to(self, t: float) -> None:
        self.t = max(self.t, t)


class DiffusionServingEngine:
    """Owns the denoising loop for many concurrent generation requests."""

    def __init__(self, cfg: UNetConfig, sched: NoiseSchedule,
                 bank: WeightBank, *,
                 act_qps: dict | None = None,
                 apply_fn: Callable | None = None,
                 max_batch: int = 8, starvation_ticks: int = 4,
                 policy: str = "fifo",
                 now_fn: Callable[[], float] | None = None,
                 clock: VirtualClock | None = None,
                 max_idle_sleep: float = 0.25,
                 prefetch: bool = True,
                 async_prefetch: bool = True,
                 obs: Observability | None = None,
                 model: str | None = None):
        # model: identity label when hosted behind the multi-model gateway
        # (obs gauges/spans carry it; None keeps single-model output
        # byte-identical to the pre-gateway format)
        self.model = model
        # replica: identity label when hosted as a fleet replica (the
        # FleetRouter sets it after construction); obs gauges gain a
        # {replica=...} label and spans land on a per-replica track
        self.replica: str | None = None
        self.cfg = cfg
        self.sched = sched
        self.bank = bank
        self.ctx = QuantContext("serve", act_qps=act_qps or {})
        self._apply = apply_fn or (
            lambda params, x, tb, y, ctx: unet_apply(params, x, tb, cfg,
                                                     y=y, ctx=ctx))
        self.batcher = ContinuousBatcher(max_batch, starvation_ticks,
                                         policy=policy)
        self.batcher.segment_warm = bank.is_cached
        self.batcher.segment_building = bank.is_building
        if clock is not None:
            self._now = clock.now
            self._advance = clock.advance_to
        else:
            t0 = time.monotonic()
            self._now = now_fn or (lambda: time.monotonic() - t0)
            self._advance = None
        # on its own monotonic clock (neither virtual nor simulated): the
        # obs bundle may then time JAX's compiles on it
        self.wall_clock = clock is None and now_fn is None
        self.max_idle_sleep = max_idle_sleep
        self.prefetch_enabled = prefetch
        # background builds only make sense when real time passes during
        # compute; a VirtualClock replay must build synchronously so the
        # golden-trace digest stays deterministic.
        self.async_prefetch = async_prefetch and self._advance is None
        # observability: the tracer follows the *engine's* clock (so a
        # VirtualClock replay traces deterministically) and propagates to
        # the scheduler and bank so their spans land in the same buffer.
        self.obs = obs or NULL_OBS
        if self.obs.enabled:
            self.obs.bind_engine(self)
            self.batcher.obs = self.obs
            if self.bank.obs is NULL_OBS:
                self.bank.obs = self.obs
            self._h_forward = self.obs.metrics.histogram(
                "engine_forward_seconds",
                help="engine-clock host time of the tick's batched "
                     "forwards: batching, dispatch and unbatching, not "
                     "the device's forward time, which async dispatch "
                     "leaves out (the same observations the scheduler "
                     "cost EWMA consumes)")
            self._h_fetch = self.obs.metrics.histogram(
                "bank_fetch_seconds",
                help="engine-clock stalls fetching the tick's segment")
        self._jit: dict[tuple, Callable] = {}
        self._last_padded_rows = 0
        self._next_rid = 0
        self.tick_count = 0
        self.n_forwards = 0
        self.n_samples_batched = 0
        self.n_padded_samples = 0
        self.n_idle_sleeps = 0
        self.n_finished = 0
        self.n_expired = 0
        self.inflight_request_ticks = 0
        self.served_request_ticks = 0
        self._latencies: list[float] = []    # scalars only; never evicted
        self.results: dict[int, RequestState] = {}
        # traffic-subsystem hooks; each receives the RequestState (or the
        # engine itself for on_tick_end)
        self.on_submit: list[Callable] = []
        self.on_complete: list[Callable] = []
        self.on_expire: list[Callable] = []
        self.on_tick_end: list[Callable] = []
        # (engine, padded_rows) once per tick's batched forwards — the
        # seam simulated service clocks charge compute through
        self.on_forward: list[Callable] = []

    def now(self) -> float:
        return self._now()

    # -- request lifecycle -------------------------------------------------

    def submit(self, *, steps: int = 20, eta: float = 0.0, seed: int = 0,
               sampler: str = "ddim", y: int | None = None,
               guidance_scale: float = 0.0, arrival: float = 0.0,
               deadline: float | None = None, priority: int = 0,
               user: int | None = None, parent: int | None = None,
               think_s: float | None = None) -> int:
        if guidance_scale > 0 and (y is None or not self.cfg.num_classes):
            raise ValueError("guidance needs a class label y and a "
                             "class-conditional model")
        rid = self._next_rid
        self._next_rid += 1
        req = GenRequest(rid, steps, eta, seed, sampler, y, guidance_scale,
                         arrival, deadline, priority, user, parent, think_s)
        shape = (1, self.cfg.image_size, self.cfg.image_size, self.cfg.in_ch)
        state = sampler_init(sampler, self.sched, shape,
                             jax.random.PRNGKey(seed), steps=steps, eta=eta)
        rs = RequestState(req, state, submitted_at=self._now())
        self.batcher.submit(rs)
        if self.obs.enabled:
            self.obs.tracer.set_track(self.replica or self.model)
            self.obs.tracer.async_begin(
                "request", rid, cat="request",
                args={"steps": steps, "sampler": sampler,
                      "arrival": arrival, "deadline": deadline,
                      "priority": priority,
                      "cfg": guidance_scale > 0})
        for cb in self.on_submit:
            cb(rs)
        return rid

    # -- one engine tick ---------------------------------------------------

    def tick(self) -> list[RequestState]:
        obs = self.obs
        if obs.enabled:
            obs.tracer.set_track(self.replica or self.model)
            with obs.tracer.span("tick", cat="engine",
                                 args={"tick": self.tick_count}) as tick_sp:
                finished = self._tick(tick_sp)
            obs.sample(self)
        else:
            finished = self._tick(NULL_SPAN)
        for cb in self.on_tick_end:
            cb(self)
        return finished

    def _tick(self, tick_sp) -> list[RequestState]:
        """The tick's phases, each a child span of ``tick_sp`` when obs is
        on; a raise closes every open span on its way out."""
        obs = self.obs
        on = obs.enabled
        tr = obs.tracer
        with tr.span("admit") if on else NULL_SPAN:
            now = self._now()
            admitted, expired = self.batcher.admit(now, self.tick_count)
            if on:
                for rs in admitted:
                    tr.async_instant("admit", rs.req.rid, cat="request")
            for rs in expired:
                rs.finished_at = now
                self.results[rs.req.rid] = rs
                self.n_expired += 1
                if on:
                    tr.async_end("request", rs.req.rid, cat="request",
                                 args={"outcome": "expired"})
                for cb in self.on_expire:
                    cb(rs)
        if not self.batcher.inflight:
            tick_sp.set("idle", True)
            return []

        with tr.span("schedule") if on else NULL_SPAN:
            groups = self.batcher.groups(
                lambda rs: self.bank.segment_of(sampler_needed_t(rs.state)))
            seg, members = self.batcher.select(groups, self.tick_count,
                                               now=now)
            self.batcher.current_seg = seg
            self.inflight_request_ticks += len(self.batcher.inflight)
            self.served_request_ticks += len(members)
            # eval items: (rs, role, t, x (1,H,W,C), y)
            items = []
            for rs in members:
                t = sampler_needed_t(rs.state)
                x = rs.state.eval_x
                if rs.req.guidance_scale > 0:
                    items.append((rs, _UNCOND, t, x, None))
                    items.append((rs, _COND, t, x, rs.req.y))
                else:
                    items.append((rs, _PLAIN, t, x, rs.req.y))
        if on:
            tick_sp.span.args.update(
                {"seg": seg, "members": [rs.req.rid for rs in members],
                 "n_groups": len(groups), "policy": self.batcher.policy})

        with (tr.span("bank_fetch", cat="bank", args={"seg": seg})
              if on else NULL_SPAN) as fetch_sp:
            t_fetch = self._now()
            misses_before = self.bank.misses
            joins_before = self.bank.build_joins
            params = self.bank.params_for_segment(seg)
            if self.bank.misses > misses_before:
                # cold fetch: the observed stall is the segment-switch cost
                self.batcher.cost.observe_switch(self._now() - t_fetch)
            elif self.bank.build_joins > joins_before:
                # joined an async build mid-way: with prefetch on this is
                # the common cold path (prefetch registers the build
                # before the fetch, so `misses` never moves) — without it
                # the switch EWMA would stay pinned to the first cold
                # build forever. The stall is the remaining ~half of a
                # build on average.
                self.batcher.cost.observe_switch(2 * (self._now() - t_fetch))
            if on:
                fetch_sp.set("outcome", (
                    "miss" if self.bank.misses > misses_before
                    else "join" if self.bank.build_joins > joins_before
                    else "hit"))
                self._h_fetch.observe(self._now() - t_fetch)

        with (tr.span("forward", cat="engine", args={"items": len(items)})
              if on else NULL_SPAN) as fwd_sp:
            t_compute = self._now()
            n_jit_before = len(self._jit)
            eps_by_item = self._run_partitions(params, items)
            compiled = len(self._jit) > n_jit_before
            dt = self._now() - t_compute
            if not compiled:
                # skip ticks that traced+compiled a new (bucket, has_y)
                # forward: seeding the EWMA with compile time would poison
                # slack estimates for many subsequent ticks
                self.batcher.cost.observe_eval(dt, self._last_padded_rows)
            if on:
                fwd_sp.set("padded_rows", self._last_padded_rows)
                fwd_sp.set("compiled", compiled)
                # the same engine-clock observation the cost EWMA consumed
                if not compiled:
                    self._h_forward.observe(dt)

        with tr.span("advance") if on else NULL_SPAN:
            finished = []
            tick = self.tick_count
            for rs in members:
                parts = eps_by_item[id(rs)]
                if _PLAIN in parts:
                    eps = parts[_PLAIN]
                else:
                    s = rs.req.guidance_scale
                    eps = parts[_UNCOND] + s * (parts[_COND] - parts[_UNCOND])
                sampler_advance(rs.state, eps)
                rs.last_advance_tick = tick
                rs.n_evals += 1
                if on:
                    tr.async_instant("eval", rs.req.rid, cat="request",
                                     args={"n_evals": rs.n_evals})
                if rs.state.done:
                    rs.x0 = rs.state.x
                    rs.finished_at = self._now()
                    self.batcher.retire(rs)
                    self.results[rs.req.rid] = rs
                    self.n_finished += 1
                    self._latencies.append(rs.latency)
                    finished.append(rs)
                    if on:
                        tr.async_end("request", rs.req.rid, cat="request",
                                     args={"outcome": "complete",
                                           "n_evals": rs.n_evals,
                                           "latency_s": rs.latency})
                    for cb in self.on_complete:
                        cb(rs)
        self.tick_count += 1
        if self.prefetch_enabled:
            # Requests that just advanced may cross into a new routing
            # segment next step — build/pack it before it is asked for.
            # Async mode hands the build to the bank's background thread
            # so the next segment merges/packs while this segment's
            # forwards keep running; a later fetch joins the in-progress
            # build instead of rebuilding.
            with tr.span("prefetch") if on else NULL_SPAN:
                for s in {self.bank.segment_of(sampler_needed_t(rs.state))
                          for rs in members if not rs.state.done}:
                    self.bank.prefetch(s, block=not self.async_prefetch)
        tick_sp.set("finished", len(finished))
        return finished

    def _run_partitions(self, params, items) -> dict[int, dict]:
        """One batched forward per class-conditioning partition.

        ``unet_apply`` takes a single optional ``y`` array, so items with
        and without a label cannot share a forward; each partition still
        batches arbitrary timesteps (``t`` is per-sample).
        """
        on = self.obs.enabled
        tr = self.obs.tracer
        eps_by_item: dict[int, dict] = {}
        padded_rows = 0
        for has_y in (False, True):
            if not any((it[4] is not None) == has_y for it in items):
                continue
            with tr.span("batch") if on else NULL_SPAN:
                part = [it for it in items if (it[4] is not None) == has_y]
                x = jnp.concatenate([it[3] for it in part], axis=0)
                tb = jnp.asarray([it[2] for it in part], jnp.float32)
                y = (jnp.asarray([it[4] for it in part], jnp.int32)
                     if has_y else None)
            eps = self._forward(params, x, tb, y)
            with tr.span("unbatch") if on else NULL_SPAN:
                self.n_forwards += 1
                self.n_samples_batched += len(part)
                padded_rows += self._bucket(len(part))
                for j, (rs, role, *_rest) in enumerate(part):
                    eps_by_item.setdefault(id(rs), {})[role] = eps[j:j + 1]
        self._last_padded_rows = padded_rows
        for cb in self.on_forward:
            cb(self, padded_rows)
        return eps_by_item

    # Partition batches pad to power-of-two buckets so churny in-flight
    # counts reuse a handful of compiled forwards instead of one jit entry
    # per distinct batch size; the scheduler's cost model shares the same
    # bucket function so slack estimates price the padding.
    _bucket = staticmethod(bucket_of)

    def _forward(self, params, x, tb, y):
        on = self.obs.enabled
        tr = self.obs.tracer
        n = x.shape[0]
        b = self._bucket(n)
        if b != n:
            with tr.span("batch") if on else NULL_SPAN:
                # Pad with copies of row 0 (always finite through norms)
                # and mask by slicing the padded outputs away below.
                pad = b - n
                x = jnp.concatenate([x, jnp.repeat(x[:1], pad, axis=0)],
                                    axis=0)
                tb = jnp.concatenate([tb, jnp.repeat(tb[:1], pad)], axis=0)
                if y is not None:
                    y = jnp.concatenate([y, jnp.repeat(y[:1], pad)], axis=0)
                self.n_padded_samples += pad
        key = (b, y is not None)
        compiled = key not in self._jit
        with (tr.span("dispatch", args={"compiled": compiled})
              if on else NULL_SPAN):
            if compiled:
                if y is None:
                    self._jit[key] = jax.jit(
                        lambda p, x, tb: self._apply(p, x, tb, None,
                                                     self.ctx))
                else:
                    self._jit[key] = jax.jit(
                        lambda p, x, tb, y: self._apply(p, x, tb, y,
                                                        self.ctx))
            fn = self._jit[key]
            eps = fn(params, x, tb) if y is None else fn(params, x, tb, y)
        with tr.span("unbatch") if on else NULL_SPAN:
            return eps[:n]

    def pop_result(self, rid: int) -> RequestState:
        """Hand a finished request to its caller and release the engine's
        reference (a long-lived engine must not retain every generated
        latent; latency scalars stay for ``stats``)."""
        return self.results.pop(rid)

    # -- driver ------------------------------------------------------------

    def run(self, *, max_idle_sleep: float | None = None
            ) -> dict[int, RequestState]:
        """Tick until every submitted request has finished or expired.

        While idle (nothing in flight, next arrival in the future) the
        driver sleeps until that arrival in one shot — capped at
        ``max_idle_sleep`` (engine default unless overridden here) as a
        clock-skew guard — instead of spinning a millisecond poll loop.

        Under a ``VirtualClock`` the driver instead advances the clock to
        the next arrival whenever an in-flight slot is free — arrival
        gaps are treated as instantaneous relative to service, so replay
        batches greedily and deterministically. The trace's arrival
        *order* and priorities still apply, but deadlines can never
        expire (virtual time never passes a pending request's own
        arrival) — score SLOs under the wall clock.
        """
        cap = self.max_idle_sleep if max_idle_sleep is None else max_idle_sleep
        while self.batcher.pending or self.batcher.inflight:
            if (self._advance is not None and self.batcher.pending
                    and len(self.batcher.inflight) < self.batcher.max_batch):
                nxt = self.batcher.next_arrival()
                if nxt > self._now():
                    self._advance(nxt)
                    self.n_idle_sleeps += 1
            self.tick()
            if (self._advance is None and not self.batcher.inflight
                    and self.batcher.pending):
                wait = self.batcher.next_arrival() - self._now()
                # cap <= 0 means "never sleep" (simulated clocks spin
                # through ticks to advance time) — sleep(0) would busy-
                # spin while still counting as an idle sleep
                if wait > 0 and cap > 0:
                    time.sleep(min(wait, cap))
                    self.n_idle_sleeps += 1
        # settle outstanding background builds so post-run stats (builds
        # vs misses+prefetches) reconcile deterministically
        self.bank.drain()
        return self.results

    # -- metrics -----------------------------------------------------------

    def stats(self) -> dict:
        lat = sorted(self._latencies)
        buckets = sorted({k[0] for k in self._jit})
        d = {"requests": self.n_finished, "ticks": self.tick_count,
             "expired": self.n_expired,
             "policy": self.batcher.policy,
             "preemptions": self.batcher.preemptions,
             "deadline_saves": self.batcher.deadline_saves,
             "forwards": self.n_forwards,
             "mean_batch": (self.n_samples_batched / self.n_forwards
                            if self.n_forwards else 0.0),
             "inflight_request_ticks": self.inflight_request_ticks,
             "served_request_ticks": self.served_request_ticks,
             "compiled_forwards": len(self._jit),
             "buckets": buckets,
             "padded_samples": self.n_padded_samples,
             "idle_sleeps": self.n_idle_sleeps,
             "prefetch_hits": self.bank.prefetch_hits,
             "p50_s": percentile(lat, 50), "p95_s": percentile(lat, 95),
             "p99_s": percentile(lat, 99)}
        d.update({f"bank_{k}": v for k, v in self.bank.describe().items()})
        return d
