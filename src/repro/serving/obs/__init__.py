"""Unified observability layer for the W4A4 serving stack.

One ``Observability`` bundle carries the three concerns every component
hangs telemetry off:

  * ``tracer`` — structured spans (``obs.tracer``): request lifecycle
    (submit -> admit -> per-eval -> complete/expire, as Chrome async
    events keyed by rid), engine ticks with scheduler decision
    annotations, weight-bank build/prefetch spans (including from the
    background prefetch worker thread), and per-dispatch kernel-route
    marks. Exports Chrome trace-event JSON (Perfetto-loadable) or JSONL.
    The diffusion engine's tick is split into phase spans, each a direct
    child of ``tick``: ``admit`` (admission and expiry), ``schedule``
    (grouping, selection, the tick's eval items), ``bank_fetch``,
    ``forward``, ``advance`` (guidance combination, sampler step,
    retirement) and ``prefetch``. Inside ``forward``, per partition:
    ``batch`` (rows concatenated, ``t``/``y`` arrays; in ``_forward``,
    padding to the bucket), ``dispatch`` (the call into the compiled
    forward, arg ``compiled``) and ``unbatch`` (``eps[:n]``; each row's
    slice).
  * ``metrics`` — the counter/gauge/histogram registry
    (``obs.metrics``): the single machine-readable home for the numbers
    previously scattered across ``engine.stats()``, ``bank.describe()``,
    scheduler attributes and launcher print lines. ``sample(engine)``
    emits the per-tick Perfetto counter tracks (``queue``, ``bank``);
    ``finalize`` copies the engine/bank/scheduler counters into gauges
    once, at run end, with the run-end summary.
  * ``kernel_profiler`` — per-route dispatch counts/timings installed
    into ``kernels/ops`` (see ``kernel_profile``).

Two bridges to JAX:

  * **Compile listener** — bound to an engine on its own wall clock, an
    enabled bundle registers one ``jax.monitoring`` duration listener:
    each trace, lowering, backend compile or persistent-cache read
    becomes a ``compile`` span (``cat="jit"``, args ``fun_name`` and
    ``stage``) on the compiling thread's track, ending when the event
    fires, and increments ``jit_compiles_total{fun_name,stage}``. A
    ``compile_listener`` instant marks where listening began.
    ``finalize`` or ``close`` unregisters it. Under a ``VirtualClock``
    or a simulated clock nothing is registered, so replays trace
    identically.
  * **Annotation bridge** — every duration span is also a
    ``jax.profiler.TraceAnnotation`` (see ``tracer``), so a profile puts
    the program's spans beside the device's ops on one clock.

Contracts:

  * **Determinism** — the tracer's clock is the *engine's* clock
    (``bind_engine``), never a wall clock of its own; under a
    ``VirtualClock`` replay the whole trace is deterministic and the
    golden outcome digest is unchanged whether obs is on or off (the
    layer only reads state; pinned by tests/test_obs.py).
  * **Near-zero disabled overhead** — ``NULL_OBS`` (the default
    everywhere) has ``enabled=False``; every instrumentation point in
    engine/scheduler/bank guards with that single branch before building
    any args, and the kernels hook is one module-global ``None`` check.
    It registers no listener and enters no annotation.
  * **Thread safety** — see ``tracer``/``metrics`` module docs; bank
    spans are emitted from the prefetch worker under churn without
    corrupting the buffer (pinned by the obs thread-safety test).
"""
from __future__ import annotations

import weakref

import jax.monitoring

from repro.serving.obs.kernel_profile import KernelProfiler
from repro.serving.obs.metrics import (Counter, Gauge, Histogram,
                                       MetricsRegistry)
from repro.serving.obs.tracer import NULL_SPAN, NullTracer, Span, SpanTracer

# jax.monitoring duration events -> the ``stage`` of a ``compile`` span
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir_module",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}


class _CompileListener:
    """Turns JAX's compile events into ``compile`` spans and counts. Holds
    its bundle weakly: a bundle dropped without ``close`` leaves a
    listener that does nothing, not one that keeps the bundle alive."""

    def __init__(self, obs: "Observability"):
        self._obs = weakref.ref(obs)

    def __call__(self, event: str, duration: float, **kw) -> None:
        stage = COMPILE_EVENTS.get(event)
        obs = self._obs()
        if stage is None or obs is None:
            return
        fun = str(kw.get("fun_name", ""))
        obs.tracer.record("compile", duration, cat="jit",
                          args={"fun_name": fun, "stage": stage})
        obs.metrics.counter(
            "jit_compiles_total",
            help="JAX compile events (trace, lowering, backend compile, "
                 "persistent-cache read) by function and stage",
            fun_name=fun, stage=stage).inc()


class Observability:
    def __init__(self, enabled: bool = True, *, clock=None,
                 max_events: int = 500_000, lock_factory=None):
        # lock_factory propagates to every obs-owned lock (tracer buffer,
        # registry + instruments, kernel profiler) — the seam
        # tools/analysis/lockcheck.py uses to install order-tracking
        # locks for the lock-discipline tests.
        self.enabled = enabled
        self.tracer = (SpanTracer(clock=clock, max_events=max_events,
                                  lock_factory=lock_factory)
                       if enabled else NullTracer())
        self.metrics = MetricsRegistry(lock_factory=lock_factory)
        self.kernel_profiler = (KernelProfiler(self,
                                               lock_factory=lock_factory)
                                if enabled else None)
        self._compile_listener = None

    # -- wiring --------------------------------------------------------------

    def bind_engine(self, engine) -> "Observability":
        """Point the tracer at the engine's clock (virtual, simulated, or
        wall — whatever the engine runs on, timestamps follow it). On an
        engine's own wall clock (``engine.wall_clock``), also listen for
        JAX's compiles, once per bundle."""
        self.tracer.set_clock(engine.now)
        if (self.enabled and getattr(engine, "wall_clock", False)
                and self._compile_listener is None):
            self._compile_listener = _CompileListener(self)
            jax.monitoring.register_event_duration_secs_listener(
                self._compile_listener)
            self.tracer.instant("compile_listener", cat="jit",
                                args={"events": sorted(COMPILE_EVENTS)})
        return self

    def close(self) -> None:
        """Stop listening for compiles; what was recorded stays readable."""
        if self._compile_listener is not None:
            jax.monitoring.unregister_event_duration_listener(
                self._compile_listener)
            self._compile_listener = None

    def install_kernels(self) -> "Observability":
        if self.kernel_profiler is not None:
            self.kernel_profiler.install()
        return self

    def uninstall_kernels(self) -> None:
        if self.kernel_profiler is not None:
            self.kernel_profiler.uninstall()

    # -- per-tick counter tracks / run-end registry sync ----------------------

    @staticmethod
    def _engine_labels(engine) -> dict:
        lab = {}
        if getattr(engine, "model", None):
            lab["model"] = engine.model
        if getattr(engine, "replica", None):
            lab["replica"] = engine.replica
        return lab

    def sample(self, engine) -> None:
        """Per-tick Perfetto counter-track samples of the queue and the
        bank. Reads plain attributes only (never ``engine.stats()``,
        which sorts latency lists)."""
        if not self.enabled:
            return
        b = engine.batcher
        bank = engine.bank
        tr = self.tracer
        tr.counter("queue", {"pending": len(b.pending),
                             "inflight": len(b.inflight)})
        tr.counter("bank", {"hits": bank.hits, "misses": bank.misses,
                            "builds": bank.builds})

    def set_gauges(self, engine) -> None:
        """Copy the engine/bank/scheduler counters into registry gauges
        (``finalize`` does, once). Sources keep their own lock
        disciplines: plain attribute reads."""
        if not self.enabled:
            return
        m = self.metrics
        b = engine.batcher
        bank = engine.bank
        # engines hosted behind the gateway carry a model identity, fleet
        # replicas a replica identity: their gauges become labeled series
        # so two engines never clobber one family; a standalone engine
        # (model=None, replica=None) keeps the unlabeled names
        # byte-identical to the pre-gateway exposition
        lab = self._engine_labels(engine)
        m.set("engine_ticks", engine.tick_count, **lab)
        m.set("engine_forwards", engine.n_forwards, **lab)
        m.set("engine_finished", engine.n_finished, **lab)
        m.set("engine_expired", engine.n_expired, **lab)
        m.set("engine_pending", len(b.pending), **lab)
        m.set("engine_inflight", len(b.inflight), **lab)
        m.set("engine_padded_samples", engine.n_padded_samples, **lab)
        m.set("engine_compiled_forwards", len(engine._jit), **lab)
        m.set("sched_preemptions", b.preemptions, **lab)
        m.set("sched_deadline_saves", b.deadline_saves, **lab)
        m.set("sched_cost_sample_s", b.cost.sample_s, **lab)
        m.set("sched_cost_switch_s", b.cost.switch_s, **lab)
        m.set("bank_hits", bank.hits, **lab)
        m.set("bank_misses", bank.misses, **lab)
        m.set("bank_builds", bank.builds, **lab)
        m.set("bank_build_joins", bank.build_joins, **lab)
        m.set("bank_build_failures", bank.build_failures, **lab)
        m.set("bank_prefetches", bank.prefetches, **lab)
        m.set("bank_prefetch_hits", bank.prefetch_hits, **lab)
        m.set("bank_evictions", bank.evictions, **lab)

    def finalize(self, engine, collector=None) -> None:
        """Run-end sync: the counters as gauges, the full
        ``engine.stats()`` plus the traffic collector's summary land in
        the registry, so ``to_text()`` / ``snapshot()`` expose every
        number the launcher prints. Stops listening for compiles."""
        if not self.enabled:
            return
        self.close()
        self.sample(engine)
        self.set_gauges(engine)
        m = self.metrics
        lab = self._engine_labels(engine)
        for k, v in engine.stats().items():
            if isinstance(v, (int, float, bool)):
                m.set(f"engine_{k}", float(v), **lab)
        if collector is not None:
            for k, v in collector.summary().items():
                if isinstance(v, (int, float, bool)):
                    m.set(f"traffic_{k}", float(v), **lab)
        if self.kernel_profiler is not None:
            m.set("kernel_routes", len(self.kernel_profiler.route_counts()))
        m.set("trace_events", len(self.tracer.events()))
        m.set("trace_events_dropped", self.tracer.dropped)


NULL_OBS = Observability(enabled=False)

__all__ = ["Observability", "NULL_OBS", "NULL_SPAN", "SpanTracer",
           "NullTracer", "Span", "MetricsRegistry", "Counter", "Gauge",
           "Histogram", "KernelProfiler"]
