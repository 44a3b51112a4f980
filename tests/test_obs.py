"""Observability layer: tracer invariants, registry, determinism, threads.

What this suite pins down, in order:

  * the metrics registry's instrument semantics (get-or-create by
    (name, labels), kind collisions rejected, exposition format),
  * span tracer invariants — nesting, deterministic clock-bound
    timestamps, ring-buffer overflow accounting, export round-trips,
  * the engine integration: a traced run emits the full span taxonomy
    (request lifecycle, ticks with scheduler decisions, bank builds,
    forwards) and — the core contract — the per-request outcomes are
    bit-identical with obs on and off (tracing reads, never perturbs),
  * thread safety: bank-build spans arriving from 4 churning threads
    never tear the buffer and reconcile with the bank's build counter,
  * kernel-route profiling: per-route counts reconcile with the ops
    dispatch rules the route-forcing tests in test_kernels pin,
  * MetricsCollector retention: capped buffers compact instead of drop —
    summary totals stay exact, and the scheduler/bank counters ride in,
  * the tick's phase spans: nesting, and children that cover each busy
    tick and each forward; the scheduler's served / in-flight counters,
  * JAX's compiles as ``compile`` spans (wall clock only), and the
    annotation bridge that puts every span on the profiler's host plane.
"""
import json
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._serving_fixtures import (SCHED, T,
                                     multi_segment_bank as
                                     _multi_segment_bank,
                                     single_segment_bank as
                                     _single_segment_bank)

from repro.configs.diffusion_presets import tiny_ddim
from repro.core.qmodule import pack_weight
from repro.kernels import ops
from repro.quant.fakequant import KIND_FP_SIGNED, QuantizerParams
from repro.serving import DiffusionServingEngine, VirtualClock, WeightBank
from repro.serving import engine as engine_mod
from repro.serving.obs import NULL_OBS, Observability, SpanTracer
from repro.serving.obs import tracer as tracer_mod
from repro.serving.obs.metrics import MetricsRegistry
from repro.serving.traffic import load_trace, submit_trace
from repro.serving.traffic.metrics import MetricsCollector, _Event
from repro.serving.traffic.scenarios import resolve_trace_path

GOLDEN = "tests/data/golden_trace.jsonl"


def _engine(obs=None, bank=None, **kw):
    return DiffusionServingEngine(
        tiny_ddim(4), SCHED, bank or _single_segment_bank(),
        apply_fn=lambda params, x, tb, y, ctx: 0.1 * x, obs=obs, **kw)


# ---------------------------------------------------------------------------
# Metrics registry.
# ---------------------------------------------------------------------------


def test_registry_instruments_and_labels():
    m = MetricsRegistry()
    c = m.counter("requests_total", help="n requests", route="a")
    c.inc()
    c.inc(2)
    assert m.counter("requests_total", route="a") is c      # get-or-create
    assert m.counter("requests_total", route="b") is not c  # new label set
    m.set("queue_depth", 7)
    h = m.histogram("lat_s")
    h.observe(0.5)
    h.observe(1.5)
    snap = m.snapshot()
    assert snap['requests_total{route="a"}'] == 3
    assert snap['requests_total{route="b"}'] == 0
    assert snap["queue_depth"] == 7
    assert snap["lat_s_count"] == 2
    assert snap["lat_s_sum"] == pytest.approx(2.0)
    assert snap["lat_s_mean"] == pytest.approx(1.0)


def test_registry_rejects_kind_collisions():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(ValueError):
        m.gauge("x")


def test_registry_text_exposition():
    m = MetricsRegistry()
    m.counter("calls_total", help="total calls", op="mm").inc(4)
    m.set("depth", 2)
    h = m.histogram("dur_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)                 # overflow bucket
    text = m.to_text()
    assert "# TYPE calls_total counter" in text
    assert '# HELP calls_total total calls' in text
    assert 'calls_total{op="mm"} 4' in text
    assert 'dur_s_bucket{le="0.1"} 1' in text
    assert 'dur_s_bucket{le="1.0"} 2' in text      # cumulative
    assert 'dur_s_bucket{le="+Inf"} 3' in text
    assert "dur_s_count 3" in text


# ---------------------------------------------------------------------------
# Span tracer.
# ---------------------------------------------------------------------------


def test_tracer_nesting_and_deterministic_clock():
    t = [0.0]
    tr = SpanTracer(clock=lambda: t[0])
    outer = tr.begin("tick", args={"n": 1})
    t[0] = 1.0
    with tr.span("forward", cat="engine") as sp:
        sp.set("rows", 4)
        t[0] = 3.0
    t[0] = 5.0
    tr.end(outer)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["forward", "tick"]  # inner ends first
    fwd, tick = evs
    assert tick["ts"] == 0.0 and tick["dur"] == 5e6         # us
    assert fwd["ts"] == 1e6 and fwd["dur"] == 2e6
    assert fwd["args"]["rows"] == 4
    # nested span lies inside its parent
    assert (tick["ts"] <= fwd["ts"]
            and fwd["ts"] + fwd["dur"] <= tick["ts"] + tick["dur"])


def test_tracer_end_tolerates_leaked_inner_span():
    tr = SpanTracer(clock=lambda: 0.0)
    outer = tr.begin("outer")
    tr.begin("leaked")              # never ended (error path)
    tr.end(outer)                   # must not corrupt later nesting
    nxt = tr.begin("next")
    tr.end(nxt)
    assert [e["name"] for e in tr.events()] == ["outer", "next"]


def test_tracer_ring_buffer_drops_oldest():
    tr = SpanTracer(clock=lambda: 0.0, max_events=3)
    for i in range(5):
        tr.instant(f"i{i}")
    assert tr.dropped == 2
    assert [e["name"] for e in tr.events()] == ["i2", "i3", "i4"]


def test_tracer_export_round_trips(tmp_path):
    tr = SpanTracer(clock=lambda: 1.0)
    tr.async_begin("request", 7, args={"steps": 3})
    tr.instant("admit", cat="sched")
    tr.counter("queue", {"pending": 2})
    tr.async_end("request", 7)
    chrome = tmp_path / "t.json"
    jsonl = tmp_path / "t.jsonl"
    n1 = tr.export(str(chrome))
    n2 = tr.export(str(jsonl))
    doc = json.loads(chrome.read_text())
    assert {e["ph"] for e in doc["traceEvents"]} == {"M", "b", "i", "C", "e"}
    lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    assert n1 == n2 == len(lines) == len(doc["traceEvents"])
    b = next(e for e in lines if e["ph"] == "b")
    assert b["id"] == "7" and b["args"]["steps"] == 3


def test_null_obs_is_inert():
    assert not NULL_OBS.enabled and not NULL_OBS.tracer.enabled
    assert NULL_OBS.tracer.begin("x") is None
    NULL_OBS.tracer.end(None)
    NULL_OBS.tracer.instant("x")
    NULL_OBS.tracer.async_begin("x", 1)
    assert NULL_OBS.tracer.events() == []
    assert NULL_OBS.kernel_profiler is None


# ---------------------------------------------------------------------------
# Engine integration: taxonomy + digest invariance.
# ---------------------------------------------------------------------------


def _replay_golden(obs):
    reqs, _ = load_trace(resolve_trace_path(GOLDEN))
    eng = _engine(obs=obs, bank=_multi_segment_bank(), max_batch=2,
                  clock=VirtualClock())
    submit_trace(eng, reqs)
    res = eng.run()
    return eng, {rid: (rs.n_evals, np.asarray(rs.x0).tobytes())
                 for rid, rs in res.items()}


def test_traced_golden_replay_has_full_taxonomy_and_identical_outcomes():
    obs = Observability()
    eng, traced_out = _replay_golden(obs)
    _, plain_out = _replay_golden(None)
    assert traced_out == plain_out        # tracing never perturbs outcomes

    evs = obs.tracer.events()
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    # request lifecycle: one async begin + end per request, eval instants
    begins = [e for e in by_name["request"] if e["ph"] == "b"]
    ends = [e for e in by_name["request"] if e["ph"] == "e"]
    assert len(begins) == len(ends) == len(traced_out)
    assert {e["id"] for e in begins} == {str(r) for r in traced_out}
    assert all(e["args"]["outcome"] == "complete" for e in ends)
    assert len(by_name["eval"]) == sum(n for n, _ in traced_out.values())
    # engine ticks carry the scheduler decision annotations
    ticks = by_name["tick"]
    busy = [e for e in ticks if not e["args"].get("idle")]
    assert busy and all("seg" in e["args"] and "members" in e["args"]
                        and e["args"]["policy"] == "fifo" for e in busy)
    assert eng.tick_count == len(ticks)
    # ticks on the engine thread never overlap, and each forward /
    # bank_fetch nests inside some tick
    spans = sorted((e for e in ticks), key=lambda e: e["ts"])
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    for name in ("forward", "bank_fetch"):
        for e in by_name[name]:
            assert any(t["ts"] <= e["ts"]
                       and e["ts"] + e["dur"] <= t["ts"] + t["dur"]
                       for t in ticks), f"{name} span outside every tick"
    # bank builds + scheduler selects + counter tracks present
    assert len(by_name["bank_build"]) == eng.bank.builds > 0
    assert len(by_name["select"]) == len(busy)
    assert {e["cat"] for e in evs} >= {"request", "engine", "bank",
                                       "sched", "metrics"}
    # virtual clock => deterministic timestamps: replay again, same trace
    obs2 = Observability()
    _replay_golden(obs2)
    strip = [dict(e) for e in obs2.tracer.events()]
    assert strip == evs


def test_obs_registry_tracks_engine_counters():
    obs = Observability()
    eng, _ = _replay_golden(obs)
    obs.finalize(eng)
    snap = obs.metrics.snapshot()
    assert snap["engine_ticks"] == eng.tick_count
    assert snap["engine_finished"] == eng.n_finished
    assert snap["bank_builds"] == eng.bank.builds
    assert snap["sched_preemptions"] == eng.batcher.preemptions
    assert snap["engine_forward_seconds_count"] > 0
    assert snap["trace_events"] == len(obs.tracer.events())
    text = obs.metrics.to_text()
    assert "engine_ticks" in text and "bank_builds" in text


# ---------------------------------------------------------------------------
# Thread safety: spans from the prefetch worker under churn.
# ---------------------------------------------------------------------------


def test_bank_spans_from_threaded_churn_reconcile():
    # The churn runs with lockcheck's order-tracking locks installed in
    # both the bank and the whole obs stack: beyond "no torn spans",
    # this pins that no thread ever held bank._lock while taking a
    # tracer/metrics lock (the deadlock precondition), not just that the
    # deadlock didn't happen to fire.
    from tools.analysis.lockcheck import LockMonitor, serving_discipline
    mon = serving_discipline(LockMonitor())
    bank = _multi_segment_bank(lock_factory=mon)
    bank.max_cached = bank.n_segments
    obs = Observability(lock_factory=mon)
    bank.obs = obs
    segs = list(range(bank.n_segments))
    errs = []

    def worker(wid):
        rng = np.random.default_rng(wid)
        try:
            for _ in range(30):
                seg = int(rng.choice(segs))
                if rng.random() < 0.5:
                    bank.prefetch(seg, block=bool(rng.random() < 0.3))
                else:
                    bank.params_for_segment(seg)
        except Exception as e:
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    bank.drain()
    assert not errs
    evs = obs.tracer.events()
    builds = [e for e in evs if e["name"] == "bank_build"]
    # one completed build span per counted build, none torn
    assert len(builds) == bank.builds == len(segs)
    for e in builds:
        assert e["ph"] == "X" and e["dur"] >= 0 and "seg" in e["args"]
        json.dumps(e)                      # fully serializable, not torn
    # spans arrived from >1 thread; metadata names every tid
    tids = {e["tid"] for e in evs}
    assert len(tids) >= 2
    meta = {m["tid"] for m in obs.tracer._metadata_events()}
    assert tids <= meta
    # the instrumented locks actually saw the churn, and the order
    # discipline held throughout
    counts = mon.acquire_counts()
    assert counts.get("bank._lock", 0) > 0
    assert counts.get("tracer._lock", 0) > 0
    mon.assert_clean()


# ---------------------------------------------------------------------------
# Kernel-route profiling reconciles with ops dispatch.
# ---------------------------------------------------------------------------


@pytest.fixture
def clean_force():
    old = ops.FORCE
    yield
    ops.FORCE = old


def _packed(rng):
    w = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4,
                         jnp.float32(jnp.abs(w).max()))
    return pack_weight(w, qp)


def test_kernel_route_counts_reconcile_with_dispatch(clean_force):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(3.0))
    pw = _packed(rng)
    obs = Observability()
    with obs.kernel_profiler:
        ops.FORCE = "interpret"            # pallas kernels, interpret mode
        ops.msfp_quantize(x, qp)
        ops.w4_matmul(x, pw)
        ops.FORCE = "xla"                  # pure reference oracles
        ops.msfp_quantize(x, qp)
        ops.FORCE = None                   # CPU default: fast XLA serving
        ops.msfp_quantize(x, qp)
    assert ops.PROFILER is None            # context manager uninstalls
    counts = obs.kernel_profiler.route_counts()
    assert counts == {"msfp_quantize:interpret": 1,
                      "w4_matmul:interpret": 1,
                      "msfp_quantize:ref": 1,
                      "msfp_quantize:xla_fast": 1}
    snap = obs.metrics.snapshot()
    # eager calls are timed into the per-route histogram
    key = 'kernel_call_seconds{op="msfp_quantize",route="interpret"}_count'
    assert snap[key] == 1
    assert snap['kernel_calls_total{mode="eager",op="msfp_quantize",'
                'route="xla_fast"}'] == 1


def test_kernel_profiler_counts_traced_calls_once_per_compile(clean_force):
    ops.FORCE = "xla"
    qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4, jnp.float32(3.0))
    x = jnp.ones((4, 8), jnp.float32)
    obs = Observability()
    with obs.kernel_profiler:
        f = jax.jit(lambda v: ops.msfp_quantize(v, qp))
        f(x)
        f(x)                               # cache hit: no re-trace
    assert obs.kernel_profiler.route_counts() == {"msfp_quantize:ref": 1}
    snap = obs.metrics.snapshot()
    assert snap['kernel_calls_total{mode="traced",op="msfp_quantize",'
                'route="ref"}'] == 1
    # traced calls are marked, not timed (timing a trace is meaningless)
    assert not any(k.startswith("kernel_call_seconds") for k in snap)
    marks = [e for e in obs.tracer.events() if e["cat"] == "kernel"]
    assert len(marks) == 1 and marks[0]["args"]["traced"]


# ---------------------------------------------------------------------------
# MetricsCollector retention + folded counters.
# ---------------------------------------------------------------------------


def _feed(col, n):
    for i in range(n):
        col.events.append(_Event(arrival=float(i), finished=i + 0.5,
                                 latency=0.5, met_deadline=(i % 3 != 0),
                                 expired=(i % 7 == 0)))
        col.ticks.append((float(i), i % 5, i % 3, 0, 0))


def test_retention_cap_keeps_summary_totals_exact():
    capped = MetricsCollector(max_events=6, max_ticks=4)
    unbounded = MetricsCollector(max_events=None, max_ticks=None)
    _feed(capped, 20)
    _feed(unbounded, 20)
    assert len(capped.events) == 6 and len(capped.ticks) == 4
    s_c, s_u = capped.summary(), unbounded.summary()
    for k in ("requests", "expired", "deadline_misses", "duration_s",
              "throughput_rps", "goodput_rps", "goodput_frac",
              "peak_queue_depth", "mean_inflight"):
        assert s_c[k] == pytest.approx(s_u[k]), k
    assert s_c["compacted_events"] == 14 and s_c["compacted_ticks"] == 16
    assert s_u["compacted_events"] == 0
    # percentiles are windowed — still well-formed over the retained tail
    assert s_c["p95_s"] == 0.5


def test_summary_folds_scheduler_and_bank_counters():
    col = MetricsCollector()
    s = col.summary()                      # unattached: zero defaults
    assert (s["preemptions"], s["deadline_saves"], s["bank_builds"],
            s["bank_build_joins"], s["prefetch_hits"]) == (0, 0, 0, 0, 0)

    eng = _engine(bank=_multi_segment_bank(), max_batch=2)
    col.attach(eng)
    for i in range(3):
        eng.submit(steps=3 + i % 2, seed=i)
    eng.run()
    s = col.summary()
    assert s["bank_builds"] == eng.bank.builds > 0
    assert s["prefetch_hits"] == eng.bank.prefetch_hits
    assert s["preemptions"] == eng.batcher.preemptions
    assert s["requests"] == 3


# ---------------------------------------------------------------------------
# The tick's phases, the scheduler's counters, compiles, the device trace.
# ---------------------------------------------------------------------------

_PHASES = ("admit", "schedule", "bank_fetch", "forward", "advance",
           "prefetch")


def _inside(e, parent):
    return (e["tid"] == parent["tid"] and parent["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= parent["ts"] + parent["dur"])


def test_tick_phase_spans_nest_and_cover_each_tick(monkeypatch):
    """Time passes only inside the work each phase names (a manual clock
    stepped by the calls each phase makes), so a busy tick's direct
    children must add up to the tick exactly, and ``batch`` +
    ``dispatch`` + ``unbatch`` to each ``forward``."""
    clock = [0.0]

    def stepping(fn):
        def call(*a, **kw):
            clock[0] += 1.0
            return fn(*a, **kw)
        return call

    class _SteppingJnp:            # every jnp call the engine makes
        def __getattr__(self, name):
            clock[0] += 1.0
            return getattr(jnp, name)

    monkeypatch.setattr(engine_mod, "jnp", _SteppingJnp())
    monkeypatch.setattr(engine_mod, "sampler_advance",
                        stepping(engine_mod.sampler_advance))
    monkeypatch.setattr(engine_mod, "sampler_needed_t",
                        stepping(engine_mod.sampler_needed_t))
    bank = _multi_segment_bank()
    bank.params_for_segment = stepping(bank.params_for_segment)
    bank.prefetch = stepping(bank.prefetch)
    obs = Observability()
    eng = DiffusionServingEngine(
        tiny_ddim(4), SCHED, bank, max_batch=3, obs=obs,
        apply_fn=stepping(lambda params, x, tb, y, ctx: 0.1 * x),
        now_fn=lambda: clock[0], async_prefetch=False)
    eng.batcher.admit = stepping(eng.batcher.admit)
    for i in range(5):
        eng.submit(steps=3 + i % 3, seed=i)
    with jax.disable_jit():        # the forward's Python runs every call
        eng.run()

    # spans publish as they end: a tick's descendants precede it, and a
    # forward's children lie between the tick's bank_fetch and forward
    evs = [e for e in obs.tracer.events()
           if e["ph"] == "X" and e["cat"] in ("engine", "bank")
           and e["name"] != "bank_build"]
    held, busy = [], 0
    for e in evs:
        if e["name"] != "tick":
            held.append(e)
            continue
        phases = [h for h in held if h["name"] in _PHASES]
        names = tuple(h["name"] for h in phases)
        if e["args"].get("idle"):
            assert names == ("admit",)
        else:
            busy += 1
            assert names == _PHASES
            # prefetch has no work once every member finished
            assert all(h["dur"] > 0 for h in phases[:-1])
            fwd = phases[3]
            k = held.index(fwd)
            kids = held[held.index(phases[2]) + 1:k]
            assert {h["name"] for h in kids} <= {"batch", "dispatch",
                                                 "unbatch"}
            dispatch = [h for h in kids if h["name"] == "dispatch"]
            assert len(dispatch) == 1 and "compiled" in dispatch[0]["args"]
            assert sum(h["dur"] for h in kids) == fwd["dur"] > 0
            # padding to the bucket is batching too: a second batch span
            assert sum(h["name"] == "batch" for h in kids) == (
                1 + (fwd["args"]["padded_rows"] != fwd["args"]["items"]))
            for h in kids:
                assert _inside(h, fwd)
        for a, b in zip(phases, phases[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        for h in held:
            assert _inside(h, e)
        assert sum(h["dur"] for h in phases) == e["dur"]
        held = []
    assert busy == eng.n_forwards > 0 and held == []


def test_phase_spans_close_when_the_tick_raises():
    obs = Observability()

    def broken(params, x, tb, y, ctx):
        raise RuntimeError("forward failed")

    eng = DiffusionServingEngine(tiny_ddim(4), SCHED,
                                 _single_segment_bank(), apply_fn=broken,
                                 obs=obs, clock=VirtualClock())
    eng.submit(steps=3)
    with pytest.raises(RuntimeError, match="forward failed"):
        eng.tick()
    names = [e["name"] for e in obs.tracer.events() if e["ph"] == "X"]
    assert names[-3:] == ["dispatch", "forward", "tick"]
    assert obs.tracer._stacks[0] == []


def _two_segment_bank():
    """Two routing segments, the halves of [0, T)."""
    params = {"l0": {"w": jnp.ones((4, 4))}}
    from repro.common.tree import flatten_paths
    from repro.serving import default_serving_plan
    sig = np.repeat([[0], [1]], T // 2, axis=0)
    return WeightBank(params, default_serving_plan(flatten_paths(params)),
                      {}, None, None, T, signatures=sig)


@pytest.mark.parametrize("segments", [1, 2])
def test_served_request_ticks_count_the_selection(segments):
    bank = _single_segment_bank() if segments == 1 else _two_segment_bank()
    assert bank.n_segments == segments
    eng = _engine(bank=bank, max_batch=4, clock=VirtualClock())
    seen = []
    select = eng.batcher.select

    def spy(groups, tick, now=None):
        seg, members = select(groups, tick, now=now)
        seen.append((len(groups), len(eng.batcher.inflight), len(members)))
        return seg, members

    eng.batcher.select = spy
    for i, steps in enumerate((2, 8, 8)):
        eng.submit(steps=steps, seed=i)
    eng.run()
    st = eng.stats()
    assert st["inflight_request_ticks"] == eng.inflight_request_ticks == sum(
        n for _, n, _ in seen)
    assert st["served_request_ticks"] == eng.served_request_ticks == sum(
        m for _, _, m in seen)
    if segments == 1:
        assert st["served_request_ticks"] == st["inflight_request_ticks"]
    else:
        # the 2-step request crosses into the low half while the others
        # are still in the high one: the segments split them
        assert any(g > 1 for g, _, _ in seen)
        assert st["served_request_ticks"] < st["inflight_request_ticks"]


def _probe_fn(v):
    return v * 3.0 + 1.0


def test_compile_spans_once_per_new_shape_inside_the_open_span():
    obs = Observability()
    _engine(obs=obs)                  # wall clock: the listener registers
    tr = obs.tracer
    f = jax.jit(_probe_fn)            # a fresh cache: every shape is new
    a3, a5 = np.ones(3, np.float32), np.ones(5, np.float32)
    try:
        with tr.span("outer"):
            f(a3)
            with tr.span("inner"):
                f(a5)
                f(a3)
            f(a5)
        obs.close()
        f(np.ones(7, np.float32))     # after close: not recorded
    finally:
        obs.close()
    evs = tr.events()
    assert any(e["name"] == "compile_listener" and e["cat"] == "jit"
               for e in evs)
    outer = next(e for e in evs if e["name"] == "outer")
    inner = next(e for e in evs if e["name"] == "inner")
    mine = [e for e in evs if e["name"] == "compile"
            and "_probe_fn" in e["args"]["fun_name"]]
    assert {e["cat"] for e in mine} == {"jit"}
    built = [e for e in mine if e["args"]["stage"] == "backend_compile"]
    assert len(built) == 2            # shapes 3 and 5, once each
    first, second = sorted(built, key=lambda e: e["ts"])
    assert _inside(first, outer) and not _inside(first, inner)
    assert _inside(second, inner)
    for e in mine:
        assert _inside(e, outer)
    counts = {k: v for k, v in obs.metrics.snapshot().items()
              if k.startswith("jit_compiles_total") and "_probe_fn" in k}
    assert sum(v for k, v in counts.items()
               if 'stage="backend_compile"' in k) == 2


def test_null_obs_and_virtual_clock_register_nothing(monkeypatch):
    registered = []
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        registered.append)
    entered = []

    class CountingAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("/" + self.name)

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", CountingAnnotation)
    plain = _engine()                 # NULL_OBS on a wall clock
    plain.submit(steps=3)
    plain.run()
    assert registered == [] and entered == []

    obs = Observability()
    replay = _engine(obs=obs, clock=VirtualClock())
    replay.submit(steps=3)
    replay.run()
    assert registered == []
    assert not any(e["name"] in ("compile", "compile_listener")
                   for e in obs.tracer.events())
    # the bridge itself: every span entered, then exited innermost first
    assert entered[:4] == ["tick", "admit", "/admit", "schedule"]
    depth = []
    for name in entered:
        if name.startswith("/"):
            assert depth.pop() == name[1:]
        else:
            depth.append(name)
    assert depth == []


def test_span_names_reach_the_profilers_host_plane(tmp_path):
    obs = Observability()
    eng = _engine(obs=obs, bank=_multi_segment_bank(), max_batch=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            eng.submit(steps=3, seed=i)
        eng.run()
    finally:
        jax.profiler.stop_trace()
        obs.close()
    (path,) = Path(tmp_path).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    host = {e.name for pl in data.planes if pl.name == "/host:CPU"
            for ln in pl.lines for e in ln.events}
    assert {"tick", "advance", "batch", "dispatch"} <= host
