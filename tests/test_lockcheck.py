"""Lock-order race detector: unit semantics + the bank/obs integration.

Unit tier: the instrumented lock still mutually excludes, a consistent
global order stays clean, an AB/BA inversion is caught as a cycle *even
when the deadlock never fires*, forbidden pairs and same-thread
re-acquire are caught, and assert_clean raises a readable report.

Integration tier (the satellite this suite exists for): the full bank +
obs lock population — ``bank._lock``, ``tracer._lock``,
``metrics._lock``, per-instrument metrics locks, the profiler lock —
under concurrent prefetch churn, per-tick ``obs.sample``, and
``metrics.to_text()`` readers, with ``serving_discipline`` armed. The
PR 7 reconciliation invariants must hold *with instrumented locks
installed* (the instrumentation itself may not perturb the counters).
"""
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from tests._serving_fixtures import multi_segment_bank

from repro.serving.obs import Observability
from tools.analysis.lockcheck import (InstrumentedLock, LockMonitor,
                                      LockOrderError, serving_discipline)


# ---------------------------------------------------------------------------
# unit: the wrapper is still a lock
# ---------------------------------------------------------------------------


def test_instrumented_lock_mutually_excludes():
    mon = LockMonitor(capture_stacks=False)
    lock = mon.lock("x")
    state = {"n": 0}

    def bump():
        for _ in range(2000):
            with lock:
                v = state["n"]
                state["n"] = v + 1

    ts = [threading.Thread(target=bump) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert state["n"] == 8000
    assert mon.acquire_counts()["x"] == 8000
    mon.assert_clean()


def test_try_acquire_and_locked():
    mon = LockMonitor()
    lock = mon.lock("x")
    assert lock.acquire(blocking=False)
    assert lock.locked()
    lock.release()
    assert not lock.locked()
    mon.assert_clean()


# ---------------------------------------------------------------------------
# unit: order graph
# ---------------------------------------------------------------------------


def test_consistent_order_is_clean():
    mon = LockMonitor()
    a, b, c = mon.lock("a"), mon.lock("b"), mon.lock("c")
    for _ in range(5):
        with a:
            with b:
                with c:
                    pass
    assert ("a", "b") in mon.edges() and ("b", "c") in mon.edges()
    mon.assert_clean()


def test_ab_ba_cycle_detected_without_deadlock_firing():
    # one thread, sequential: A->B then B->A. No deadlock ever happens,
    # but the *precondition* exists and must be reported.
    mon = LockMonitor()
    a, b = mon.lock("a"), mon.lock("b")
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    kinds = [v.kind for v in mon.violations()]
    assert "cycle" in kinds
    with pytest.raises(LockOrderError, match="cycle"):
        mon.assert_clean()


def test_transitive_cycle_detected():
    mon = LockMonitor()
    a, b, c = mon.lock("a"), mon.lock("b"), mon.lock("c")
    with a:
        with b:
            pass
    with b:
        with c:
            pass
    with c:
        with a:
            pass   # closes a -> b -> c -> a
    assert any(v.kind == "cycle" for v in mon.violations())


def test_cross_thread_inversion_detected():
    mon = LockMonitor()
    a, b = mon.lock("a"), mon.lock("b")
    barrier = threading.Barrier(2)

    def t1():
        with a:
            barrier.wait()
        barrier.wait()
        # after t2 released b, take b->a (inverted) without contention
        with b:
            with a:
                pass

    def t2():
        with b:
            barrier.wait()
        barrier.wait()

    ts = [threading.Thread(target=t1), threading.Thread(target=t2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # t1 recorded a->b? No — it recorded b->a only; seed the other side
    with a:
        with b:
            pass
    assert any(v.kind == "cycle" for v in mon.violations())


def test_forbidden_pair_detected():
    mon = LockMonitor()
    mon.forbid("bank._lock", "tracer", "spans under the bank lock")
    bank = mon.lock("bank._lock")
    tr = mon.lock("tracer._lock")
    with bank:
        with tr:
            pass
    vs = mon.violations()
    assert len(vs) == 1 and vs[0].kind == "forbidden"
    assert "spans under the bank lock" in vs[0].reason
    with pytest.raises(LockOrderError, match="bank._lock -> tracer._lock"):
        mon.assert_clean()


def test_leaf_policy_empty_inner_prefix_matches_any():
    mon = LockMonitor()
    mon.forbid("tracer._lock", "", "tracer lock is a leaf")
    tr, other = mon.lock("tracer._lock"), mon.lock("anything")
    with tr:
        with other:
            pass
    assert [v.kind for v in mon.violations()] == ["forbidden"]


def test_self_deadlock_raises_instead_of_hanging():
    mon = LockMonitor()
    lock = mon.lock("x")
    lock.acquire()
    with pytest.raises(LockOrderError, match="self-deadlock"):
        lock.acquire()
    lock.release()
    assert any(v.kind == "self-deadlock" for v in mon.violations())


def test_same_name_siblings_carry_no_order_edge():
    # every Counter of one family shares a name; holding two distinct
    # objects of the same name is not an inversion (and no self-edge)
    mon = LockMonitor()
    l1, l2 = mon.lock("metrics.kcalls"), mon.lock("metrics.kcalls")
    with l1:
        with l2:
            pass
    assert mon.edges() == set()
    mon.assert_clean()


def test_report_mentions_counts_and_violation():
    mon = LockMonitor()
    mon.forbid("a", "b", "because")
    with mon.lock("a"):
        with mon.lock("b"):
            pass
    rep = mon.report()
    assert "violation" in rep and "because" in rep and "acquires" in rep


# ---------------------------------------------------------------------------
# integration: bank._lock x tracer/metrics locks under concurrent load
# ---------------------------------------------------------------------------


def _fake_engine(bank):
    """The attribute surface obs.sample() and obs.set_gauges() read,
    wired to a real bank."""
    batcher = SimpleNamespace(pending=[], inflight=[], preemptions=0,
                              deadline_saves=0,
                              cost=SimpleNamespace(sample_s=0.0,
                                                   switch_s=0.0))
    return SimpleNamespace(batcher=batcher, bank=bank, tick_count=0,
                           n_forwards=0, n_finished=0, n_expired=0,
                           n_padded_samples=0, _jit={})


def test_bank_obs_lock_population_under_concurrent_load():
    mon = serving_discipline(LockMonitor())
    bank = multi_segment_bank(lock_factory=mon)
    bank.max_cached = bank.n_segments
    obs = Observability(lock_factory=mon)
    bank.obs = obs
    eng = _fake_engine(bank)
    segs = list(range(bank.n_segments))
    errs = []
    stop = threading.Event()

    def churn(wid):
        rng = np.random.default_rng(wid)
        try:
            for _ in range(40):
                seg = int(rng.choice(segs))
                if rng.random() < 0.5:
                    bank.prefetch(seg, block=bool(rng.random() < 0.3))
                else:
                    bank.params_for_segment(seg)
        except Exception as e:   # pragma: no cover - surfaced below
            errs.append(e)

    def sampler():
        try:
            while not stop.is_set():
                obs.sample(eng)
                obs.set_gauges(eng)
                with obs.tracer.span("tick", cat="engine") as sp:
                    sp.set("pending", 0)
        except Exception as e:   # pragma: no cover
            errs.append(e)

    def reader():
        try:
            while not stop.is_set():
                obs.metrics.to_text()
                obs.metrics.snapshot()
                obs.tracer.events()
        except Exception as e:   # pragma: no cover
            errs.append(e)

    workers = [threading.Thread(target=churn, args=(w,)) for w in range(2)]
    aux = [threading.Thread(target=sampler), threading.Thread(target=reader)]
    for t in workers + aux:
        t.start()
    for t in workers:
        t.join()
    bank.drain()
    stop.set()
    for t in aux:
        t.join()
    assert not errs

    # the run exercised the full lock population from >= 4 threads...
    counts = mon.acquire_counts()
    for name in ("bank._lock", "tracer._lock", "metrics._lock"):
        assert counts.get(name, 0) > 0, (name, counts)
    assert any(n.startswith("metrics.") and n != "metrics._lock"
               for n in counts), counts
    # ...the ordering discipline held throughout (no span/metrics call
    # ever nested under bank._lock, tracer/profiler stayed leaves)...
    mon.assert_clean()
    # ...and the PR 7 reconciliation invariants survive instrumentation:
    assert bank.builds + bank.build_failures == bank.misses + bank.prefetches
    build_spans = [e for e in obs.tracer.events()
                   if e["name"] == "bank_build"]
    assert len(build_spans) == bank.builds == len(segs)
    # registry gauges set concurrently converged to the bank's final
    # counters once the churn drained
    obs.set_gauges(eng)
    snap = obs.metrics.snapshot()
    assert snap["bank_builds"] == bank.builds
    assert snap["bank_misses"] == bank.misses
    assert snap["bank_prefetches"] == bank.prefetches
