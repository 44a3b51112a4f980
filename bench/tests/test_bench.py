"""Tests of the benchmark itself, on the CPU at a test size.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The harness runs end to end here through ``harness.run``, which does not
look for a chip (``run.py`` does, and refuses the CPU). Kernels run in
interpret mode where a test says so; no timing taken here is a device
number.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import flops
import harness
import trace_reduce
import traffic
import unet_ref as ref

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TINY = json.loads((BENCH / "tests" / "tiny-ddim.json").read_text())
SEED = 2**40 + 12345          # larger than 32 bits, as run seeds may be
# the benchmark's cell, and a closed loop of the kept backlog mix as a later
# cell would run it
CELLS = ("cifar10-poisson", "backlog")
MIXES = {"backlog": "backlog_20"}


def tiny_cell(name: str, rate: float = 2.0, mix: str | None = None
              ) -> harness.Cell:
    """The cell's own mix (or the mix named) and loop on the test-size
    model, with short requests and, by default, a light load so that
    interpret mode keeps up. A name that is no cell of the benchmark runs
    under the benchmark's spec as a cell of its own."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if name in {w["name"] for w in spec["workloads"]}:
        cell = harness.load_cell(name)
    else:
        mix = mix or MIXES[name]
        cell = harness.Cell(name, {"name": name, "config": TINY["name"],
                                   "traffic": mix, "chips": 1},
                            TINY, traffic.load(mix), spec)
    mix = copy.deepcopy(traffic.load(mix) if mix else cell.mix)
    mix["steps"] = [3, 5] if mix["loop"] == "open" else [4]
    mix["warm_batches"] = sorted({min(n, 4) for n in mix["warm_batches"]})
    if mix["loop"] == "open":
        mix["rate_per_s"] = rate
    else:
        mix["clients"] = min(mix["clients"], 4)
        mix["tracked"] = min(mix["tracked"], 4)
    return harness.Cell(name, cell.workload, copy.deepcopy(TINY), mix,
                        cell.spec)


@pytest.fixture
def interpret():
    from repro.kernels import ops
    was, ops.FORCE = ops.FORCE, "interpret"
    yield
    ops.FORCE = was


@pytest.fixture(autouse=True)
def no_compile_cache():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


# ---------------------------------------------------------------------------
# the spec and the files it names
# ---------------------------------------------------------------------------


def test_every_name_in_the_spec_has_its_file():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg)
    for w in spec["workloads"]:
        harness.load_cell(w["name"])
    for m in spec["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}


def test_new_files_are_found_without_editing_any(tmp_path):
    """A later cell, configuration, mix and metric are files and entries:
    a copy of the benchmark finds them by name."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    (tmp_path / "bench/configs/tiny-new.json").write_text(
        json.dumps({**TINY, "name": "tiny-new"}))
    (tmp_path / "bench/traffic/new_mix.json").write_text(json.dumps(
        {**harness.load_cell("cifar10-poisson").mix, "rate_per_s": 3.0}))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec["configs"].append({"name": "tiny-new", "source": "test",
                            "file": "bench/configs/tiny-new.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-new.poisson", "config": "tiny-new",
                              "traffic": "new_mix", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "new_metric", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "engine and scheduler",
                              "moves": "steps_per_s",
                              "workloads": ["tiny-new.poisson"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    probe = ("import harness; c = harness.load_cell('tiny-new.poisson'); "
             "print(c.cfg['name'], c.mix['rate_per_s'], "
             "[m['name'] for m in c.per_layer], "
             "harness.load_reader('new_metric')(None))")
    out = subprocess.run(
        [sys.executable, "-c", probe], cwd=tmp_path, capture_output=True,
        text=True, env={**os.environ, "PYTHONPATH": f"{tmp_path / 'bench'}"},
        timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["tiny-new", "3.0", "['new_metric']", "42.0"]
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_run_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cifar10-poisson",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_refuses_in_a_directory_of_the_benchmark_alone(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cifar10-poisson",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


def test_open_loop_gives_every_seed_the_same_work():
    """The same arrivals and step counts in the same order for every seed;
    the seed draws each request's noise."""
    mix = harness.load_cell("cifar10-poisson").mix
    a = traffic.Requests(mix, SEED, None).schedule(30.0)
    b = traffic.Requests(mix, SEED, None).schedule(30.0)
    c = traffic.Requests(mix, SEED + 1, None).schedule(30.0)
    assert a == b
    assert [(r.due, r.steps) for r in a] == [(r.due, r.steps) for r in c]
    assert [r.seed for r in a] != [r.seed for r in c]
    assert sorted(r.steps for r in a) == sorted(
        mix["steps"][k % len(mix["steps"])] for k in range(len(a)))
    assert max(r.due for r in a) < 30.0
    assert all(r.y is None and r.guidance == 0.0 for r in a)


def test_tracked_requests_include_one_of_the_longest():
    mix = harness.load_cell("cifar10-poisson").mix
    reqs = traffic.Requests(mix, SEED, None)
    sched = reqs.schedule(30.0)
    tracked = reqs.tracked(sched)
    assert len(tracked) == mix["tracked"]
    assert max(sched[k].steps for k in tracked) == max(mix["steps"])


def test_labelled_mix_draws_labels_and_guidance():
    reqs = traffic.Requests(traffic.load("backlog_cfg_20"), SEED, 1000)
    rs = [reqs.closed(k, 0.0) for k in range(50)]
    assert all(0 <= r.y < 1000 and r.guidance == 3.0 for r in rs)
    assert len({r.y for r in rs}) > 40


# ---------------------------------------------------------------------------
# operations, bytes and peaks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("config", ["ddim-cifar10", "ddim-celeba",
                                    "ldm4-imagenet"])
def test_unet_flops_agree_with_xla_cost_analysis(config):
    from repro.nn.unet import UNetConfig, unet_apply, unet_init

    m = json.loads((BENCH / "configs" / f"{config}.json").read_text())["model"]
    cfg = UNetConfig(**{**m, "ch_mult": tuple(m["ch_mult"]),
                        "attn_resolutions": tuple(m["attn_resolutions"])})
    p = jax.eval_shape(lambda k: unet_init(k, cfg), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, cfg.image_size, cfg.image_size, cfg.in_ch),
                             jnp.float32)
    t = jax.ShapeDtypeStruct((1,), jnp.float32)
    y = jax.ShapeDtypeStruct((1,), jnp.int32) if cfg.num_classes else None
    cost = jax.jit(lambda p, x, t, y: unet_apply(p, x, t, cfg, y=y)).lower(
        p, x, t, y).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert flops.unet_flops(m) == pytest.approx(cost["flops"], rel=0.01)


def test_conv_work_counts_taps_inside_the_input():
    # 3x3 SAME on 4x4: 4 corners x 4 taps + 8 edges x 6 + 4 inner x 9
    assert flops.conv_flops(4, 4, 3, 1, 1, 1) == 2 * (16 + 48 + 36)
    assert flops.conv_flops(8, 8, 1, 1, 2, 3) == 2 * 64 * 6


def test_peaks_table_is_keyed_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_roofline_takes_the_larger_bound():
    p = flops.peaks("TPU v5 lite")
    assert flops.least_seconds(197e12, 1.0, p) == pytest.approx(1.0)
    assert flops.least_seconds(1.0, 819e9, p) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def test_union_of_intervals():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduction_of_a_recorded_chip_trace():
    """A trace recorded on one TPU v5e chip (``tests/data``): inside the
    window annotation, three rounds of a 10 ms host sleep (annotated
    ``host_sleep``) and two small jitted programs."""
    red = trace_reduce.reduce_dir(BENCH / "tests" / "data")
    want = json.loads((BENCH / "tests" / "data" / "expected.json").read_text())
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-6)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < red.busy_s < red.window_s
    assert red.gaps[0][1] == want["longest_gap_host"]
    assert sum(red.op_seconds.values()) == pytest.approx(
        want["op_seconds_total"], rel=1e-6)
    # three rounds of the two programs, each one fused op, 10 ms sleeps
    # between them
    assert red.op_counts == {"fusion": 3, "multiply_add_fusion": 3}
    assert [h for _, h in red.gaps[:3]] == ["host_sleep"] * 3
    assert all(0.009 < s < 0.013 for s, _ in red.gaps[:3])


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


def test_reference_matches_the_served_forward_at_test_size():
    """The plain reference and the program's UNet on the packed weights of
    one segment, through the fast XLA path: the same numbers."""
    from repro.core.qmodule import PackedW4, dequant_weight
    from repro.launch.serve_diffusion import fp4_act_qps
    from repro.nn.unet import UNetConfig, io_sites, unet_apply
    from repro.quant.calibrate import QuantContext
    from repro.serving import WeightBank, default_serving_plan
    from repro.core.talora import TALoRAConfig

    cfg = TINY
    m, tl = cfg["model"], cfg["talora"]
    params, hubs = ref.make_weights(SEED, cfg)
    fl = ref.flat(params)
    plan = default_serving_plan({k: fl[k] for k in hubs},
                                io_sites=io_sites(params))
    sig = ref.signatures(SEED, cfg)
    bank = WeightBank(params, plan, hubs, {},
                      TALoRAConfig(tl["hub_size"], tl["rank"], tl["alpha"]),
                      cfg["T"], signatures=sig)
    assert bank.n_segments == tl["segments"]
    ucfg = UNetConfig(**{**m, "ch_mult": tuple(m["ch_mult"]),
                         "attn_resolutions": tuple(m["attn_resolutions"])})
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 8, 3))
    t = jnp.asarray([999.0, 500.0, 3.0])
    y = jnp.asarray([1, 2, 3])
    for seg, (lo, _) in enumerate(ref.segment_bounds(cfg)):
        served = bank.params_for_segment(seg)
        ctx = QuantContext("serve", act_qps=fp4_act_qps(6.0))
        got = unet_apply(served, x, t, ucfg, y=y, ctx=ctx)
        # a weight at a grid midpoint takes the bank's value, as in the check
        w, ties = ref.served_weights(params, hubs, sig[lo], cfg)
        flat = ref.flat(served)
        w, gap = check._settle_ties(ref.flat(w), ties, {
            s: (dequant_weight(flat[s], jnp.float32)
                if isinstance(flat[s], PackedW4) else flat[s])
            for s in ref.weight_sites(m)})
        assert float(gap) < 1e-6
        want = ref.forward(ref.nest(w), x, t, y, cfg)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_segments_have_distinct_adapters():
    sig = ref.signatures(SEED, TINY)
    bounds = ref.segment_bounds(TINY)
    rows = [tuple(sig[lo]) for lo, _ in bounds]
    assert all(a != b for a, b in zip(rows, rows[1:]))
    assert [hi - lo + 1 for lo, hi in bounds] == [250] * 4


# ---------------------------------------------------------------------------
# the harness, end to end at test size
# ---------------------------------------------------------------------------


def test_guided_mix_runs_end_to_end_with_interpret_kernels(interpret):
    """Class labels and guidance, as a class-conditional cell will send."""
    r = harness.run(tiny_cell("guided", mix="backlog_cfg_20"),
                    SEED, 3.0, False, time.perf_counter(), log=lambda m: None)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end_with_interpret_kernels(name, interpret):
    cell = tiny_cell(name)
    r = harness.run(cell, SEED, 3.0, False, time.perf_counter(),
                    log=lambda m: None)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"


def test_window_compiles_nothing(interpret, monkeypatch):
    seen = {}
    real = harness.Window.run

    def spy(self):
        out = real(self)
        seen.update(out)
        return out

    monkeypatch.setattr(harness.Window, "run", spy)
    harness.run(tiny_cell("cifar10-poisson"), SEED, 3.0, False,
                time.perf_counter(), log=lambda m: None)
    assert seen["compiles"] == 0


def test_traced_run_reads_its_per_layer_metrics(monkeypatch):
    """On the CPU there is no device plane to reduce: a fixed reduction
    stands in for it, and every reader of the cell finds its number."""
    fake = trace_reduce.Reduction(window_s=2.0, busy_s=1.5, n_devices=1,
                                  op_seconds={"w4a4_matmul_2d": 1.0,
                                              "fusion": 0.5},
                                  op_counts={"w4a4_matmul_2d": 3,
                                             "fusion": 3},
                                  gaps=[(0.5, "engine.tick")])
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda d: fake)
    monkeypatch.setattr(harness, "device_info", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
        "memory_peak_bytes": 1})
    monkeypatch.setattr(harness, "TRACE_SECONDS", 2.0)
    for name in ("cifar10-poisson",):
        cell = tiny_cell(name)
        r = harness.run(cell, SEED, 4.0, True, time.perf_counter(),
                        log=lambda m: None)
        assert r["correct"], r["checks"]
        assert set(r["metrics"]) == {m["name"] for m in cell.per_layer}
        assert r["device"]["busy_s"] == 1.5 and r["device"]["window_s"] == 2.0
        assert r["breakdown"]["idle_gaps"] == [["engine.tick", 0.5]]
        for share in ("unet_mfu", "w4a4_roofline"):
            v = r["metrics"].get(share)
            assert v is None or 0 < v["value"] < 100


# ---------------------------------------------------------------------------
# the check fails what it should
# ---------------------------------------------------------------------------


def _unchanged_step(st, eps):
    """A sampler step that returns its state unchanged."""
    st.i += 1
    st.done = st.i >= len(st.seq)
    return st


def _fault(kind, monkeypatch):
    import repro.serving.engine as engine_mod
    from repro.kernels import ops
    if kind == "unchanged_step":
        monkeypatch.setattr(engine_mod, "sampler_advance", _unchanged_step)
        return
    if kind == "altered_kernel":
        # an answer altered where a kernel produces it, inside the forward
        real_conv = ops.w4a4_conv2d
        monkeypatch.setattr(ops, "w4a4_conv2d",
                            lambda *a, **k: real_conv(*a, **k) * 1.02)
        return
    real = engine_mod.DiffusionServingEngine._forward

    def forward(self, params, x, tb, y):
        eps = real(self, params, x, tb, y)
        if kind == "half_batch":
            # the second half of the rows left out: they get row 0's eps
            keep = (x.shape[0] + 1) // 2
            return jnp.concatenate([eps[:keep],
                                    jnp.repeat(eps[:1], x.shape[0] - keep, 0)])
        # an answer altered where the forward hands it over
        return eps * 1.02

    monkeypatch.setattr(engine_mod.DiffusionServingEngine, "_forward", forward)


@pytest.mark.parametrize("kind", ["unchanged_step", "half_batch",
                                  "altered_answer", "altered_kernel"])
@pytest.mark.parametrize("name", CELLS)
def test_faults_make_the_run_incorrect(name, kind, monkeypatch):
    _fault(kind, monkeypatch)
    # a load at which requests share forwards, so half a batch exists, and
    # every request tracked, so that some tracked row sits in such a half
    cell = tiny_cell(name, rate=40.0)
    cell.mix["tracked"] = 10**6
    r = harness.run(cell, SEED, 2.0, False, time.perf_counter(),
                    log=lambda m: None)
    assert not r["correct"], r["checks"]


def test_control_fails_the_limits_at_test_size():
    """The reference one precision lower (bfloat16 operands) in the
    program's place comes out not correct on every seed tried, through the
    run's own judgement; the program on the same run comes out correct."""
    cell = tiny_cell("guided", mix="backlog_cfg_20")
    for seed in (SEED, 7, 2**33 + 1):
        r = harness.run(cell, seed, 2.0, False, time.perf_counter(),
                        log=lambda m: None, control=True)
        assert r["correct"], r["checks"]
        assert not r["control"]["correct"], r["control"]
        ctl = r["control"]["checks"]
        assert ctl["site_gap_max"]["value"] > 2 * ctl["site_gap_max"]["limit"]
