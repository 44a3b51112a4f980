"""Sharding rules + dry-run machinery on a subprocess multi-device mesh.

The test process holds 1 CPU device; these tests exec short scripts with
``--xla_force_host_platform_device_count=8`` to get a real (4, 2) mesh, and
assert lower+compile works with the production sharding rules — a scaled
replica of the 512-chip dry-run.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(body: str, timeout=420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_param_shardings_rules_unit():
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import param_spec

    class FakeMesh:
        axis_names = ("data", "model")
        axis_sizes = (4, 2)

    m = FakeMesh()
    assert param_spec("embed", (1024, 64), m) == P("model", None)
    assert param_spec("blocks/#0/attn/wq/w", (8, 64, 64), m) == \
        P(None, "data", "model")
    assert param_spec("blocks/#0/mlp/down/w", (8, 128, 64), m) == \
        P(None, "model", "data")
    assert param_spec("blocks/#0/moe/w_gate", (4, 64, 32), m) == \
        P("model", "data", None)
    # indivisible dims drop axes
    assert param_spec("lm_head/w", (63, 101), m) == P(None, None)
    # norms replicate
    assert param_spec("final_norm/g", (64,), m) == P()


@pytest.mark.slow
def test_train_step_compiles_sharded_8dev():
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.configs.registry import get_config
        from repro.launch.sharding import param_shardings, data_spec
        from repro.launch.steps import (make_train_step, abstract_params,
                                        abstract_opt, input_specs)
        from repro.optim.adam import AdamConfig
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_config("qwen1.5-0.5b", smoke=True)
        acfg = AdamConfig()
        with jax.set_mesh(mesh):
            ap = abstract_params(cfg)
            ao = abstract_opt(ap, acfg)
            ps = param_shardings(ap, mesh)
            os_ = param_shardings(ao, mesh)
            tokens = jax.ShapeDtypeStruct((8, 16), jnp.int32)
            bs = {"tokens": NamedSharding(mesh, data_spec((8, 16), mesh))}
            step = make_train_step(cfg, acfg)
            co = jax.jit(step, in_shardings=(ps, os_, bs),
                         out_shardings=(ps, os_, None)) \\
                .lower(ap, ao, {"tokens": tokens}).compile()
            ca = co.cost_analysis()
            print("FLOPS", ca.get("flops", -1) > 0)
            print("OK")
    """)
    assert "OK" in out and "FLOPS True" in out


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["zamba2-2.7b", "kimi-k2-1t-a32b"])
def test_decode_step_compiles_sharded_8dev(arch):
    out = run_py(f"""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import get_config
        from repro.configs.shapes import ShapeSpec
        from repro.launch.sharding import (param_shardings, cache_shardings,
                                           data_spec)
        from repro.launch.steps import (abstract_params, input_specs,
                                        make_decode_fn, quantize_abstract)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_config("{arch}", smoke=True)
        shape = ShapeSpec("d", 32, 8, "decode")
        with jax.set_mesh(mesh):
            ap = quantize_abstract(abstract_params(cfg))
            ps = param_shardings(ap, mesh)
            specs = input_specs(cfg, shape)
            cs = cache_shardings(specs["caches"], mesh)
            ts = NamedSharding(mesh, data_spec((8, 1), mesh))
            co = jax.jit(make_decode_fn(cfg),
                         in_shardings=(ps, cs, ts, NamedSharding(mesh, P())),
                         out_shardings=(None, cs)) \\
                .lower(ap, specs["caches"], specs["token"],
                       specs["pos"]).compile()
            ca = co.cost_analysis()
            print("OK", ca.get("flops", 0) > 0)
    """)
    assert "OK True" in out


@pytest.mark.slow
def test_checkpoint_restore_onto_different_mesh():
    """Elasticity: save sharded on (4,2), restore onto (2,4)."""
    out = run_py("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.ckpt import CheckpointManager
        from jax.sharding import AxisType
        m1 = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        m2 = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        tree = {"w": jnp.arange(64.0).reshape(8, 8)}
        sh1 = {"w": NamedSharding(m1, P("data", "model"))}
        sh2 = {"w": NamedSharding(m2, P("data", "model"))}
        placed = jax.device_put(tree, sh1)
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d)
            cm.save(1, placed)
            _, back, _ = cm.restore(1, shardings=sh2)
            assert back["w"].sharding == sh2["w"]
            np.testing.assert_allclose(np.asarray(back["w"]),
                                       np.asarray(tree["w"]))
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_moe_expert_parallel_matches_global():
    """shard_map EP dispatch == global-sort dispatch (no-drop capacity)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.nn.moe import MoEConfig, moe_init, moe_apply, moe_apply_ep
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = MoEConfig(d_model=32, d_ff=16, n_experts=4, top_k=2,
                        n_shared=1, capacity_factor=8.0)
        key = jax.random.PRNGKey(0)
        p = moe_init(key, cfg, jnp.float32)
        x = jax.random.normal(key, (8, 6, 32))
        with jax.set_mesh(mesh):
            xg = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
            y_g = jax.jit(lambda p, x: moe_apply(p, x, cfg))(p, xg)
            y_e = jax.jit(lambda p, x: moe_apply_ep(p, x, cfg))(p, xg)
            assert float(jnp.abs(y_g - y_e).max()) < 1e-4
            g = jax.jit(jax.grad(
                lambda p: jnp.sum(moe_apply_ep(p, xg, cfg) ** 2)))(p)
            assert all(bool(jnp.isfinite(l).all())
                       for l in jax.tree.leaves(g))
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_collective_parser():
    from repro.launch.dryrun import parse_collectives
    hlo = """
      %ar = f32[16,1024]{1,0} all-reduce(%x), replica_groups=...
      %ag.1 = bf16[8,512]{1,0} all-gather(%y), dimensions={0}
      %a2a = (bf16[4,4]{1,0}, bf16[4,4]{1,0}) all-to-all(%a, %b)
      %cp = u8[100]{0} collective-permute-start(%z)
    """
    r = parse_collectives(hlo)
    assert r["count_by_op"] == {"all-reduce": 1, "all-gather": 1,
                                "all-to-all": 1, "collective-permute": 1}
    assert r["bytes_by_op"]["all-reduce"] == 2 * 16 * 1024 * 4  # 2x payload
    assert r["bytes_by_op"]["all-gather"] == 8 * 512 * 2
    assert r["bytes_by_op"]["all-to-all"] == 2 * 16 * 2
    assert r["bytes_by_op"]["collective-permute"] == 100
