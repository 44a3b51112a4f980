"""Training launcher: --arch <id> [--smoke] with the fault-tolerant trainer.

On this CPU container it runs the reduced configs end-to-end (the
``examples/train_lm.py`` driver trains a ~100M-class model for a few
hundred steps); on a real fleet the same entry point runs the full config
on the production mesh — the mesh/sharding path is identical, only the
device count differs.
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.checkpoint.ckpt import CheckpointManager
from repro.common.compile_cache import setup_compile_cache
from repro.configs.registry import get_config
from repro.data.synthetic import zipf_tokens
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.sharding import data_spec, param_shardings
from repro.launch.steps import make_train_step
from repro.models.lm import lm_init
from repro.optim.adam import AdamConfig, adam_init
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else make_host_mesh(args.model_parallel))
    acfg = AdamConfig(lr=args.lr, schedule="linear_warmup_cosine",
                      warmup_steps=max(10, args.steps // 20),
                      total_steps=args.steps)
    ckpt = CheckpointManager(os.path.join(args.ckpt_dir, cfg.name), keep=3)

    with jax.set_mesh(mesh):
        params = lm_init(jax.random.PRNGKey(0), cfg)
        ps = param_shardings(params, mesh)
        params = jax.tree.map(jax.device_put, params, ps)
        opt = adam_init(params, acfg)
        step = make_train_step(cfg, acfg)

        @jax.jit
        def step_fn(state, batch):
            params, opt = state
            params, opt, metrics = step(params, opt, batch)
            return (params, opt), metrics

        def data():
            key = jax.random.PRNGKey(1)
            bspec = NamedSharding(mesh, data_spec((args.batch, args.seq), mesh))
            while True:
                key, k = jax.random.split(key)
                toks = zipf_tokens(k, args.batch, args.seq, cfg.vocab)
                batch = {"tokens": jax.device_put(toks, bspec)}
                if cfg.family == "vlm":
                    batch["extra"] = jnp.zeros(
                        (args.batch, cfg.n_img_tokens, cfg.d_vision),
                        jnp.bfloat16)
                yield batch

        tcfg = TrainerConfig(max_steps=args.steps, ckpt_every=args.ckpt_every,
                             log_every=20)
        trainer = Trainer(tcfg, ckpt, step_fn)
        state, history = trainer.run((params, opt), data())
        losses = [r.metrics.get("loss", float("nan")) for r in history]
        print(f"arch={cfg.name} steps={len(history)} "
              f"loss[0]={losses[0]:.4f} loss[-1]={losses[-1]:.4f} "
              f"stragglers={trainer.straggler_steps()}")


if __name__ == "__main__":
    main()
