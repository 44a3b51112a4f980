"""Serving launcher: batched decode with optional W4 weights + FP4/8 KV.

Demonstrates the paper's deployment path end-to-end at reduced scale:
quantize a trained (or randomly initialized) LM to packed W4, prefill a
prompt batch, then decode tokens against the (optionally quantized) KV
cache. The same step functions are what the dry-run lowers at production
scale.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.clock import wall_clock
from repro.common.compile_cache import setup_compile_cache
from repro.configs.registry import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_decode_fn, quantize_lm_for_serving
from repro.models.lm import forward, init_caches, lm_init
from repro.quant.calibrate import QuantContext
from repro.quant.fakequant import KIND_FP_SIGNED, QuantizerParams


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--quant", default="bf16",
                    choices=["bf16", "w4", "w4pc"],
                    help="w4 = per-tensor scales; w4pc = per-output-channel")
    ap.add_argument("--kv", default="bf16", choices=["bf16", "fp8", "fp4"])
    ap.add_argument("--act-quant", default="off", choices=["off", "fp4"],
                    help="fp4 = fuse E2M1 activation quant into the W4 "
                         "matmul kernel (W4A4 serving)")
    ap.add_argument("--act-maxval", type=float, default=6.0,
                    help="per-tensor activation grid max for --act-quant "
                         "(deployment default; calibration would refine it)")
    ap.add_argument("--greedy", action="store_true", default=True)
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    cfg = dataclasses.replace(cfg, kv_dtype=args.kv)
    mesh = make_host_mesh()
    s_max = args.prompt_len + args.gen_len

    with jax.set_mesh(mesh):
        key = jax.random.PRNGKey(0)
        params = lm_init(key, cfg)
        if args.quant in ("w4", "w4pc"):
            t0 = wall_clock()
            params = quantize_lm_for_serving(
                params, searched=False, per_channel=(args.quant == "w4pc"))
            print(f"quantized to W4 ({args.quant}) in {wall_clock() - t0:.1f}s")
        ctx = None
        if args.act_quant == "fp4" and args.quant == "bf16":
            print("note: --act-quant fp4 with --quant bf16 quantizes "
                  "activations in a standalone msfp pass (A4 only; no "
                  "packed weights to fuse into)")
        if args.act_quant == "fp4":
            # Fused W4A4: every packed dense site quantizes its input to
            # signed E2M1 inside the matmul kernel (no separate qdq pass);
            # bf16-fallback sites quantize in a standalone pass so serving
            # numerics track the fake-quant model at every act site.
            qp = QuantizerParams(KIND_FP_SIGNED, 2, 1, 4,
                                 jnp.float32(args.act_maxval))
            ctx = QuantContext("serve", act_qps={"*": qp})
        prompts = jax.random.randint(key, (args.batch, args.prompt_len),
                                     0, cfg.vocab)
        extra = (jnp.zeros((args.batch, cfg.n_img_tokens, cfg.d_vision),
                           cfg.dtype) if cfg.family == "vlm" else None)
        caches = init_caches(cfg, args.batch, s_max)
        dec = jax.jit(make_decode_fn(cfg, ctx=ctx))

        # prefill by stepping the prompt (teacher-forced decode fills caches)
        t0 = wall_clock()
        logits = None
        for i in range(args.prompt_len):
            logits, caches = dec(params, caches, prompts[:, i:i + 1],
                                 jnp.int32(i))
        prefill_s = wall_clock() - t0

        out_tokens = []
        t0 = wall_clock()
        tok = jnp.argmax(logits[:, -1:], axis=-1)
        for i in range(args.gen_len):
            out_tokens.append(np.asarray(tok)[:, 0])
            logits, caches = dec(params, caches, tok,
                                 jnp.int32(args.prompt_len + i))
            tok = jnp.argmax(logits[:, -1:], axis=-1)
        jax.block_until_ready(logits)
        decode_s = wall_clock() - t0
        gen = np.stack(out_tokens, axis=1)
        print(f"arch={cfg.name} quant={args.quant} act={args.act_quant} "
              f"kv={args.kv}")
        print(f"prefill: {prefill_s:.2f}s  decode: {decode_s:.2f}s "
              f"({args.gen_len * args.batch / max(decode_s, 1e-9):.1f} tok/s)")
        print("sample ids:", gen[0][:16].tolist())


if __name__ == "__main__":
    main()
