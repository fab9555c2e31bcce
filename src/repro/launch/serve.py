"""Production serving launcher: batched decode against a KV/state cache.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
        --batch 4 --steps 32

Production meshes are validated compile-only via launch/dryrun.py (decode_32k
and long_500k cells lower exactly this serve_step).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

import repro.models as M
from repro.checkpoint import Checkpointer, latest_step
from repro.configs import get_config, reduce_config
from repro.launch.compile_cache import use_compile_cache
from repro.serve import ServeEngine


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=32,
                    help="new tokens per sequence")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--ckpt-dir", default="",
                    help="restore params from a training checkpoint")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    params = M.init_params(jax.random.PRNGKey(args.seed), cfg, jnp.float32)
    if args.ckpt_dir:
        ck = Checkpointer(args.ckpt_dir)
        step = latest_step(args.ckpt_dir)
        # restore params only; optimizer state is discarded for serving
        from repro.optim import sgd
        params, _, _ = ck.restore(params, sgd().init(params), step)
        print(f"[serve] restored params from step {step}")

    print(f"[serve] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"family={cfg.family}, batch={args.batch}")
    engine = ServeEngine(cfg, params, max_len=args.max_len,
                         temperature=args.temperature)
    rng = jax.random.PRNGKey(args.seed)
    prompts = jax.random.randint(
        rng, (args.batch, args.prompt_len), 1, cfg.vocab_size, jnp.int32)

    t0 = time.perf_counter()
    out = engine.generate(prompts, args.steps, rng=rng)
    dt = time.perf_counter() - t0
    total_new = args.batch * args.steps
    print(f"[serve] {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s incl. prefill)")
    for b in range(min(2, args.batch)):
        print(f"  seq{b}: {out[b].tolist()}")


if __name__ == "__main__":
    main()
