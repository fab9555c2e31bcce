"""Production training launcher.

On real hardware this process runs once per host (jax.distributed initialises
from the cluster env); on CPU it runs the same code on a 1x1 dev mesh, so the
launch path itself is exercised by tests and examples.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b \
        --reduced --steps 20 --batch 4 --seq 64 --ckpt-dir runs/ckpt

Production invocation (per pod host):
    python -m repro.launch.train --arch llama3-405b --mesh 16x16 \
        --batch 256 --seq 4096 --opt adafactor --remat dots \
        --ckpt-dir gs://bucket/run1 --microbatch 4 --compress-grads
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.models as M
from repro.checkpoint import Checkpointer, latest_step
from repro.configs import get_config, reduce_config
from repro.data.pipeline import ShardedLoader, TokenStream
from repro.distributed.ctx import sharding_ctx
from repro.distributed.sharding import batch_specs, opt_state_specs, \
    param_specs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import auto_mesh
from repro.optim import adafactor, adamw, linear_warmup_cosine
from repro.train import make_train_step


def parse_mesh(spec: str):
    dims = tuple(int(x) for x in spec.lower().split("x"))
    n = int(np.prod(dims))
    if n > len(jax.devices()):
        raise SystemExit(f"mesh {spec} needs {n} devices, have "
                         f"{len(jax.devices())} (use launch/dryrun.py for "
                         f"compile-only validation of production meshes)")
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}[len(dims)]
    return auto_mesh(dims, axes)


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="family-preserving reduced config (CPU)")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--opt", default="adamw", choices=["adamw", "adafactor"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default="dots",
                    choices=["dots", "full", "none"])
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    mesh = parse_mesh(args.mesh)
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}, "
          f"batch={args.batch}x{args.seq}")

    opt = (adamw if args.opt == "adamw" else adafactor)(
        lr=linear_warmup_cosine(args.lr, max(1, args.steps // 10),
                                args.steps))

    with sharding_ctx(mesh, {}):
        params_sds = jax.eval_shape(
            lambda: M.init_params(jax.random.PRNGKey(args.seed), cfg,
                                  jnp.float32))
        p_specs = param_specs(params_sds, mesh, {})
        o_specs = opt_state_specs(jax.eval_shape(opt.init, params_sds),
                                  p_specs, mesh)
        params = jax.jit(
            lambda k: M.init_params(k, cfg, jnp.float32),
            out_shardings=p_specs)(jax.random.PRNGKey(args.seed))
        opt_state = jax.jit(opt.init, out_shardings=o_specs)(params)

        step_fn = jax.jit(
            make_train_step(cfg, opt, remat=args.remat,
                            microbatch=args.microbatch),
            in_shardings=(p_specs, o_specs, None),
            out_shardings=(p_specs, o_specs, None),
            donate_argnums=(0, 1))

        ckpt = None
        start = 0
        if args.ckpt_dir:
            ckpt = Checkpointer(args.ckpt_dir,
                                process_index=jax.process_index(),
                                process_count=jax.process_count())
            if args.resume and latest_step(args.ckpt_dir) is not None:
                start = latest_step(args.ckpt_dir)
                params, opt_state, _ = ckpt.restore(params, opt_state, start)
                print(f"[train] resumed from step {start}")

        stream = ShardedLoader(
            TokenStream(cfg.vocab_size, args.batch, args.seq, args.seed,
                        step=start),
            jax.process_index(), jax.process_count())

        tokens_per_step = args.batch * args.seq
        for step in range(start, args.steps):
            t0 = time.perf_counter()
            batch = next(stream)
            params, opt_state, m = step_fn(params, opt_state, batch)
            jax.block_until_ready(m["loss"])
            dt = time.perf_counter() - t0
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"  step {step:>5} loss {float(m['loss']):.4f} "
                      f"|g| {float(m['grad_norm']):.3f} "
                      f"{tokens_per_step/dt:,.0f} tok/s")
            if ckpt and args.ckpt_every and step and \
                    step % args.ckpt_every == 0:
                ckpt.save(params, opt_state, step,
                          extra={"data": stream.state()})
        if ckpt:
            ckpt.save(params, opt_state, args.steps,
                      extra={"data": stream.state()})
            ckpt.wait()
    print("[train] done")


if __name__ == "__main__":
    main()
