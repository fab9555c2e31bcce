"""Mesh-sharding benchmarks: weak scaling + Gram ring vs replicated.

Three claims from the mesh-aware dispatch (see the mesh note in
``repro.kernels.ops``) are tracked per PR:

1. *Weak scaling*: with a fixed per-device batch, wall-clock of the
   signature forward+grad under ``sharding_ctx(make_sig_mesh(P))`` should be
   ~flat in P.  Inputs are committed to the mesh with ``jax.device_put``
   BEFORE timing — an uncommitted host array is re-scattered on every call,
   which measures the transfer, not the compute (that resharding was the
   bulk of the historical P=8 cliff).  On CPU the 8 "devices" share the
   same cores, so the CPU numbers measure dispatch overhead, not speedup —
   the *trajectory* (and the TPU run of the same file) is the claim.
2. *Retrace-free dispatch*: the sweep calls each sharded entry point
   repeatedly per P; the ``pathsig_jit_traces_total`` counters snapshotted
   into the JSON must show one compile per (site, shape) — the jit-cache
   test in ``tests/test_shard.py`` enforces it, the bench records it.
3. *Ring communication law*: the cross-device Gram moves O(B·D_sig) bytes
   over collective-permutes — measured from lowered HLO via
   ``repro.distributed.hlo.collective_stats`` and compared against the
   would-be replicated spellings (all-gather of Y: B·D_sig result bytes;
   elementwise blow-up: B_x·B_y·D_sig).  A ring-vs-oracle *crossover
   curve* over B shows where the double-buffered ring overtakes the
   replicated oracle.

Every record lands in ``BENCH_shard.json`` (cwd), matching the other
suites, so CI uploads it with the rest.  On the CPU the module re-executes
itself in a subprocess with 8 forced host devices (XLA locks the device
count at first init, so the in-process ``run()`` entry point cannot force
it).  Where real devices are present it runs in the calling process on all
of them: a chip belongs to the process that touched JAX first, so a child
could not reach it.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

N_DEV = 8
_FLAGS = f"--xla_force_host_platform_device_count={N_DEV}"
JSON_PATH = os.environ.get("PATHSIG_BENCH_JSON_SHARD", "BENCH_shard.json")


def run(quick: bool = True) -> None:
    """benchmarks.run entry point: in process on real devices, re-exec with
    forced host devices on the CPU."""
    import jax
    if jax.default_backend() != "cpu":
        _bench(quick)
        return
    env = dict(os.environ, XLA_FLAGS=_FLAGS)
    cmd = [sys.executable, "-m", "benchmarks.shard_scaling", "--inner"]
    if quick:
        cmd.append("--quick")
    r = subprocess.run(cmd, env=env)
    if r.returncode:
        raise RuntimeError(f"shard_scaling subprocess failed ({r.returncode})")


def _bench(quick: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro import obs
    from repro.core.words import sig_dim
    from repro.distributed import collective_stats, sharding_ctx
    from repro.kernels import ops
    from repro.launch.mesh import make_sig_mesh
    from repro.sigkernel import sig_gram, word_weights

    from .common import header, make_paths, row, time_fn

    n_dev = len(jax.devices())
    # forced host "devices" timeslice the machine's physical cores: the
    # ideal weak-scaling time at P shards is P·t1·min(1, cores/P) — on a
    # box with >= 8 cores that reduces to the classic flat-t1 ideal, on a
    # 1-core box to the serial bound P·t1.  Efficiency is measured against
    # that ideal so the number isolates the dispatch/resharding overhead
    # the PR controls (a real TPU run of this file has cores >= P and
    # reduces to the textbook definition).
    n_cores = os.cpu_count() or 1
    out = {"devices": n_dev, "host_cores": n_cores,
           "weak_scaling": [], "gram_ring": {}}

    # --- 1. weak scaling: fixed per-device batch -------------------------
    header("shard weak scaling (per-device batch fixed, committed inputs)")
    b_dev, M, d, depth = (32, 128, 3, 4) if quick else (32, 256, 4, 4)
    iters = 3 if quick else 5
    obs.enable()
    obs.reset()
    t1 = None
    for P in (p for p in (1, 2, 4, 8) if p <= n_dev):
        mesh = make_sig_mesh(P)
        x = make_paths(b_dev * P, M, d, seed=0)
        # commit the batch-sharded increments to the mesh BEFORE timing:
        # an uncommitted array is host-scattered again on every call
        incs = jax.device_put(
            jnp.diff(x, axis=1),
            NamedSharding(mesh, PartitionSpec("data", None, None)))

        def fwd_bwd(a):
            return jax.grad(lambda z: ops.signature(
                z, depth, backend="auto").sum())(a)

        with sharding_ctx(mesh):
            t = time_fn(jax.jit(fwd_bwd), incs, warmup=2, iters=iters)
        t1 = t if t1 is None else t1
        ideal = t1 * max(1.0, P / n_cores)   # timesliced-host ideal
        eff = ideal / t if t > 0 else 0.0
        eff_raw = t1 / t if t > 0 else 0.0
        tag = f"P={P};B={b_dev * P};M={M};d={d};N={depth}"
        row("shard/weak_fwdbwd", f"{t * 1e3:.3f}", "ms",
            f"{tag};eff={eff:.3f}")
        out["weak_scaling"].append({"P": P, "B": b_dev * P, "M": M, "d": d,
                                    "depth": depth, "ms": t * 1e3,
                                    "ideal_ms": ideal * 1e3,
                                    "efficiency_vs_P1": eff,
                                    "efficiency_raw_t1_over_t": eff_raw})
    # compile-per-shape accounting over the whole sweep (claim 2): every
    # (site, shapes) label pair should sit at 1 — recorded, and enforced by
    # tests/test_shard.py
    snap = obs.snapshot()["metrics"].get("pathsig_jit_traces_total", {})
    out["jit_traces"] = snap.get("values", [])

    # --- 2. Gram ring vs replicated --------------------------------------
    header(f"gram ring vs replicated ({n_dev}-device mesh)")
    B, gd, gN = (64, 3, 4) if quick else (256, 4, 4)
    D = sig_dim(gd, gN)
    w = jnp.asarray(word_weights(gd, gN))
    mesh = make_sig_mesh(n_dev)

    def ring(a):
        return sig_gram(a, None, gN, route="tiled", backend="jax")

    def oracle(a):
        return sig_gram(a, None, gN, route="oracle", backend="jax")

    # crossover curve: ring (O(B·D) wire, P partial tiles) vs replicated
    # oracle over growing B — the ring's per-step latency is amortised once
    # the per-shard tiles are large enough to hide the permutes
    curve = []
    bs = (16, 32, 64, 128) if quick else (64, 128, 256, 512)
    with sharding_ctx(mesh):
        for Bc in bs:
            Xc = make_paths(Bc, M, gd, seed=1)
            tr = time_fn(jax.jit(ring), Xc, warmup=1, iters=iters)
            to = time_fn(jax.jit(oracle), Xc, warmup=1, iters=iters)
            row("shard/gram_crossover", f"{tr * 1e3:.3f}", "ms",
                f"B={Bc};D={D};oracle={to * 1e3:.3f}ms")
            curve.append({"B": Bc, "D_sig": D, "ring_ms": tr * 1e3,
                          "oracle_ms": to * 1e3,
                          "ring_over_oracle": tr / to if to > 0 else 0.0})

        X = make_paths(B, M, gd, seed=1)
        t_ring = time_fn(jax.jit(ring), X, warmup=1, iters=iters)
        t_oracle = time_fn(jax.jit(oracle), X, warmup=1, iters=iters)
        a = np.asarray(jax.jit(ring)(X))
        b = np.asarray(jax.jit(oracle)(X))
        err = float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-12))

        Sx = jnp.asarray(np.random.default_rng(0).standard_normal(
            (B, D)).astype(np.float32))
        txt = jax.jit(lambda s: ops.gram(s, s, w, backend="jax")
                      ).lower(Sx).compile().as_text()
    st = collective_stats(txt, default_group=n_dev)
    permute_wire = st.by_kind.get("collective-permute", (0, 0, 0.0))[2]
    ag_result = st.by_kind.get("all-gather", (0, 0.0, 0.0))[1]
    replicated_y = B * D * 4                    # all-gather-of-Y spelling
    blowup = B * B * D * 4                      # elementwise spelling
    row("shard/ring_ms", f"{t_ring * 1e3:.3f}", "ms", f"B={B};D={D}")
    row("shard/oracle_ms", f"{t_oracle * 1e3:.3f}", "ms", f"B={B};D={D}")
    row("shard/ring_vs_oracle_relerr", f"{err:.2e}", "rel", "")
    row("shard/permute_wire", f"{permute_wire / 2**20:.3f}", "MiB/dev",
        f"replicated_y={replicated_y / 2**20:.3f}MiB;"
        f"blowup={blowup / 2**20:.1f}MiB")
    assert err < 1e-5, err
    assert ag_result < blowup, (ag_result, blowup)
    out["gram_ring"] = {"B": B, "D_sig": D, "ring_ms": t_ring * 1e3,
                        "oracle_ms": t_oracle * 1e3, "relerr": err,
                        "permute_wire_bytes_per_dev": permute_wire,
                        "allgather_result_bytes": ag_result,
                        "replicated_y_bytes": replicated_y,
                        "elementwise_blowup_bytes": blowup,
                        "crossover": curve,
                        "collectives": {k: list(v)
                                        for k, v in st.by_kind.items()}}

    with open(JSON_PATH, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\n# wrote {JSON_PATH}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--inner", action="store_true",
                    help="already re-executed with forced host devices")
    args = ap.parse_args(argv)
    if args.inner:
        _bench(args.quick)
    else:
        run(quick=args.quick)


if __name__ == "__main__":
    main()
