"""Run the signature stack once on a TPU and check it against float32 JAX.

    python chip_smoke.py              # one chip: phases (a)-(f)
    python chip_smoke.py --chips 4    # four chips: the sharded training
                                      # step and the ring Gram only

Every phase calls public entry points with ``backend="auto"`` (the compiled
Pallas kernels on a TPU) and compares the result with ``backend="jax"``
(the plain float32 engine) computed on the same chip, to the tolerance the
tests use for float32 kernel accumulation: rtol 1e-4, atol 1e-5 of the
reference's largest magnitude.  XLA's own matmuls run at float32
precision on both sides.  Each phase asserts that its compiled program
holds a Pallas kernel (``tpu_custom_call``): the kernel ran, not a
fallback.  It prints one line per phase (shapes, max error scaled to the
reference's largest magnitude, compile and warm seconds; the times are
for information) and, when every phase passed, the device as its last
line.  It refuses to run without a TPU and catches nothing: any failure
exits non-zero.  Inputs and weights are random, drawn from ``--seed``.

Compiled programs go to JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

RTOL, ATOL = 1e-4, 1e-5
BACKEND, REFERENCE = "auto", "jax"
KERNEL = "tpu_custom_call"


def check_kernel(compiled) -> None:
    assert KERNEL in compiled.as_text(), "no Pallas kernel in the program"


def max_error(name: str, got, want) -> float:
    """Assert ``got`` matches ``want`` leaf by leaf; -> the largest error
    as a fraction of the reference's largest magnitude."""
    worst = 0.0
    g_leaves, w_leaves = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(g_leaves) == len(w_leaves), name
    for g, w in zip(g_leaves, w_leaves):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert g.shape == w.shape, (name, g.shape, w.shape)
        assert np.isfinite(g).all(), f"{name}: non-finite output"
        scale = max(float(np.abs(w).max(initial=0.0)), 1.0)
        np.testing.assert_allclose(g, w, rtol=RTOL, atol=ATOL * scale,
                                   err_msg=name)
        worst = max(worst, float(np.abs(g - w).max(initial=0.0)) / scale)
    return worst


def run_compiled(fn, *args, devices: int = 1):
    """Compile ``fn`` for ``args``, check for the kernel (and that the
    program spans ``devices``), run it twice.
    -> (output, compile seconds, warm seconds)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t_compile = time.perf_counter() - t0
    check_kernel(compiled)
    if devices > 1:
        assert f"num_partitions={devices}" in compiled.as_text(), \
            f"the program does not span {devices} devices"
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, t_compile, time.perf_counter() - t0


def report(phase: str, shapes: str, err: float, t_compile: float,
           t_warm: float) -> None:
    print(f"[{phase}] {shapes} max_err={err:.3e} compile_s={t_compile:.2f} "
          f"warm_s={t_warm:.4f}", flush=True)


def with_grad(fn):
    """x -> (fn(x), gradient of ½‖fn(x)‖² w.r.t. x)."""
    def f(*args):
        out, vjp = jax.vjp(fn, *args)
        return out, vjp(out)
    return f


def random_paths(key, B: int, M: int, d: int):
    """(B, M+1, d) random walks of unit total variance from 0."""
    steps = jax.random.normal(key, (B, M, d)) / np.sqrt(M)
    return jnp.concatenate([jnp.zeros((B, 1, d)), jnp.cumsum(steps, 1)], 1)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def train_step(backend: str, depth: int, lr: float = 0.1):
    """One SGD step of a linear readout on the signature of a learned
    channel mix, differentiated through the §4.2 inverse backward."""
    from repro.kernels import ops

    def loss(p, incs, y):
        sig = ops.signature(incs @ p["mix"], depth, backend=backend,
                            backward="inverse")
        return jnp.mean((sig @ p["w"] - y) ** 2)

    def step(p, incs, y):
        value, grad = jax.value_and_grad(loss)(p, incs, y)
        return value, grad, jax.tree.map(lambda a, g: a - lr * g, p, grad)
    return step


def train_inputs(key, B: int, M: int, d: int, depth: int):
    from repro.core.words import sig_dim
    k = jax.random.split(key, 4)
    D = sig_dim(d, depth)
    params = {"mix": jnp.eye(d) + 0.1 * jax.random.normal(k[0], (d, d)),
              "w": jax.random.normal(k[1], (D,)) / np.sqrt(D)}
    incs = jax.random.normal(k[2], (B, M, d)) / np.sqrt(M)
    return params, incs, jax.random.normal(k[3], (B,))


def phase_train(key, B=1024, M=500, d=4, depth=5):
    """(a) the paper's training step: forward + §4.2 backward + update."""
    args = train_inputs(key, B, M, d, depth)
    got, tc, tw = run_compiled(train_step(BACKEND, depth), *args)
    want = jax.jit(train_step(REFERENCE, depth))(*args)
    report("a train", f"B={B} M={M} d={d} depth={depth}",
           max_error("train", got, want), tc, tw)


def phase_stream(key, B=32, M=2048, d=4, depth=4, window=256, stride=8):
    """(b) every prefix signature, and sliding windows by Chen's identity
    over that stream, with gradients."""
    from repro.core import sliding_windows, windowed_signature
    from repro.kernels import ops
    path = random_paths(key, B, M, d)
    incs = jnp.diff(path, axis=1)

    def streamed(backend):
        return with_grad(lambda x: ops.signature(x, depth, backend=backend,
                                                 stream=True))
    got, tc, tw = run_compiled(streamed(BACKEND), incs)
    want = jax.jit(streamed(REFERENCE))(incs)
    report("b stream", f"B={B} M={M} d={d} depth={depth} stride=1",
           max_error("stream", got, want), tc, tw)

    windows = sliding_windows(M, window, stride=stride)

    def windowed(backend):
        return with_grad(lambda p: windowed_signature(
            p, windows, depth, route="chen", backend=backend))
    got, tc, tw = run_compiled(windowed(BACKEND), path)
    want = jax.jit(windowed(REFERENCE))(path)
    report("b windows", f"B={B} M={M} K={len(windows)} window={window} "
           f"stride={stride} route=chen", max_error("windows", got, want),
           tc, tw)


def phase_projected(key, B=512, M=200, d=4):
    """(c) projected signatures over multi-tile word sets, with gradients."""
    from repro.core.words import all_words, anisotropic_words
    from repro.kernels import ops
    incs = jax.random.normal(key, (B, M, d)) / np.sqrt(M)
    sets = {"all words depth 4": all_words(d, 4),
            "anisotropic (1,1,2,2) r=6": anisotropic_words((1, 1, 2, 2), 6)}
    for name, words in sets.items():
        words = tuple(words)
        tiles = len(ops._closure_tiled_plan(words, d, 256).tiles)

        def proj(backend):
            return with_grad(lambda x: ops.projected(x, words,
                                                     backend=backend))
        got, tc, tw = run_compiled(proj(BACKEND), incs)
        want = jax.jit(proj(REFERENCE))(incs)
        report("c projected", f"B={B} M={M} d={d} {name}: {len(words)} "
               f"words, {tiles} tiles", max_error(name, got, want), tc, tw)


def phase_mmd(key, B=1024, M=100, d=4, depth=4):
    """(d) signature MMD² between two samples and its gradient."""
    from repro.sigkernel import sig_mmd
    kx, ky = jax.random.split(key)
    X, Y = random_paths(kx, B, M, d), 1.1 * random_paths(ky, B, M, d)

    def mmd(backend):
        return jax.value_and_grad(
            lambda x, y: sig_mmd(x, y, depth, backend=backend))
    got, tc, tw = run_compiled(mmd(BACKEND), X, Y)
    want = jax.jit(mmd(REFERENCE))(X, Y)
    report("d mmd", f"Bx=By={B} M={M} d={d} depth={depth}",
           max_error("mmd", got, want), tc, tw)


def phase_batcher(seed: int, n=512, d=3, depth=4, max_len=1024):
    """(e) ragged requests through ``DynamicBatcher.signature_service``."""
    from repro.data import geometric_lengths
    from repro.kernels import ops
    from repro.ragged import RaggedPaths
    from repro.serve import DynamicBatcher
    lengths = geometric_lengths(seed, n, max_len)
    rng = np.random.default_rng(seed)
    paths = [np.concatenate([np.zeros((1, d), np.float32), np.cumsum(
        rng.standard_normal((L, d)).astype(np.float32) / np.sqrt(L), 0)])
        for L in lengths]
    svc = DynamicBatcher.signature_service(d, depth, max_len=max_len,
                                           backend=BACKEND)

    def serve():
        tickets = [svc.submit(p) for p in paths]
        out = svc.flush()
        return jax.block_until_ready(jnp.stack([out[t] for t in tickets]))
    t0 = time.perf_counter()
    serve()                         # compiles every (rung, batch) shape
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = serve()
    t_warm = time.perf_counter() - t0
    rung, rows = max(svc.shapes_seen)
    check_kernel(jax.jit(svc.compute).lower(RaggedPaths(
        values=jnp.zeros((rows, rung + 1, d)),
        lengths=jnp.zeros((rows,), jnp.int32))).compile())
    dense = RaggedPaths.from_list(paths)
    want = ops.signature(jnp.diff(dense.values, axis=1), depth,
                         backend=REFERENCE, lengths=dense.lengths)
    report("e batcher", f"{n} requests d={d} depth={depth} lengths "
           f"{lengths.min()}..{lengths.max()} shapes={len(svc.shapes_seen)}",
           max_error("batcher", got, want), t_cold, t_warm)


def phase_sessions(seed: int, n=100_000, d=3, depth=4, rounds=3,
                   ticking=10_000):
    """(f) a pool of ``n`` sessions taking a few flushes of bursty ticks."""
    from repro.core.stream import stream_extend, stream_scatter, stream_take
    from repro.data import session_tick_stream
    from repro.serve import SessionStore
    sids = [f"u{i}" for i in range(n)]
    stream = session_tick_stream(n, d, seed=seed,
                                 tick_prob=ticking / (6.0 * n))
    traffic = [next(stream) for _ in range(rounds)]
    stores = {b: SessionStore(d, depth, initial_sessions=n, backend=b)
              for b in (BACKEND, REFERENCE)}
    times = []                      # the traffic twice: cold, then warm
    for store in stores.values():
        store.create_many(sids)
        for _ in range(2):
            t0 = time.perf_counter()
            for r in traffic:
                store.ingest_many(r["sids"], r["counts"], r["ticks"])
                store.flush()
            jax.block_until_ready(store.pool.sig)
            times.append(time.perf_counter() - t0)
    store = stores[BACKEND]
    rung, rows = max(store.stats()["flush_shapes"])

    def flush_step(carry, slots, incs, counts):     # SessionStore's update
        sub = stream_extend(stream_take(carry, slots), incs, counts=counts,
                            backend=BACKEND)
        return stream_scatter(carry, slots, sub)
    check_kernel(jax.jit(flush_step).lower(
        store.pool, jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows, rung, d)), jnp.ones((rows,), jnp.int32)).compile())
    got = store.block_features(sids)
    want = stores[REFERENCE].block_features(sids)
    ticks = sum(int(r["counts"].sum()) for r in traffic)
    report("f sessions", f"{n} sessions d={d} depth={depth} 2x{rounds} "
           f"flushes of {ticks} ticks, "
           f"shapes={len(store.stats()['flush_shapes'])}",
           max_error("sessions", got, want), times[0], times[1])


def phase_four_chips(key, devices, B=1024, M=500, d=4, depth=5, gram_B=1024,
                     gram_M=100, gram_depth=4):
    """The sharded training step and the ring Gram on a mesh over
    ``devices``, each against its one-device result."""
    from repro.distributed import sharding_ctx
    from repro.launch.mesh import make_sig_mesh
    from repro.sigkernel import sig_gram
    mesh = make_sig_mesh(len(devices))
    args = train_inputs(key, B, M, d, depth)
    one = jax.jit(train_step(BACKEND, depth))(*args)
    with sharding_ctx(mesh):
        got, tc, tw = run_compiled(train_step(BACKEND, depth), *args,
                                   devices=len(devices))
    report(f"train x{len(devices)}", f"B={B} M={M} d={d} depth={depth}",
           max_error("sharded train", got, one), tc, tw)

    X = random_paths(jax.random.fold_in(key, 1), gram_B, gram_M, d)

    def gram():  # a fresh function per jit: jit's trace cache cannot see
        return lambda x: sig_gram(  # the sharding context
            x, None, gram_depth, route="tiled", backend=BACKEND)
    one = jax.jit(gram())(X)
    with sharding_ctx(mesh):
        got, tc, tw = run_compiled(gram(), X, devices=len(devices))
        text = jax.jit(gram()).lower(X).as_text()
    assert "collective_permute" in text or "collective-permute" in text, \
        "the Gram did not run as a ring"
    report(f"gram ring x{len(devices)}", f"B={gram_B} M={gram_M} d={d} "
           f"depth={gram_depth}", max_error("ring gram", got, one), tc, tw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1
    key = jax.random.PRNGKey(args.seed)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("float32"):
        if args.chips == 4:
            phase_four_chips(key, devices[:4])
        else:
            phase_train(key)
            phase_train(key, B=1024, M=100, d=6, depth=5)
            phase_stream(key)
            phase_projected(key)
            phase_mmd(key)
            phase_batcher(args.seed)
            phase_sessions(args.seed)
    print(f"# all phases passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
