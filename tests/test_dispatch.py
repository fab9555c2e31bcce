"""Engine-dispatch tests: every backend is differentiable (the grad-through-
kernels regression), cross-engine golden agreement vs the exp/Chen oracle,
and dtype/shape edge cases (B=1, M=1, float64)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as C
from repro.core import tensor_ops as tops
from repro.core.words import make_plan
from repro.kernels import ops

BACKENDS = ["jax", "pallas", "pallas_interpret", "auto"]
WORDS = [(0,), (2, 1), (1, 1, 0), (0, 0, 1)]


def _incs(seed, B, M, d, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(B, M, d)).astype(dtype) * 0.3)


def _plan():
    return make_plan(WORDS, 3)


def _grad(backend, loss, x):
    """``jax.grad(loss)(x)``; None where ``backend="pallas"`` has no TPU,
    after checking that it raises instead of falling back to interpret
    mode."""
    if backend == "pallas" and jax.default_backend() != "tpu":
        with pytest.raises(RuntimeError, match="backend='pallas'"):
            jax.grad(loss)(x)
        return None
    return jax.grad(loss)(x)


# ---------------------------------------------------------------------------
# regression: jax.grad succeeds through EVERY backend string (the docstring
# used to promise this while the Pallas path raised AssertionError)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_grad_through_signature_every_backend(backend):
    x = _incs(0, 2, 7, 3)
    g = _grad(backend, lambda z: ops.signature(z, 3, backend=backend,
                                               batch_tile=8).sum(), x)
    if g is None:
        return
    assert g.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.max(jnp.abs(g))) > 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_grad_through_projected_every_backend(backend):
    x = _incs(1, 2, 7, 3)
    g = _grad(backend, lambda z: ops.projected(z, _plan(), backend=backend,
                                               batch_tile=8).sum(), x)
    if g is None:
        return
    assert g.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(g)))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("backward", ["inverse", "checkpoint", "autodiff"])
def test_grad_every_backend_backward_combination(backend, backward):
    x = _incs(2, 2, 9, 2)
    g = _grad(backend, lambda z: ops.signature(z, 3, backend=backend,
                                               backward=backward,
                                               batch_tile=8).sum(), x)
    if g is None:
        return
    assert g.shape == x.shape and bool(jnp.all(jnp.isfinite(g)))


def test_unknown_backend_and_backward_raise():
    x = _incs(3, 1, 4, 2)
    with pytest.raises(ValueError, match="unknown backend"):
        ops.signature(x, 2, backend="cuda")
    with pytest.raises(ValueError, match="unknown backward"):
        ops.signature(x, 2, backend="pallas_interpret", backward="nope")


@pytest.mark.parametrize("backend", BACKENDS)
def test_grad_through_streamed_signature_every_backend(backend):
    x = _incs(4, 2, 7, 3)
    g = _grad(backend, lambda z: ops.signature(
        z, 3, backend=backend, batch_tile=8, stream=True).sum(), x)
    if g is None:
        return
    assert g.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.max(jnp.abs(g))) > 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_grad_through_streamed_projected_every_backend(backend):
    x = _incs(5, 2, 7, 3)
    g = _grad(backend, lambda z: ops.projected(
        z, _plan(), backend=backend, batch_tile=8, stream=True).sum(), x)
    if g is None:
        return
    assert g.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(g)))


def test_stream_through_core_entry_point_routes_to_pallas():
    """stream=True + pallas used to silently drop to the JAX scan; it now
    routes through dispatch (and unsupported cells raise, see test_stream)."""
    from repro.core.signature import signature_from_increments
    x = _incs(6, 2, 6, 2)
    a = signature_from_increments(x, 3, stream=True, backend="pallas_interpret")
    b = signature_from_increments(x, 3, stream=True, backend="jax")
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_plan_caches_are_content_keyed():
    """Rebuilding an identical WordPlan must hit the same kernel caches
    instead of recompiling (WordPlan hashes by identity, eq=False)."""
    x = _incs(7, 2, 5, 3)
    words = [(0,), (1, 2)]
    p1, p2 = make_plan(words, 3), make_plan(words, 3)
    assert p1 is not p2
    ops.projected(x, p1, backend="pallas_interpret", batch_tile=8)
    before = ops._pallas_proj_inverse.cache_info()
    ops.projected(x, p2, backend="pallas_interpret", batch_tile=8)
    after = ops._pallas_proj_inverse.cache_info()
    assert after.currsize == before.currsize
    assert after.hits == before.hits + 1
    # the interned WordPlan is shared, so downstream jit caches are too
    assert ops._plan_for_words(tuple(words), 3) is \
        ops._plan_for_words(tuple(words), 3)


# ---------------------------------------------------------------------------
# cross-engine golden: pallas_interpret vs jax vs the exp/Chen oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,M,d,N", [(3, 12, 3, 4), (1, 9, 2, 3), (2, 1, 3, 3),
                                     (1, 1, 2, 2)])
def test_truncated_cross_engine_values(B, M, d, N):
    x = _incs(B * M + d, B, M, d)
    oracle = tops.signature_exp_chen(x, N)
    a = ops.signature(x, N, backend="jax")
    b = ops.signature(x, N, backend="pallas_interpret", batch_tile=8)
    np.testing.assert_allclose(a, oracle, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(b, oracle, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("B,M", [(3, 12), (1, 9), (2, 1), (1, 1)])
def test_truncated_cross_engine_gradients(B, M):
    x = _incs(10 + B * M, B, M, 3)

    def loss(backend, backward="inverse"):
        return lambda z: jnp.sum(jnp.tanh(
            ops.signature(z, 4, backend=backend, backward=backward,
                          batch_tile=8)))

    g_jax = jax.grad(loss("jax"))(x)
    g_pal = jax.grad(loss("pallas_interpret"))(x)
    g_cp = jax.grad(loss("pallas_interpret", "checkpoint"))(x)
    np.testing.assert_allclose(g_pal, g_jax, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(g_cp, g_jax, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("B,M", [(2, 11), (1, 1)])
def test_projected_cross_engine_values_and_gradients(B, M):
    d = 3
    x = _incs(20 + B * M, B, M, d)
    plan = _plan()
    # values: both engines vs the dense oracle read at the requested words
    dense = tops.signature_exp_chen(x, 3)
    idx = [C.flat_index(w, d) for w in WORDS]
    a = ops.projected(x, plan, backend="jax")
    b = ops.projected(x, plan, backend="pallas_interpret", batch_tile=8)
    np.testing.assert_allclose(a, dense[:, idx], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(b, dense[:, idx], rtol=1e-4, atol=1e-6)
    # gradients agree across engines
    g_jax = jax.grad(lambda z: jnp.sum(jnp.sin(
        ops.projected(z, plan, backend="jax"))))(x)
    g_pal = jax.grad(lambda z: jnp.sum(jnp.sin(
        ops.projected(z, plan, backend="pallas_interpret",
                      batch_tile=8))))(x)
    np.testing.assert_allclose(g_pal, g_jax, rtol=1e-4, atol=1e-6)


def test_projected_checkpoint_backward_matches_inverse():
    x = _incs(30, 2, 13, 3)
    plan = _plan()
    g_inv = jax.grad(lambda z: jnp.sum(
        ops.projected(z, plan, backend="jax", backward="inverse") ** 2))(x)
    g_cp = jax.grad(lambda z: jnp.sum(
        ops.projected(z, plan, backend="jax", backward="checkpoint") ** 2))(x)
    np.testing.assert_allclose(g_cp, g_inv, rtol=1e-4, atol=1e-6)


def test_windowed_cross_engine_values_and_gradients(rng):
    from tests.conftest import make_path
    path = jnp.asarray(make_path(rng, 2, 16, 3))
    windows = np.asarray([[0, 8], [4, 16], [7, 8]], np.int32)
    a = C.windowed_signature(path, windows, 3, backend="jax")
    b = C.windowed_signature(path, windows, 3, backend="pallas_interpret")
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    g_jax = jax.grad(lambda p: jnp.sum(
        C.windowed_signature(p, windows, 3, backend="jax") ** 2))(path)
    g_pal = jax.grad(lambda p: jnp.sum(
        C.windowed_signature(p, windows, 3,
                             backend="pallas_interpret") ** 2))(path)
    np.testing.assert_allclose(g_pal, g_jax, rtol=1e-4, atol=1e-6)


def test_windowed_projection_cross_engine(rng):
    from tests.conftest import make_path
    path = jnp.asarray(make_path(rng, 2, 12, 3))
    windows = np.asarray([[0, 6], [3, 12]], np.int32)
    plan = _plan()
    a = C.windowed_projection(path, windows, plan, backend="jax")
    b = C.windowed_projection(path, windows, plan,
                              backend="pallas_interpret")
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_logsignature_cross_engine(rng):
    from tests.conftest import make_path
    path = jnp.asarray(make_path(rng, 2, 9, 3))
    for fn in (C.logsignature, C.logsignature_projected):
        a = fn(path, 3, backend="jax")
        b = fn(path, 3, backend="pallas_interpret")
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
        ga = jax.grad(lambda p: jnp.sum(fn(p, 3, backend="jax") ** 2))(path)
        gb = jax.grad(lambda p: jnp.sum(
            fn(p, 3, backend="pallas_interpret") ** 2))(path)
        np.testing.assert_allclose(gb, ga, rtol=1e-4, atol=1e-5)


def test_time_parallel_gradients_match():
    x = _incs(40, 2, 13, 3)
    g_plain = jax.grad(lambda z: jnp.sum(
        ops.signature(z, 3, backend="pallas_interpret", batch_tile=8) ** 2))(x)
    g_tp = jax.grad(lambda z: jnp.sum(
        ops.signature(z, 3, backend="pallas_interpret", batch_tile=8,
                      time_chunks=3) ** 2))(x)
    np.testing.assert_allclose(g_tp, g_plain, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# dtype preservation
# ---------------------------------------------------------------------------

def test_float64_dtype_preserved_across_engines():
    try:
        jax.config.update("jax_enable_x64", True)
        x = _incs(50, 2, 7, 3, dtype=np.float64)
        assert x.dtype == jnp.float64
        a = ops.signature(x, 3, backend="jax")
        b = ops.signature(x, 3, backend="pallas_interpret", batch_tile=8)
        assert a.dtype == jnp.float64
        assert b.dtype == jnp.float64  # kernel computes f32, restores dtype
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
        p = ops.projected(x, _plan(), backend="pallas_interpret",
                          batch_tile=8)
        assert p.dtype == jnp.float64
        g = jax.grad(lambda z: ops.signature(
            z, 3, backend="pallas_interpret", batch_tile=8).sum())(x)
        assert g.dtype == jnp.float64
    finally:
        jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# compiled-Pallas-only twin (runs on a real TPU; interpret twin covers CPU)
# ---------------------------------------------------------------------------

@pytest.mark.tpu
def test_compiled_pallas_grad_matches_jax():
    x = _incs(60, 4, 11, 3)
    a = ops.signature(x, 4, backend="pallas")
    b = ops.signature(x, 4, backend="jax")
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    g_pal = jax.grad(lambda z: jnp.sum(
        ops.signature(z, 4, backend="pallas") ** 2))(x)
    g_jax = jax.grad(lambda z: jnp.sum(
        ops.signature(z, 4, backend="jax") ** 2))(x)
    np.testing.assert_allclose(g_pal, g_jax, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# hybrid engine exposure: backend="hybrid" cell (dense W_{<=N-1} + top words)
# ---------------------------------------------------------------------------

def _logsig_shape_words(d, N):
    """The §3.3-shaped set: all words below N plus Lyndon words at N."""
    return tuple(C.all_words(d, N - 1) +
                 [w for w in C.lyndon_words(d, N) if len(w) == N])


def test_hybrid_backend_golden_vs_dense_logsig_shape():
    d, N = 3, 4
    plan = make_plan(_logsig_shape_words(d, N), d)
    x = _incs(3, 4, 18, d)
    a = np.asarray(ops.projected(x, plan, backend="hybrid"))
    b = np.asarray(ops.projected(x, plan, backend="jax"))
    np.testing.assert_allclose(a, b, atol=1e-5 * max(np.abs(b).max(), 1.0))


def test_hybrid_backend_golden_arbitrary_mixed_set():
    # requested words at several levels, unsorted, with a duplicate level-N
    words = ((1, 0, 2), (0,), (2, 1), (0, 0, 0), (1,), (1, 0, 2))
    plan = make_plan(words, 3)
    x = _incs(4, 3, 15, 3)
    a = np.asarray(ops.projected(x, plan, backend="hybrid"))
    b = np.asarray(ops.projected(x, plan, backend="jax"))
    assert a.shape == (3, len(words))
    np.testing.assert_allclose(a, b, atol=1e-5 * max(np.abs(b).max(), 1.0))


@pytest.mark.parametrize("backward", ["inverse", "autodiff", "checkpoint"])
def test_hybrid_backend_gradients_match_jax(backward):
    plan = _plan()
    x = _incs(5, 2, 12, 3)
    gh = jax.grad(lambda z: jnp.sum(ops.projected(
        z, plan, backend="hybrid", backward=backward) ** 2))(x)
    gj = jax.grad(lambda z: jnp.sum(ops.projected(
        z, plan, backend="jax", backward="inverse") ** 2))(x)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(gj), rtol=2e-4,
                               atol=2e-5)


def test_hybrid_backend_through_core_and_logsignature():
    d, N = 3, 3
    rng = np.random.default_rng(11)
    path = jnp.asarray(np.cumsum(rng.normal(size=(2, 14, d)) * 0.3,
                                 axis=1).astype(np.float32))
    a = np.asarray(C.projected_signature(path, WORDS, d, backend="hybrid"))
    b = np.asarray(C.projected_signature(path, WORDS, d, backend="jax"))
    np.testing.assert_allclose(a, b, atol=1e-5)
    la = np.asarray(C.logsignature_projected(path, N, backend="hybrid"))
    lb = np.asarray(C.logsignature(path, N))
    np.testing.assert_allclose(la, lb, atol=1e-4 * max(np.abs(lb).max(), 1.0))


def test_hybrid_backend_depth1_and_stream_and_trunc():
    plan1 = make_plan(((0,), (2,)), 3)     # depth 1: falls back to word engine
    x = _incs(6, 2, 9, 3)
    a = np.asarray(ops.projected(x, plan1, backend="hybrid"))
    b = np.asarray(ops.projected(x, plan1, backend="jax"))
    np.testing.assert_allclose(a, b, atol=1e-6)
    with pytest.raises(NotImplementedError):
        ops.projected(x, _plan(), backend="hybrid", stream=True)
    with pytest.raises(ValueError):
        ops.signature(x, 3, backend="hybrid")


def test_hybrid_backend_forward_only():
    plan = _plan()
    x = _incs(7, 3, 11, 3)
    a = np.asarray(ops.projected_forward_only(x, plan, backend="hybrid"))
    b = np.asarray(ops.projected(x, plan, backend="jax"))
    np.testing.assert_allclose(a, b, atol=1e-5)
