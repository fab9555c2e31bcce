"""Engine-dispatch tests: every backend is differentiable (the grad-through-
kernels regression), cross-engine golden agreement vs the exp/Chen oracle,
and dtype/shape edge cases (B=1, M=1, float64)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as C
from repro import obs
from repro.core import tensor_ops as tops
from repro.core.signature import mask_increments
from repro.core.transforms import (as_transform, augment_increments,
                                   transform_dim)
from repro.core.words import make_plan
from repro.kernels import ops
from repro.kernels.sig_trunc import level_offsets
from tests.conftest import projected_engine

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


@pytest.mark.parametrize("backend,engine", [
    *[pytest.param(b, "rule", id=b) for b in BACKENDS],
    pytest.param("pallas_interpret", "words", id="pallas_interpret-words"),
])
def test_grad_through_projected_every_backend(backend, engine):
    x = _incs(1, 2, 7, 3)
    with projected_engine(engine):
        g = _grad(backend, lambda z: ops.projected(
            z, _plan(), backend=backend, batch_tile=8).sum(), x)
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
    # the dense route's word indices, the word engine's closure VJP
    for engine, cache in (("rule", ops._dense_index),
                          ("words", ops._pallas_proj_inverse)):
        with projected_engine(engine):
            ops.projected(x, p1, backend="pallas_interpret", batch_tile=8)
            before = cache.cache_info()
            ops.projected(x, p2, backend="pallas_interpret", batch_tile=8)
            after = cache.cache_info()
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


@pytest.mark.parametrize("B,M,engine", [
    pytest.param(2, 11, "rule", id="2-11"),
    pytest.param(1, 1, "rule", id="1-1"),
    pytest.param(2, 11, "words", id="2-11-words"),
    pytest.param(1, 1, "words", id="1-1-words"),
])
def test_projected_cross_engine_values_and_gradients(B, M, engine):
    d = 3
    x = _incs(20 + B * M, B, M, d)
    plan = _plan()
    # values: both engines vs the dense oracle read at the requested words
    dense = tops.signature_exp_chen(x, 3)
    idx = [C.flat_index(w, d) for w in WORDS]
    a = ops.projected(x, plan, backend="jax")
    with projected_engine(engine):
        b = ops.projected(x, plan, backend="pallas_interpret", batch_tile=8)
        g_pal = jax.grad(lambda z: jnp.sum(jnp.sin(
            ops.projected(z, plan, backend="pallas_interpret",
                          batch_tile=8))))(x)
    np.testing.assert_allclose(a, dense[:, idx], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(b, dense[:, idx], rtol=1e-4, atol=1e-6)
    # gradients agree across engines
    g_jax = jax.grad(lambda z: jnp.sum(jnp.sin(
        ops.projected(z, plan, backend="jax"))))(x)
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
        for engine in ("rule", "words"):
            with projected_engine(engine):
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


# ---------------------------------------------------------------------------
# the projected Pallas inverse row's two engines: the dense route (the
# truncated kernels to the set's depth, then a gather) and the word engine
# (the closure kernel and the projected §4.2 scan)
# ---------------------------------------------------------------------------

# d = 3, depth 6: 1,128 truncated rows against a closure of 9
SPARSE_WORDS = ((0, 1, 2, 0, 1, 2), (1,), (2, 1))


def _projected_engine_ticks():
    c = obs.counter("pathsig_projected_engine_total", labelnames=("engine",))
    return {e: c.value(engine=e) for e in ("dense", "words")}


def _oracle_words(incs, words, spec, x0, lens):
    """The exp/Chen signature of the materialised (transformed, masked)
    increments, read at the words."""
    if spec is None:
        e = incs if lens is None else mask_increments(incs, lens)
    elif lens is None:
        e = augment_increments(incs, spec, x0=x0)
    else:
        e, al = augment_increments(incs, spec, x0=x0, lengths=lens)
        e = mask_increments(e, al)
    depth = max(len(w) for w in words)
    dense = tops.signature_exp_chen(e, depth)
    return dense[:, [C.flat_index(w, e.shape[-1]) for w in words]]


@pytest.mark.parametrize("tname,ragged", [
    pytest.param(None, False, id="plain"),
    pytest.param(None, True, id="plain-ragged"),
    pytest.param("lead_lag", False, id="lead_lag"),
    pytest.param("lead_lag", True, id="lead_lag-ragged"),
    pytest.param("time_augment", False, id="time_augment"),
    pytest.param("basepoint", True, id="basepoint-ragged"),
    pytest.param("basepoint+lead_lag", False, id="basepoint-lead_lag"),
])
def test_dense_route_matches_oracle_and_word_engine(tname, ragged):
    B, M, d_raw = 3, 6, 2
    spec = as_transform(tname)
    d = transform_dim(spec, d_raw) if spec else d_raw
    words = tuple(C.all_words(d, 3))[1::4]
    x = _incs(70 + d, B, M, d_raw)
    x0 = _incs(71, B, 1, d_raw)[:, 0]
    lens = jnp.asarray([6, 3, 0]) if ragged else None

    def proj(z):
        return ops.projected(z, words, backend="pallas_interpret",
                             batch_tile=8, transform=tname, x0=x0,
                             lengths=lens)

    def grad(z):
        return jax.grad(lambda z: jnp.sum(jnp.sin(proj(z))))(z)

    with obs.enabled_scope():
        before = _projected_engine_ticks()
        val, g = proj(x), grad(x)
        after = _projected_engine_ticks()
    assert after == {**before, "dense": before["dense"] + 2}
    np.testing.assert_allclose(
        val, _oracle_words(x, words, spec, x0, lens), rtol=1e-4, atol=1e-6)
    g_jax = jax.grad(lambda z: jnp.sum(jnp.sin(ops.projected(
        z, words, backend="jax", transform=tname, x0=x0,
        lengths=lens))))(x)
    np.testing.assert_allclose(g, g_jax, rtol=1e-4, atol=1e-6)
    with projected_engine("words"), obs.enabled_scope():
        before = _projected_engine_ticks()
        val_w, g_w = proj(x), grad(x)
        after = _projected_engine_ticks()
    assert after == {**before, "words": before["words"] + 2}
    np.testing.assert_allclose(val, val_w, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(g, g_w, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("tname", [None, "lead_lag"])
def test_sparse_set_stays_on_word_engine(tname):
    """The rule's other side: a set whose truncated tensor has more than
    DENSE_ROWS_PER_CLOSURE_ROW rows per closure row runs the word kernel
    and the projected scan."""
    spec = as_transform(tname)
    d_raw = 3 if spec is None else 2
    words = SPARSE_WORDS if spec is None else ((0, 1, 2, 3, 0, 1), (3, 2))
    d = transform_dim(spec, d_raw) if spec else d_raw
    plan = make_plan(words, d)
    assert int(level_offsets(d, plan.depth)[-1]) > \
        ops.DENSE_ROWS_PER_CLOSURE_ROW * plan.closure_size
    x = _incs(80, 2, 5, d_raw)

    def proj(z, backend="pallas_interpret"):
        return ops.projected(z, plan, backend=backend, batch_tile=8,
                             transform=tname)

    with obs.enabled_scope():
        before = _projected_engine_ticks()
        val = proj(x)
        g = jax.grad(lambda z: jnp.sum(proj(z) ** 2))(x)
        after = _projected_engine_ticks()
    assert after == {**before, "words": before["words"] + 2}
    np.testing.assert_allclose(val, _oracle_words(x, words, spec, None, None),
                               rtol=1e-4, atol=1e-7)
    g_jax = jax.grad(lambda z: jnp.sum(proj(z, "jax") ** 2))(x)
    np.testing.assert_allclose(g, g_jax, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("B,M,d_raw,depth,tname,fits", [
    (1024, 250, 5, 3, "lead_lag", True),     # the §8 set's shape
    (1024, 500, 4, 5, None, True),           # paper Table 1 widths
    (1, 2, 8, 6, None, False),               # S and G overflow VMEM
    (2, 0, 3, 3, None, False),               # no time axis to sweep
])
def test_dense_rule_needs_both_truncated_kernels(B, M, d_raw, depth, tname,
                                                 fits):
    assert ops._trunc_kernels_fit(B, M, d_raw, depth, 128, None, "fp32",
                                  as_transform(tname)) is fits


def test_projected_engine_counter_ticks_once_per_trace():
    x = _incs(90, 2, 5, 3)
    f = jax.jit(lambda z: ops.projected(z, _plan(),
                                        backend="pallas_interpret",
                                        batch_tile=8))
    with obs.enabled_scope():
        before = _projected_engine_ticks()
        f(x)
        f(x + 1.0)
        after = _projected_engine_ticks()
    assert after == {**before, "dense": before["dense"] + 1}
