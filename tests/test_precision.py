"""Mixed-precision (bf16 storage, fp32 accumulation) dispatch cells.

Precision is quantise-once-at-dispatch (a straight-through bf16 rounding of
the increments before any engine runs), so every backend × backward
combination must agree EXACTLY under ``precision="bf16_fp32"``; the forward
error against the fp32 oracle is the compounding of one bf16 rounding per
increment, bounded per level n by ~n·2^-8 relative (bf16 keeps 8 mantissa
bits).  The storage dtype halves the kernels' VMEM footprints.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.sig_trunc import choose_split, state_footprint
from repro.kernels.sig_words import tile_footprint
from tests.conftest import projected_engine

DEPTH = 6
B, M, d = 4, 40, 3


@pytest.fixture(autouse=True)
def _autotune_off(monkeypatch):
    monkeypatch.setenv("PATHSIG_AUTOTUNE", "off")


@pytest.fixture(scope="module")
def incs():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.standard_normal((B, M, d)).astype(np.float32)
                       * 0.2)


def _per_level_relerr(got, ref, d, depth):
    errs, off = [], 0
    for n in range(1, depth + 1):
        w = d ** n
        g, r = got[:, off:off + w], ref[:, off:off + w]
        errs.append(float(jnp.linalg.norm(g - r) /
                          jnp.maximum(jnp.linalg.norm(r), 1e-30)))
        off += w
    return errs


@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
def test_bf16_per_level_error_bound(incs, backend):
    """Level-n relative error vs the fp32 oracle stays within n·2^-8 at
    depth <= 6 (the documented bound: n compounded bf16 roundings)."""
    ref = ops.signature(incs, DEPTH, backend="jax")
    got = ops.signature(incs, DEPTH, backend=backend, precision="bf16_fp32",
                        batch_tile=8)
    for n, err in enumerate(_per_level_relerr(got, ref, d, DEPTH), start=1):
        assert err <= n * 2.0 ** -8, (n, err)


def test_bf16_engines_agree_exactly(incs):
    """Rounding happens ONCE at dispatch, so engines agree to fp32 noise."""
    a = ops.signature(incs, 4, backend="jax", precision="bf16_fp32")
    b = ops.signature(incs, 4, backend="pallas_interpret",
                      precision="bf16_fp32", batch_tile=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-6)


@pytest.mark.parametrize("bwd", ["inverse", "checkpoint", "autodiff"])
def test_bf16_grads_finite_and_backends_agree(incs, bwd):
    co = jnp.asarray(np.random.default_rng(1).standard_normal(
        (B, sum(d ** n for n in range(1, 4)))).astype(np.float32))

    def loss(backend):
        return jax.grad(lambda x: jnp.vdot(ops.signature(
            x, 3, backend=backend, backward=bwd, precision="bf16_fp32",
            batch_tile=8), co))(incs)

    gj, gp = loss("jax"), loss("pallas_interpret")
    assert np.isfinite(np.asarray(gj)).all()
    np.testing.assert_allclose(np.asarray(gj), np.asarray(gp), atol=3e-5)


def test_bf16_projected_and_gram(incs):
    from repro.core.words import all_words
    words = tuple(all_words(d, 3))
    ref = ops.projected(incs, words, backend="jax")
    for engine in ("rule", "words"):
        with projected_engine(engine):
            got = ops.projected(incs, words, backend="pallas_interpret",
                                precision="bf16_fp32", batch_tile=8)
        # projections are signature coordinates: same per-level (relative)
        # bound
        rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
        assert rel < 3 * 2.0 ** -8, engine
    S = ops.signature(incs, 3, backend="jax")
    w = jnp.ones(S.shape[1], jnp.float32)
    g32 = ops.gram(S, S, w, backend="pallas_interpret")
    g16 = ops.gram(S, S, w, backend="pallas_interpret",
                   precision="bf16_fp32")
    rel = float(jnp.max(jnp.abs(g16 - g32)) / jnp.max(jnp.abs(g32)))
    assert rel < 2.0 ** -7


def test_bf16_streamed_per_level_error_bound(incs):
    """Streamed emission under bf16_fp32: the kernels' emission buffers
    store bf16 (fp32 scratch accumulators), adding at most ONE more
    rounding on top of the per-increment storage rounding — level-n error
    of every emitted frame vs the fp32 streamed oracle stays within
    (n+1)·2^-8."""
    ref = ops.signature(incs, DEPTH, backend="jax", stream=True,
                        stream_stride=5)
    for backend in ("jax", "pallas_interpret"):
        got = ops.signature(incs, DEPTH, backend=backend, stream=True,
                            stream_stride=5, precision="bf16_fp32",
                            batch_tile=8)
        assert got.dtype == ref.dtype  # storage dtype never leaks out
        errs, off = [], 0
        for n in range(1, DEPTH + 1):
            w = d ** n
            g, r = got[..., off:off + w], ref[..., off:off + w]
            err = float(jnp.linalg.norm(g - r) /
                        jnp.maximum(jnp.linalg.norm(r), 1e-30))
            assert err <= (n + 1) * 2.0 ** -8, (backend, n, err)
            off += w


def test_bf16_streamed_engines_agree_exactly(incs):
    """The dispatch-level straight-through rounding of the emitted frames
    is idempotent and shared, so both engines land on the same bf16 grid
    points — streamed outputs agree to the bit."""
    a = ops.signature(incs, 4, backend="jax", stream=True, stream_stride=5,
                      precision="bf16_fp32")
    b = ops.signature(incs, 4, backend="pallas_interpret", stream=True,
                      stream_stride=5, precision="bf16_fp32", batch_tile=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    from repro.core.words import all_words
    words = tuple(all_words(d, 3))
    pa = ops.projected(incs, words, backend="jax", stream=True,
                       stream_stride=5, precision="bf16_fp32")
    pb = ops.projected(incs, words, backend="pallas_interpret", stream=True,
                       stream_stride=5, precision="bf16_fp32", batch_tile=8)
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


def test_bf16_streamed_terminal_matches_nonstreamed(incs):
    """The terminal emitted frame is the non-streamed bf16 result plus at
    most one emission rounding: within one bf16 ulp relative."""
    s = ops.signature(incs, DEPTH, backend="jax", stream=True,
                      stream_stride=5, precision="bf16_fp32")
    ns = ops.signature(incs, DEPTH, backend="jax", precision="bf16_fp32")
    rel = float(jnp.max(jnp.abs(s[:, -1] - ns)) / jnp.max(jnp.abs(ns)))
    assert rel <= 2.0 ** -8, rel


@pytest.mark.parametrize("bwd", ["inverse", "autodiff"])
def test_bf16_streamed_grads_finite_and_backends_agree(incs, bwd):
    D3 = sum(d ** n for n in range(1, 4))
    co = jnp.asarray(np.random.default_rng(1).standard_normal(
        (B, 8, D3)).astype(np.float32))

    def g(backend):
        return jax.grad(lambda x: jnp.vdot(ops.signature(
            x, 3, backend=backend, backward=bwd, stream=True,
            stream_stride=5, precision="bf16_fp32", batch_tile=8), co))(incs)

    gj, gp = g("jax"), g("pallas_interpret")
    assert np.isfinite(np.asarray(gj)).all()
    scale = float(jnp.max(jnp.abs(gj)))
    assert float(jnp.max(jnp.abs(gj - gp))) <= 2.0 ** -7 * scale


def test_bf16_halves_state_footprint():
    """Satellite: the bytes-per-element literals are dtype-parameterised —
    bf16 storage halves both kernels' VMEM footprints exactly."""
    assert state_footprint(4, 5, 2, 128, itemsize=2) * 2 == \
        state_footprint(4, 5, 2, 128, itemsize=4)
    assert tile_footprint(64, 4, 3, 128, itemsize=2) * 2 == \
        tile_footprint(64, 4, 3, 128, itemsize=4)


def test_choose_split_sees_dtype():
    """Halving the element size can only loosen the split (more state fits
    in the same VMEM budget), and does so strictly on a budget that fp32
    just overflows."""
    d_, depth_, bt = 4, 6, 128
    s32 = choose_split(d_, depth_, bt, itemsize=4)
    s16 = choose_split(d_, depth_, bt, itemsize=2)
    assert s16 <= s32
    # a budget exactly at the bf16 footprint of split 0 separates the two
    budget = state_footprint(d_, depth_, 0, bt, itemsize=2)
    assert choose_split(d_, depth_, bt, vmem_budget=budget, itemsize=2) == 0
    assert choose_split(d_, depth_, bt, vmem_budget=budget, itemsize=4) > 0


def test_canon_precision_aliases():
    from repro.core.signature import canon_precision
    assert canon_precision("bf16") == "bf16_fp32"
    assert canon_precision("fp32") == "fp32"
    with pytest.raises(ValueError):
        canon_precision("fp64")
