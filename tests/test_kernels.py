"""Pallas kernel tests: shape/dtype sweeps, allclose vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.words import (all_words, anisotropic_words, lyndon_words,
                              make_plan, make_tiled_plan)
from repro.kernels import ops, ref
from repro.kernels.sig_trunc import choose_split, cone_rows, sig_trunc


def _incs(seed, B, M, d, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(B, M, d)).astype(dtype) * 0.3)


# ---------------------------------------------------------------------------
# sig_trunc: shape sweep × split levels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,M,d,N", [
    (1, 1, 2, 1), (1, 5, 2, 3), (3, 17, 2, 6), (2, 9, 3, 4),
    (5, 13, 4, 3), (2, 7, 6, 2), (9, 21, 3, 5), (2, 3, 10, 2),
])
def test_sig_trunc_shapes(B, M, d, N):
    x = _incs(B * M * d, B, M, d)
    want = ref.sig_trunc_ref(x, N)
    got = sig_trunc(x, N, batch_tile=8, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("split", [0, 1, 2, 3])
def test_sig_trunc_splits_agree(split):
    x = _incs(0, 2, 11, 3)
    want = ref.sig_trunc_ref(x, 4)
    got = sig_trunc(x, 4, split=split, batch_tile=8, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_sig_trunc_batch_tile_padding():
    x = _incs(1, 5, 6, 2)   # B=5 not a multiple of the tile
    want = ref.sig_trunc_ref(x, 3)
    for bt in (2, 4, 8, 16):
        got = sig_trunc(x, 3, batch_tile=bt, interpret=True)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("B,tile,want", [
    (5, 2, (8, 8)), (5, 128, (8, 8)), (130, 8, (128, 256)),
    (130, 128, (128, 256)), (1024, 256, (256, 1024)), (200, 256, (200, 200)),
])
def test_lane_tile_is_a_multiple_of_128_or_the_whole_batch(B, tile, want):
    from repro.kernels.sig_trunc import lane_tile
    got = lane_tile(B, tile)
    assert got == want
    assert got[0] % 128 == 0 or got[0] == got[1]


def test_sig_trunc_blocks_over_vmem_raise():
    """A path whose VMEM blocks fit at no split fails with a clear error at
    trace time, before any compile."""
    x = jax.ShapeDtypeStruct((128, 40_000, 6), jnp.float32)
    with pytest.raises(ValueError, match="MiB of VMEM"):
        jax.eval_shape(lambda a: sig_trunc(a, 3, interpret=True), x)
    # streamed: the split rises until the emission block fits
    y = jax.ShapeDtypeStruct((32, 2048, 4), jnp.float32)
    out = jax.eval_shape(lambda a: sig_trunc(a, 4, interpret=True,
                                             stream=True), y)
    assert out.shape == (32, 2048, 340)


def test_sig_trunc_bf16():
    x = _incs(2, 2, 6, 3).astype(jnp.bfloat16)
    got = sig_trunc(x, 3, batch_tile=8, interpret=True)  # f32 accumulation
    want = ref.sig_trunc_ref(x.astype(jnp.float32), 3)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2e-2, atol=2e-2)


def test_choose_split_respects_vmem():
    for d, N in [(3, 6), (8, 6), (10, 4), (40, 2)]:
        s = choose_split(d, N, 128, vmem_budget=6 * 2**20)
        state = (max(0, s - 1) + cone_rows(d, N, s) + d ** (N - s)) * 128 * 4
        assert state <= 6 * 2**20, (d, N, s, state)


@given(st.integers(2, 4), st.integers(1, 4), st.integers(1, 12),
       st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_sig_trunc_property(d, N, M, B):
    x = _incs(d * 1000 + N * 100 + M * 10 + B, B, M, d)
    want = ref.sig_trunc_ref(x, N)
    got = sig_trunc(x, N, batch_tile=8, interpret=True)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-5)


# ---------------------------------------------------------------------------
# sig_words: arbitrary word sets × tilings
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("max_rows", [8, 32, 512])
def test_sig_words_full_truncation(max_rows):
    d, N = 3, 4
    x = _incs(7, 3, 9, d)
    tp = make_tiled_plan(all_words(d, N), d, max_rows=max_rows)
    got = ops.projected(x, tp, backend="pallas_interpret", batch_tile=8)
    np.testing.assert_allclose(got, ref.sig_trunc_ref(x, N),
                               rtol=2e-4, atol=2e-5)


def test_sig_words_sparse_set():
    d = 4
    wordset = [(0,), (3, 2), (1, 1, 1, 1), (2, 0, 3), (3, 3)]
    x = _incs(8, 2, 14, d)
    got = ops.projected(x, wordset, backend="pallas_interpret", batch_tile=8)
    want = ref.sig_words_ref(x, wordset, d)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # cross-check each coefficient against the dense oracle
    dense = ref.sig_trunc_ref(x, 4)
    from repro.core.words import flat_index
    for k, w in enumerate(wordset):
        np.testing.assert_allclose(got[:, k], dense[:, flat_index(w, d)],
                                   rtol=2e-4, atol=2e-5)


def test_sig_words_anisotropic():
    gamma, r = [1.0, 2.0, 1.5], 4.0
    ws = anisotropic_words(gamma, r)
    x = _incs(9, 2, 8, 3)
    tp = make_tiled_plan(ws, 3, max_rows=16)
    got = ops.projected(x, tp, backend="pallas_interpret", batch_tile=8)
    np.testing.assert_allclose(got, ref.sig_words_ref(x, ws, 3),
                               rtol=2e-4, atol=2e-5)


def test_sig_words_lyndon_projection():
    """The log-signature projection word set (§3.3): dense to N-1 + Lyndon_N."""
    d, N = 2, 5
    ws = all_words(d, N - 1) + [w for w in lyndon_words(d, N) if len(w) == N]
    x = _incs(10, 2, 9, d)
    tp = make_tiled_plan(ws, d, max_rows=24)
    got = ops.projected(x, tp, backend="pallas_interpret", batch_tile=8)
    np.testing.assert_allclose(got, ref.sig_words_ref(x, ws, d),
                               rtol=2e-4, atol=2e-5)


@given(st.integers(2, 4), st.data())
@settings(max_examples=15, deadline=None)
def test_sig_words_property(d, data):
    n_words = data.draw(st.integers(1, 8))
    wordset = list({tuple(data.draw(st.integers(0, d - 1))
                          for _ in range(data.draw(st.integers(1, 4))))
                    for _ in range(n_words)})
    M = data.draw(st.integers(1, 10))
    x = _incs(data.draw(st.integers(0, 10**6)), 2, M, d)
    max_rows = data.draw(st.sampled_from([8, 16, 128]))
    tp = make_tiled_plan(wordset, d, max_rows=max_rows)
    got = ops.projected(x, tp, backend="pallas_interpret", batch_tile=8)
    want = ref.sig_words_ref(x, wordset, d)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-5)


# ---------------------------------------------------------------------------
# dispatch + time-parallel
# ---------------------------------------------------------------------------

def test_ops_backends_agree():
    x = _incs(11, 3, 12, 3)
    a = ops.signature(x, 4, backend="jax")
    b = ops.signature(x, 4, backend="pallas_interpret", batch_tile=8)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("chunks", [2, 4, 7])
def test_time_parallel_combine(chunks):
    x = _incs(12, 2, 13, 3)
    want = ref.sig_trunc_ref(x, 4)
    got = ops.signature_time_parallel(x, 4, chunks,
                                      backend="pallas_interpret", batch_tile=8)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
