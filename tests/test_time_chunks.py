"""The truncated kernels over time chunks: the cone forward ``sig_trunc`` and
the §4.2 reverse-scan backward ``sig_trunc_inverse_bwd`` stream the time
axis through VMEM in chunks of T steps, the last padded with zero steps.

Chunking changes no arithmetic: a zero step is the identity update, so the
chunked kernels are bit-identical to one chunk over the whole axis, and both
agree with the jax engine and the plain reference.  Small chunks are forced
by shrinking ``TIME_BLOCK_BYTES``; the jit caches are dropped around it, as
the chunk length is read when a kernel is traced.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as C
from repro import obs
from repro.core.signature import inverse_bwd_scan
from repro.core.transforms import as_transform, transform_time_aux
from repro.kernels import ops, ref, sig_trunc_bwd

# the package exports the function under the module's name
st = importlib.import_module("repro.kernels.sig_trunc")


def _incs(seed, B, M, d):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(B, M, d)).astype(np.float32)
                       / np.sqrt(M))


def _ticks():
    c = obs.counter("pathsig_kernel_time_chunks_total",
                    labelnames=("kernel",))
    e = obs.counter("pathsig_inverse_bwd_engine_total",
                    labelnames=("engine",))
    return {"sig_trunc": c.value(kernel="sig_trunc"),
            "sig_trunc_bwd": c.value(kernel="sig_trunc_bwd"),
            "kernel": e.value(engine="kernel"), "scan": e.value(engine="scan")}


@pytest.fixture
def steps(monkeypatch):
    """-> set(n): chunks of n forward steps (n // 2 backward, which chunks
    two blocks), at 8-lane tiles of up to 8 channels."""
    def set_steps(n):
        jax.clear_caches()
        monkeypatch.setattr(st, "TIME_BLOCK_BYTES",
                            n * 2 * st.block_bytes((1, 8, 8)))
    monkeypatch.setenv("PATHSIG_AUTOTUNE", "off")
    yield set_steps
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("M,bytes_per_step,want", [
    (500, 8192, (500, 1)),          # t1_d4n5's forward: one chunk
    (500, 16384, (500, 1)),         # and its backward
    (17_983, 8192, (1499, 12)),     # EigenWorms' forward, 6 channels
    (17_983, 16384, (782, 23)),     # its backward, 7 channels
    (0, 8192, (0, 1)),
])
def test_time_chunk(M, bytes_per_step, want):
    T, n = st.time_chunk(M, bytes_per_step)
    assert (T, n) == want
    assert T * bytes_per_step <= st.TIME_BLOCK_BYTES
    assert 0 <= n * T - M < n


def test_cell_shapes_chunk_as_planned():
    """t1_d4n5 keeps one chunk in both kernels; the EigenWorms step's
    blocks now fit VMEM with the kernel backward."""
    assert sig_trunc_bwd._grid(1024, 500, 4, 128)[3] == 1
    assert sig_trunc_bwd.fits(128, 17_983, 7, 4, 128)
    assert sig_trunc_bwd.fits(131, 17_983, 7, 4, 128)     # two lane tiles
    assert not sig_trunc_bwd.fits(1, 2, 8, 6, 128)        # S and G do not
    out = jax.eval_shape(lambda a: st.sig_trunc(a, 4, interpret=True),
                         jax.ShapeDtypeStruct((256, 17_983, 6), jnp.float32))
    assert out.shape == (256, 1554)


@pytest.mark.parametrize("B,M,d,N,split,batch_tile", [
    (3, 37, 3, 3, 0, 8),        # 5 chunks of 8, the last of 5 steps
    (130, 21, 2, 4, 0, 128),    # two lane tiles
    (2, 19, 3, 4, 1, 8),        # 3 cones, each sweeping every chunk
    (4, 8, 2, 3, 0, 8),         # the chunk is the whole axis
    (2, 19, 7, 3, 0, 8),        # levels of 7, 49, 343 rows, each 8-aligned
    (2, 19, 7, 3, 1, 8),        # 7 cones of 1, 7, 49 rows
    (3, 21, 4, 4, 0, 8),        # levels on the 8-row tile
    (2, 17, 4, 4, 2, 8),
])
def test_chunked_forward_is_bit_identical(steps, B, M, d, N, split,
                                          batch_tile):
    x = _incs(B * M + d, B, M, d)
    whole = st.sig_trunc(x, N, split=split, batch_tile=batch_tile,
                         interpret=True)
    steps(8)
    with obs.enabled_scope():
        before = _ticks()
        got = st.sig_trunc(x, N, split=split, batch_tile=batch_tile,
                           interpret=True)
        after = _ticks()
    assert after["sig_trunc"] - before["sig_trunc"] == -(-M // 8)
    np.testing.assert_array_equal(got, whole)
    np.testing.assert_allclose(got, ref.sig_trunc_ref(x, N), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("tname,d", [
    *(pytest.param(t, 2, id=t) for t in ("time_augment", "lead_lag",
                                         "time_augment+lead_lag")),
    ("time_augment", 6), ("time_augment+lead_lag", 3),   # 7 channels
    ("time_augment", 3),                                 # 4 channels
])
def test_chunked_fused_forward(steps, tname, d):
    """The fused channels see each step's global index: the time channel
    runs on across chunks and stops at the true end, not the padded one."""
    B, M, N = 5, 29, 3
    x = _incs(7, B, M, d)
    spec = as_transform(tname)
    taux = transform_time_aux(spec, B, M)
    whole = st.sig_trunc(x, N, batch_tile=8, interpret=True, transform=spec,
                         taux=taux)
    steps(8)
    got = st.sig_trunc(x, N, batch_tile=8, interpret=True, transform=spec,
                       taux=taux)
    np.testing.assert_array_equal(got, whole)
    want = C.signature_from_increments(x, N, backend="jax", transform=spec)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("B,M,d,N", [
    (3, 37, 3, 3),      # 10 chunks of 4 steps, 3 of them padding
    (130, 21, 2, 4),    # two lane tiles
    (2, 7, 4, 2),
])
def test_chunked_backward_is_bit_identical(steps, B, M, d, N):
    x = _incs(M + N, B, M, d)
    out = C.signature_from_increments(x, N, backend="jax")
    g = jax.random.normal(jax.random.PRNGKey(M), out.shape)
    whole = sig_trunc_bwd.sig_trunc_inverse_bwd(x, out, g, N, interpret=True)
    steps(8)
    n_t = sig_trunc_bwd._grid(B, M, d, 128)[3]
    assert n_t == -(-M // 4)
    with obs.enabled_scope():
        before = _ticks()
        got = sig_trunc_bwd.sig_trunc_inverse_bwd(x, out, g, N,
                                                  interpret=True)
        after = _ticks()
    assert after["sig_trunc_bwd"] - before["sig_trunc_bwd"] == n_t
    np.testing.assert_array_equal(got, whole)
    scan = inverse_bwd_scan(x, out, g, N)
    scale = float(jnp.max(jnp.abs(scan)))
    np.testing.assert_allclose(got, scan, rtol=1e-4, atol=1e-5 * scale)


@pytest.mark.parametrize("B,M,d,N", [(3, 29, 3, 3), (2, 21, 4, 4),
                                     (2, 19, 7, 3)])
def test_backward_takes_the_forward_kernel_state(steps, B, M, d, N):
    """The §4.2 kernel reads the terminal state the cone forward wrote, in
    the layout both kernels share: its gradient is the XLA scan's."""
    x = _incs(M * d, B, M, d)
    steps(8)
    out = st.sig_trunc(x, N, batch_tile=8, interpret=True)
    np.testing.assert_allclose(out, ref.sig_trunc_ref(x, N), rtol=2e-4,
                               atol=2e-5)
    g = jax.random.normal(jax.random.PRNGKey(d), out.shape)
    got = sig_trunc_bwd.sig_trunc_inverse_bwd(x, out, g, N, interpret=True)
    scan = inverse_bwd_scan(x, out, g, N)
    scale = float(jnp.max(jnp.abs(scan)))
    np.testing.assert_allclose(got, scan, rtol=1e-4, atol=1e-5 * scale)


@pytest.mark.parametrize("B,d,N", [(5, 2, 4), (130, 3, 3)])
def test_fused_time_augment_backward_runs_the_kernel(steps, B, d, N):
    """``_pallas_sig_fused_inverse``: the time-augmented signature's §4.2
    backward is the reverse-scan kernel over chunks, ticked as such, and
    its gradient is autodiff's through the jax engine."""
    M = 23
    x = _incs(B + d, B, M, d)
    co = jax.random.normal(jax.random.PRNGKey(N),
                           (B, sum((d + 1) ** n for n in range(1, N + 1))))

    def grad(backend, backward):
        return jax.grad(lambda z: jnp.sum(co * ops.signature(
            z, N, backend=backend, backward=backward,
            transform="time_augment")))(x)

    steps(8)
    with obs.enabled_scope():
        before = _ticks()
        got = grad("pallas_interpret", "inverse")
        after = _ticks()
    assert after["kernel"] == before["kernel"] + 1
    assert after["scan"] == before["scan"]
    assert after["sig_trunc"] - before["sig_trunc"] == 3      # chunks of 8
    assert after["sig_trunc_bwd"] - before["sig_trunc_bwd"] == 6   # of 4
    want = grad("jax", "autodiff")
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * scale)
