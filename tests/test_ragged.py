"""Ragged (variable-length) paths: padding invariance across the stack.

The subsystem's contract, tested per backend × stream × backward cell:

1. terminal signatures of a padded batch with ``lengths=`` equal per-example
   unpadded oracles (<= 1e-6), and are BIT-stable in the amount of padding;
2. streamed outputs are masked after each example's true-terminal slot and
   ``ragged_terminal`` gathers the exact terminal;
3. gradients w.r.t. padded steps are exactly zero.

Plus the container/bucketing/serving layers (RaggedPaths, DynamicBatcher),
ragged windows, sigkernel, transforms, sig-head mask pass-through and the
deterministic ragged data pipeline.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (projected_signature, signature, windowed_projection,
                        windowed_signature)
from repro.core.signature import (length_mask, mask_increments,
                                  ragged_terminal, stream_emit_mask,
                                  stream_emit_slots, stream_emit_steps)
from repro.core.words import make_plan
from repro.ragged import (RaggedPaths, assign_buckets, bucket_ladder,
                          bucket_paths, pad_batch)

from tests.conftest import make_path, projected_engine

BACKENDS = ("jax", "pallas_interpret")
WORDS = ((0,), (1,), (0, 1), (1, 0, 1))


def ragged_batch(rng, B=3, M=12, d=2, scale=0.3):
    path = make_path(rng, B, M, d, scale)
    lengths = np.asarray([M] + list(rng.integers(1, M, size=B - 1)))
    return jnp.asarray(path), lengths


# ---------------------------------------------------------------------------
# mask helpers
# ---------------------------------------------------------------------------

def test_length_mask_and_slots():
    lengths = jnp.asarray([0, 1, 5, 12])
    m = np.asarray(length_mask(lengths, 12))
    for b, L in enumerate([0, 1, 5, 12]):
        assert m[b].sum() == L and m[b, :L].all()
    for stride in (1, 3, 5, 12, 17):
        steps = stream_emit_steps(12, stride)
        slots = np.asarray(stream_emit_slots(12, stride, lengths))
        emit = np.asarray(stream_emit_mask(12, stride, lengths))
        for b, L in enumerate([0, 1, 5, 12]):
            # the slot's emission covers >= L increments, and it is minimal
            covered = steps[slots[b]] + 1
            assert covered >= L
            if slots[b] > 0:
                assert steps[slots[b] - 1] + 1 < max(L, 1)
            assert emit[b].sum() == slots[b] + 1


def test_mask_increments_zeros_tail():
    rng = np.random.default_rng(0)
    incs = jnp.asarray(rng.standard_normal((3, 8, 2)).astype(np.float32))
    out = np.asarray(mask_increments(incs, jnp.asarray([8, 3, 0])))
    assert np.array_equal(out[0], np.asarray(incs[0]))
    assert np.array_equal(out[1, :3], np.asarray(incs[1, :3]))
    assert np.all(out[1, 3:] == 0) and np.all(out[2] == 0)


# ---------------------------------------------------------------------------
# padding invariance: terminal values vs unpadded oracles (every cell)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("backward", ["inverse", "checkpoint", "autodiff"])
def test_ragged_terminal_matches_unpadded(rng, backend, backward):
    path, lengths = ragged_batch(rng)
    out = signature(path, 3, backend=backend, backward=backward,
                    lengths=lengths)
    for b, L in enumerate(lengths):
        ref = signature(path[b:b + 1, :L + 1], 3, backend=backend,
                        backward=backward)[0]
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref),
                                   atol=1e-6)


@pytest.mark.parametrize("backward,backend,engine", [
    *[pytest.param(bw, b, "rule", id=f"{bw}-{b}")
      for bw in ("inverse", "checkpoint", "autodiff")
      for b in BACKENDS + ("hybrid",)],
    pytest.param("inverse", "pallas_interpret", "words",
                 id="inverse-pallas_interpret-words"),
])
def test_ragged_projected_matches_unpadded(rng, backend, backward, engine):
    path, lengths = ragged_batch(rng)
    plan = make_plan(WORDS, 2)
    with projected_engine(engine):
        out = projected_signature(path, plan.words, 2, plan=plan,
                                  backend=backend, backward=backward,
                                  lengths=lengths)
        for b, L in enumerate(lengths):
            ref = projected_signature(path[b:b + 1, :L + 1], plan.words, 2,
                                      plan=plan, backend=backend,
                                      backward=backward)[0]
            np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref),
                                       atol=1e-6)


@settings(max_examples=8, deadline=None)
@given(k=st.integers(min_value=0, max_value=9),
       backend=st.sampled_from(BACKENDS),
       stream=st.booleans(),
       backward=st.sampled_from(["inverse", "checkpoint", "autodiff"]))
def test_padding_bitstable_in_k(k, backend, stream, backward):
    """signature(pad(x, k), lengths) is BIT-stable in the padding amount k,
    across backend × stream × checkpoint cells (property test)."""
    if stream and backward == "checkpoint":
        return  # unsupported cell (raises; covered elsewhere)
    rng = np.random.default_rng(42)
    path = jnp.asarray(make_path(rng, 2, 10, 2))
    lengths = np.asarray([10, 6])

    def run(p):
        return signature(p, 3, backend=backend, backward=backward,
                         stream=stream, lengths=lengths)

    base = np.asarray(run(path))
    if k:
        garbage = jnp.asarray(
            rng.standard_normal((2, k, 2)).astype(np.float32))
        padded = jnp.concatenate([path, garbage], axis=1)
        got = np.asarray(run(padded))
        if stream:
            # emissions at the shared slots agree bitwise; the extra padded
            # slots are exactly zero (masked)
            emit = np.asarray(stream_emit_mask(10 + k, 1,
                                               jnp.asarray(lengths)))
            assert np.array_equal(got[:, :base.shape[1]], base)
            assert np.all(got[~emit] == 0)
        else:
            assert np.array_equal(got, base)


# ---------------------------------------------------------------------------
# streamed emissions: masking + true-terminal gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("stride", [1, 4])
def test_ragged_stream_mask_and_terminal(rng, backend, stride):
    path, lengths = ragged_batch(rng, M=12)
    out = signature(path, 3, backend=backend, stream=True,
                    stream_stride=stride, lengths=lengths)
    emit = np.asarray(stream_emit_mask(12, stride, jnp.asarray(lengths)))
    assert np.all(np.asarray(out)[~emit] == 0)
    term = ragged_terminal(out, lengths, stride, M=12)
    ref = signature(path, 3, backend=backend, lengths=lengths)
    np.testing.assert_allclose(np.asarray(term), np.asarray(ref), atol=1e-6)
    # in-range emissions match the unpadded per-example stream
    steps = stream_emit_steps(12, stride)
    for b, L in enumerate(lengths):
        sref = signature(path[b:b + 1, :L + 1], 3, backend=backend,
                         stream=True)[0]          # (L, D)
        for j, t in enumerate(steps):
            if t + 1 <= L:
                np.testing.assert_allclose(np.asarray(out[b, j]),
                                           np.asarray(sref[t]), atol=1e-6)


@pytest.mark.parametrize("backend", BACKENDS)
def test_ragged_projected_stream(rng, backend):
    path, lengths = ragged_batch(rng, M=10)
    plan = make_plan(WORDS, 2)
    out = projected_signature(path, plan.words, 2, plan=plan, stream=True,
                              stream_stride=3, backend=backend,
                              lengths=lengths)
    emit = np.asarray(stream_emit_mask(10, 3, jnp.asarray(lengths)))
    assert np.all(np.asarray(out)[~emit] == 0)
    term = ragged_terminal(out, lengths, 3, M=10)
    ref = projected_signature(path, plan.words, 2, plan=plan,
                              backend=backend, lengths=lengths)
    np.testing.assert_allclose(np.asarray(term), np.asarray(ref), atol=1e-6)


# ---------------------------------------------------------------------------
# gradients: exactly zero past each example's true end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("backward", ["inverse", "checkpoint", "autodiff"])
@pytest.mark.parametrize("stream", [False, True])
def test_ragged_grads_zero_past_end(rng, backend, backward, stream):
    if stream and backward == "checkpoint":
        pytest.skip("stream x checkpoint raises (support matrix)")
    path, lengths = ragged_batch(rng)

    def loss(p):
        out = signature(p, 3, backend=backend, backward=backward,
                        stream=stream, lengths=lengths)
        return jnp.sum(out ** 2)

    g = np.asarray(jax.grad(loss)(path))
    assert np.all(np.isfinite(g))
    for b, L in enumerate(lengths):
        # path point k feeds increments k-1 and k; every increment >= L is
        # masked, so points strictly past L get EXACTLY zero gradient
        assert np.all(g[b, L + 1:] == 0.0), (backend, backward, stream, b)
        assert np.any(g[b, :L + 1] != 0.0)


@pytest.mark.parametrize("backend,engine", [
    *[pytest.param(b, "rule", id=b) for b in BACKENDS + ("hybrid",)],
    pytest.param("pallas_interpret", "words", id="pallas_interpret-words"),
])
def test_ragged_projected_grads_zero_past_end(rng, backend, engine):
    path, lengths = ragged_batch(rng)
    plan = make_plan(WORDS, 2)

    def loss(p):
        out = projected_signature(p, plan.words, 2, plan=plan,
                                  backend=backend, lengths=lengths)
        return jnp.sum(out ** 2)

    with projected_engine(engine):
        g = np.asarray(jax.grad(loss)(path))
    for b, L in enumerate(lengths):
        assert np.all(g[b, L + 1:] == 0.0)


# ---------------------------------------------------------------------------
# RaggedPaths container + bucketing
# ---------------------------------------------------------------------------

def test_ragged_paths_constructors(rng):
    paths = [np.cumsum(rng.normal(size=(L + 1, 2)).astype(np.float32), 0)
             for L in (3, 9, 5)]
    rp = RaggedPaths.from_list(paths)
    assert rp.batch == 3 and rp.max_len == 9 and rp.d == 2
    assert np.array_equal(np.asarray(rp.lengths), [3, 9, 5])
    # frozen tail: zero increments past the end even WITHOUT masking
    incs = np.asarray(rp.values[:, 1:] - rp.values[:, :-1])
    for b, L in enumerate([3, 9, 5]):
        assert np.all(incs[b, L:] == 0)
    flat = np.concatenate(paths, axis=0)
    rp2 = RaggedPaths.from_segments(flat, [4, 10, 6])
    assert np.array_equal(np.asarray(rp2.values), np.asarray(rp.values))
    # the container is accepted directly by the signature entry points
    sig = signature(rp, 3)
    for b, p in enumerate(paths):
        ref = signature(jnp.asarray(p)[None], 3)[0]
        np.testing.assert_allclose(np.asarray(sig[b]), np.asarray(ref),
                                   atol=1e-6)
    # pytree: jit accepts it
    jsig = jax.jit(lambda r: signature(r, 3))(rp)
    np.testing.assert_allclose(np.asarray(jsig), np.asarray(sig), atol=0)
    # terminal points + pad_to keep exactness
    tp = np.asarray(rp.terminal_points())
    for b, p in enumerate(paths):
        assert np.array_equal(tp[b], p[-1])
    sig2 = signature(rp.pad_to(16), 3)
    np.testing.assert_allclose(np.asarray(sig2), np.asarray(sig), atol=0)


def test_ragged_paths_validation():
    with pytest.raises(ValueError):
        RaggedPaths.from_list([])
    with pytest.raises(ValueError):
        RaggedPaths.from_list([np.zeros((3, 2)), np.zeros((3, 3))])
    with pytest.raises(ValueError):
        RaggedPaths.from_segments(np.zeros((5, 2)), [2, 2])
    with pytest.raises(ValueError):
        RaggedPaths.from_list([np.zeros((4, 2))], pad_to=2)


def test_bucket_ladder_and_assignment():
    lad = bucket_ladder(100, min_len=8, growth=2.0)
    assert lad[0] == 8 and lad[-1] >= 100
    assert all(b > a for a, b in zip(lad, lad[1:]))
    lengths = np.asarray([1, 8, 9, 16, 100])
    which = assign_buckets(lengths, lad)
    for L, k in zip(lengths, which):
        assert lad[k] >= L and (k == 0 or lad[k - 1] < L)
    with pytest.raises(ValueError):
        assign_buckets([101], lad)
    with pytest.raises(ValueError):
        bucket_ladder(10, growth=1.0)


def test_bucket_paths_exact(rng):
    paths = [np.cumsum(rng.normal(size=(L + 1, 2)).astype(np.float32), 0)
             for L in (2, 3, 17, 40, 9, 64, 33, 5)]
    rp = RaggedPaths.from_list(paths)
    full = signature(rp, 3)
    groups = bucket_paths(rp, bucket_ladder(64, min_len=8))
    covered = []
    for idx, sub in groups:
        assert sub.max_len <= 64
        s = signature(sub, 3)
        for j, i in enumerate(idx):
            covered.append(int(i))
            np.testing.assert_allclose(np.asarray(s[j]),
                                       np.asarray(full[i]), atol=1e-6)
    assert sorted(covered) == list(range(8))
    padded = pad_batch(rp, 16)
    assert padded.batch == 16
    np.testing.assert_allclose(np.asarray(signature(padded, 3)[:8]),
                               np.asarray(full), atol=0)


# ---------------------------------------------------------------------------
# DynamicBatcher serving layer
# ---------------------------------------------------------------------------

def test_dynamic_batcher_exact_and_bounded(rng):
    from repro.serve import DynamicBatcher
    reqs = [np.cumsum(rng.normal(size=(L + 1, 2)).astype(np.float32), 0)
            for L in (5, 40, 12, 3, 63, 21, 9, 2, 31, 17)]
    db = DynamicBatcher.signature_service(2, 3, max_len=64, backend="jax",
                                          min_bucket=8, max_batch=4)
    tickets = [db.submit(r) for r in reqs]
    res = db.flush()
    assert db.pending == 0 and set(res) == set(tickets)
    for t, r in zip(tickets, reqs):
        ref = signature(jnp.asarray(r)[None], 3)[0]
        np.testing.assert_allclose(np.asarray(res[t]), np.asarray(ref),
                                   atol=1e-6)
    st_ = db.stats()
    ladder = st_["ladder"]
    # the shape set is bounded by ladder x batch rungs, whatever the traffic
    assert st_["compiled_shapes"] <= len(ladder) * 3
    for rung, B in st_["shapes"]:
        assert rung in ladder and B <= 4
    # second wave reuses shapes (no growth for repeat traffic)
    n_shapes = st_["compiled_shapes"]
    t2 = [db.submit(r) for r in reqs]
    res2 = db.flush()
    assert db.stats()["compiled_shapes"] == n_shapes
    for t, r in zip(t2, reqs):
        ref = signature(jnp.asarray(r)[None], 3)[0]
        np.testing.assert_allclose(np.asarray(res2[t]), np.asarray(ref),
                                   atol=1e-6)


def test_dynamic_batcher_async_prefetch_matches_sync(rng):
    """async_dispatch places the next rung's padded batch on device while
    the current rung computes (bounded by max_in_flight) — results are
    identical to the synchronous path and the stats surface the overlap."""
    from repro.serve import DynamicBatcher
    reqs = [np.cumsum(rng.normal(size=(L + 1, 2)).astype(np.float32), 0)
            for L in (5, 40, 12, 3, 63, 21, 9, 2, 31, 17, 48, 7)]

    def run(**kw):
        db = DynamicBatcher.signature_service(2, 3, max_len=64,
                                              backend="jax", min_bucket=8,
                                              max_batch=4, **kw)
        tickets = [db.submit(r) for r in reqs]
        res = db.flush()
        return db, {id(r): res[t] for t, r in zip(tickets, reqs)}

    db_a, res_a = run(async_dispatch=True, max_in_flight=3)
    db_s, res_s = run(async_dispatch=False)
    for r in reqs:
        np.testing.assert_array_equal(np.asarray(res_a[id(r)]),
                                      np.asarray(res_s[id(r)]))
    sa, ss = db_a.stats(), db_s.stats()
    assert sa["async_dispatch"] and sa["max_in_flight"] == 3
    assert sa["prefetched_rungs"] >= 1, sa       # overlap actually happened
    assert sa["in_flight_peak"] >= 2, sa
    assert not ss["async_dispatch"] and ss["prefetched_rungs"] == 0, ss
    with pytest.raises(ValueError, match="max_in_flight"):
        DynamicBatcher.signature_service(2, 3, max_len=16, max_in_flight=0)


def test_dynamic_batcher_validation(rng):
    from repro.serve import DynamicBatcher
    db = DynamicBatcher.signature_service(2, 3, max_len=32, backend="jax")
    with pytest.raises(ValueError):
        db.submit(np.zeros((40, 2), np.float32))   # too long
    with pytest.raises(ValueError):
        db.submit(np.zeros((4, 3), np.float32))    # wrong d
    assert db.flush() == {}


def test_dynamic_batcher_scoring(rng):
    from repro.serve import DynamicBatcher, SigScoreEngine
    refs = jnp.asarray(np.cumsum(
        rng.normal(size=(5, 17, 2)).astype(np.float32) * 0.2, axis=1))
    eng = SigScoreEngine(d=2, depth=3, batch=2, references=refs,
                         backend="jax",
                         targets=np.arange(5, dtype=np.float32))
    db = DynamicBatcher.scoring_service(eng, max_len=32, min_bucket=8)
    # a full-length request equals the engine's own scoring of that path
    q = np.cumsum(rng.normal(size=(17, 2)).astype(np.float32) * 0.2, 0)
    t = db.submit(q)
    got = np.asarray(db.flush()[t])
    eng.state = eng.state.extend(
        jnp.asarray(q[1:] - q[:-1])[None].repeat(2, 0))
    want = np.asarray(eng.scores())[0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    pb = DynamicBatcher.scoring_service(eng, max_len=32, mode="predict")
    t2 = pb.submit(q)
    pred = np.asarray(pb.flush()[t2])
    np.testing.assert_allclose(
        pred, np.asarray(eng.predict())[0], atol=1e-5)
    with pytest.raises(ValueError):
        DynamicBatcher.scoring_service(eng, max_len=32, mode="nope")


# ---------------------------------------------------------------------------
# ragged windows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("route", ["fold", "chen"])
def test_ragged_windows_clip(rng, route):
    path, lengths = ragged_batch(rng, M=16)
    wins = np.asarray([[0, 4], [2, 10], [0, 16], [12, 16]])
    out = windowed_signature(path, wins, 3, route=route, lengths=lengths)
    for b, L in enumerate(lengths):
        for k, (l, r) in enumerate(wins):
            lc, rc = min(l, L), min(r, L)
            if rc > lc:
                ref = signature(path[b:b + 1, lc:rc + 1], 3)[0]
            else:
                ref = jnp.zeros_like(out[b, k])
            np.testing.assert_allclose(np.asarray(out[b, k]),
                                       np.asarray(ref), atol=1e-5)


def test_ragged_windowed_projection(rng):
    path, lengths = ragged_batch(rng, M=16)
    plan = make_plan(WORDS, 2)
    wins = np.asarray([[0, 8], [4, 16]])
    out = windowed_projection(path, wins, plan, route="fold",
                              lengths=lengths)
    full = windowed_signature(path, wins, plan.depth, route="fold",
                              lengths=lengths)
    from repro.core.words import flat_index
    idx = [flat_index(w, 2) for w in plan.words]
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(full)[..., idx], atol=1e-6)
    # RaggedPaths accepted directly
    rp = RaggedPaths.from_dense(path, lengths)
    out2 = windowed_signature(rp, wins, 3, route="fold")
    np.testing.assert_allclose(
        np.asarray(out2),
        np.asarray(windowed_signature(path, wins, 3, route="fold",
                                      lengths=lengths)), atol=0)


# ---------------------------------------------------------------------------
# ragged sigkernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_ragged_sig_gram(rng, backend):
    from repro.sigkernel import sig_gram
    x, xl = ragged_batch(rng, B=4, M=10)
    y, yl = ragged_batch(rng, B=3, M=14)
    K = sig_gram(x, y, 3, backend=backend, x_lengths=xl, y_lengths=yl)
    Sx = [signature(x[b:b + 1, :xl[b] + 1], 3)[0] for b in range(4)]
    Sy = [signature(y[b:b + 1, :yl[b] + 1], 3)[0] for b in range(3)]
    ref = np.asarray([[float(jnp.dot(a, c)) for c in Sy] for a in Sx])
    np.testing.assert_allclose(np.asarray(K), ref, atol=1e-5, rtol=1e-5)
    # RaggedPaths spelling agrees
    K2 = sig_gram(RaggedPaths.from_dense(x, xl),
                  RaggedPaths.from_dense(y, yl), 3, backend=backend)
    np.testing.assert_allclose(np.asarray(K2), np.asarray(K), atol=0)


def test_ragged_sig_mmd_grad(rng):
    from repro.sigkernel import sig_mmd
    x, xl = ragged_batch(rng, B=4, M=10)
    y, yl = ragged_batch(rng, B=3, M=8)
    val = sig_mmd(x, y, 3, x_lengths=xl, y_lengths=yl)
    assert np.isfinite(float(val))
    g = np.asarray(jax.grad(
        lambda a: sig_mmd(a, y, 3, x_lengths=xl, y_lengths=yl))(x))
    for b, L in enumerate(xl):
        assert np.all(g[b, L + 1:] == 0.0)
    # padding invariance of the statistic itself
    pad = jnp.concatenate(
        [x, jnp.asarray(rng.standard_normal((4, 5, 2)).astype(np.float32))],
        axis=1)
    val2 = sig_mmd(pad, y, 3, x_lengths=xl, y_lengths=yl)
    np.testing.assert_allclose(float(val2), float(val), atol=1e-6)


# ---------------------------------------------------------------------------
# ragged transforms
# ---------------------------------------------------------------------------

def test_transforms_ragged_invariance(rng):
    from repro.core import basepoint_augment, lead_lag, time_augment
    path, lengths = ragged_batch(rng, B=3, M=9)
    for name, fn in [("time", lambda p, l: time_augment(p, lengths=l)),
                     ("leadlag", lambda p, l: lead_lag(p, lengths=l)),
                     ("base", lambda p, l: basepoint_augment(p, l))]:
        out, nl = fn(path, jnp.asarray(lengths))
        sig = signature(out, 3, lengths=nl)
        for b, L in enumerate(lengths):
            ref_t, ref_l = fn(path[b:b + 1, :L + 1], jnp.asarray([L]))
            ref = signature(ref_t[:, :int(ref_l[0]) + 1], 3)[0]
            np.testing.assert_allclose(np.asarray(sig[b]), np.asarray(ref),
                                       atol=1e-6, err_msg=name)
    # without lengths: legacy single-return behaviour is untouched
    assert time_augment(path).shape == (3, 10, 3)
    assert lead_lag(path).shape == (3, 19, 4)
    assert basepoint_augment(path).shape == (3, 11, 2)


def test_time_augment_ragged_reaches_t1(rng):
    from repro.core import time_augment
    path, _ = ragged_batch(rng, B=2, M=8)
    lengths = jnp.asarray([8, 3])
    out, _ = time_augment(path, lengths=lengths)
    t = np.asarray(out[..., 0])
    assert np.isclose(t[1, 3], 1.0) and np.allclose(t[1, 3:], 1.0)
    assert np.isclose(t[0, -1], 1.0) and t[1, 2] < 1.0


# ---------------------------------------------------------------------------
# sig-head mask pass-through + trainer ragged MMD
# ---------------------------------------------------------------------------

def _sig_cfg(**kw):
    from repro.models.config import ModelConfig, SigHeadConfig
    return ModelConfig(name="t", family="decoder", n_layers=1, d_model=16,
                       n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=50,
                       sig_head=SigHeadConfig(channels=3, depth=3,
                                              backend="jax", **kw))


def test_sig_pool_mask_matches_unpadded(rng):
    from repro.models.sig_head import init_sig_head, sig_pool
    cfg = _sig_cfg()
    p = init_sig_head(jax.random.PRNGKey(0), cfg, 5)
    h = jnp.asarray(rng.standard_normal((3, 12, 16)).astype(np.float32))
    n_valid = [12, 7, 4]
    mask = jnp.asarray(np.arange(12)[None, :] < np.asarray(n_valid)[:, None])
    out = sig_pool(p, h, cfg, mask=mask)
    for b, n in enumerate(n_valid):
        ones = jnp.ones((1, n), bool)
        ref = sig_pool(p, h[b:b + 1, :n], cfg, mask=ones)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref[0]),
                                   atol=1e-5)
    # gradient w.r.t. masked-out hidden states is exactly zero
    g = np.asarray(jax.grad(
        lambda hh: jnp.sum(sig_pool(p, hh, cfg, mask=mask) ** 2))(h))
    for b, n in enumerate(n_valid):
        assert np.all(g[b, n:] == 0.0)


def test_sig_stream_features_mask(rng):
    from repro.models.sig_head import init_sig_head, sig_stream_features
    cfg = _sig_cfg()
    p = init_sig_head(jax.random.PRNGKey(1), cfg, 4)
    h = jnp.asarray(rng.standard_normal((2, 10, 16)).astype(np.float32))
    mask = jnp.asarray(np.arange(10)[None, :] < np.asarray([10, 5])[:, None])
    out = np.asarray(sig_stream_features(p, h, cfg, mask=mask))
    assert out.shape[:2] == (2, 9)
    assert np.all(out[1, 4:] == 0.0)       # post-end steps fully zeroed
    assert np.any(out[1, :4] != 0.0)


def test_sig_stream_features_mask_strided_no_pad_leak(rng):
    """stream_stride > 1: the true-terminal emission slot may cover past-end
    steps; its displacement must read X_L (clamped), never a pad-token
    projection — so pad hidden states get exactly zero gradient."""
    from repro.models.sig_head import init_sig_head, sig_stream_features
    cfg = _sig_cfg(stream_stride=3)
    p = init_sig_head(jax.random.PRNGKey(1), cfg, 4)
    h = jnp.asarray(rng.standard_normal((2, 10, 16)).astype(np.float32))
    n_valid = [10, 5]
    mask = jnp.asarray(
        np.arange(10)[None, :] < np.asarray(n_valid)[:, None])
    out = sig_stream_features(p, h, cfg, mask=mask)
    # terminal-slot features equal the unpadded per-example terminal slot
    ref = sig_stream_features(p, h[1:2, :5], cfg, mask=jnp.ones((1, 5),
                                                                bool))
    np.testing.assert_allclose(np.asarray(out[1, 1]), np.asarray(ref[0, -1]),
                               atol=1e-5)
    g = np.asarray(jax.grad(lambda hh: jnp.sum(
        sig_stream_features(p, hh, cfg, mask=mask) ** 2))(h))
    assert np.all(g[1, 5:] == 0.0)         # no gradient into pad positions
    assert np.any(g[1, :5] != 0.0)


def test_sig_kernel_pool_mask(rng):
    from repro.models.sig_head import init_sig_head, sig_pool
    cfg = _sig_cfg(kernel_landmarks=4, landmark_steps=5)
    p = init_sig_head(jax.random.PRNGKey(2), cfg, 5)
    h = jnp.asarray(rng.standard_normal((2, 8, 16)).astype(np.float32))
    mask = jnp.asarray(np.arange(8)[None, :] < np.asarray([8, 3])[:, None])
    out = sig_pool(p, h, cfg, mask=mask)
    ref = sig_pool(p, h[1:2, :3], cfg, mask=jnp.ones((1, 3), bool))
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref[0]),
                               atol=1e-5)


def test_trainer_ragged_sig_mmd(rng):
    """The trainer's sig_mmd loss consumes the ragged pipeline keys
    (paths + path_lengths) AND the backbone attention mask — finite loss,
    zero gradient into masked-out token positions' hidden states."""
    import dataclasses
    import repro.models as M
    from repro.configs import get_config, reduce_config, with_sig_head
    from repro.data import RaggedPathStream
    from repro.optim import adamw
    from repro.train import make_train_step
    base = reduce_config(get_config("qwen3-4b"))
    cfg = dataclasses.replace(
        with_sig_head(base, channels=3, depth=2, backend="jax"),
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
        vocab_size=64, head_dim=16)
    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    step = jax.jit(make_train_step(cfg, adamw(lr=1e-3), loss="sig_mmd"))
    batch = next(RaggedPathStream(batch=6, max_steps=8, d=3, seed=0))
    full = dict(batch,
                tokens=jnp.asarray(rng.integers(0, 64, size=(4, 9))),
                mask=jnp.asarray(np.arange(9)[None, :] < np.asarray(
                    [9, 6, 4, 9])[:, None], jnp.int32))
    opt_state = adamw(lr=1e-3).init(params)
    _, _, metrics = step(params, opt_state, full)
    assert np.isfinite(float(metrics["loss"]))


# ---------------------------------------------------------------------------
# ragged data pipeline determinism
# ---------------------------------------------------------------------------

def test_geometric_lengths_deterministic_and_skewed():
    from repro.data import geometric_lengths
    a = geometric_lengths(0, 4000, 256)
    assert np.array_equal(a, geometric_lengths(0, 4000, 256))
    assert a.min() >= 2 and a.max() <= 256
    assert a.max() / np.median(a) >= 4.0     # the serving-traffic shape
    assert not np.array_equal(a, geometric_lengths(1, 4000, 256))


def test_ragged_path_stream_seekable(rng):
    from repro.data import RaggedPathStream
    s1 = RaggedPathStream(batch=3, max_steps=16, d=2, seed=5)
    batches = [next(s1) for _ in range(3)]
    s2 = RaggedPathStream(batch=3, max_steps=16, d=2, seed=5)
    s2.restore({"step": 2, "seed": 5})
    b2 = next(s2)
    assert np.array_equal(np.asarray(b2["paths"]),
                          np.asarray(batches[2]["paths"]))
    p, L = np.asarray(batches[0]["paths"]), \
        np.asarray(batches[0]["path_lengths"])
    for b in range(3):                      # frozen tails
        assert np.all(p[b, L[b]:] == p[b, L[b]])
    # ragged fbm + token variants are deterministic too
    from repro.data import ragged_fbm_dataset, ragged_token_batches
    x1, l1, h1 = ragged_fbm_dataset(3, 4, 12, 2)
    x2, l2, h2 = ragged_fbm_dataset(3, 4, 12, 2)
    assert np.array_equal(x1, x2) and np.array_equal(l1, l2)
    t1 = next(iter(ragged_token_batches(30, 2, 10, seed=4)))
    t2 = next(iter(ragged_token_batches(30, 2, 10, seed=4)))
    assert np.array_equal(np.asarray(t1["tokens"]), np.asarray(t2["tokens"]))
    assert np.array_equal(np.asarray(t1["mask"]), np.asarray(t2["mask"]))
