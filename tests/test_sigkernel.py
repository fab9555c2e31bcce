"""Tests for the repro.sigkernel subsystem: weighted/projected Gram matrices,
MMD, low-rank features, KRR + the serving/model/training integrations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.sigkernel as SK
from repro.core import anisotropic_words, sig_dim
from repro.core import tensor_ops as tops
from repro.kernels import ops
from tests.conftest import projected_engine


def make_path(rng, B, M, d, scale=0.3):
    return jnp.asarray(np.cumsum(rng.normal(size=(B, M + 1, d)) * scale,
                                 axis=1).astype(np.float32))


# ---------------------------------------------------------------------------
# sig_gram: tiled/Pallas routes vs the naive oracle (acceptance 1e-5 fp32)
# ---------------------------------------------------------------------------

GRAM_BACKENDS = ("jax", "pallas_interpret")


@pytest.mark.parametrize("backend", GRAM_BACKENDS)
def test_gram_truncated_matches_oracle(rng, backend):
    x = make_path(rng, 7, 30, 3)
    y = make_path(rng, 5, 22, 3)
    ref = np.asarray(SK.sig_gram(x, y, 4, route="oracle", backend="jax"))
    got = np.asarray(SK.sig_gram(x, y, 4, route="tiled", backend=backend,
                                 block_words=64))
    np.testing.assert_allclose(got, ref, atol=1e-5 * np.abs(ref).max())


@pytest.mark.parametrize("backend,engine", [
    *[pytest.param(b, "rule", id=b) for b in GRAM_BACKENDS],
    pytest.param("pallas_interpret", "words", id="pallas_interpret-words"),
])
def test_gram_projected_words_matches_oracle(rng, backend, engine):
    x = make_path(rng, 6, 25, 3)
    y = make_path(rng, 4, 25, 3)
    words = anisotropic_words((1.0, 1.0, 2.0), 4.0)
    ref = np.asarray(SK.sig_gram(x, y, words=words, route="oracle",
                                 backend="jax"))
    with projected_engine(engine):
        got = np.asarray(SK.sig_gram(x, y, words=words, route="tiled",
                                     backend=backend, block_words=16))
    assert ref.shape == (6, 4)
    np.testing.assert_allclose(got, ref, atol=1e-5 * np.abs(ref).max())


@pytest.mark.parametrize("backend", GRAM_BACKENDS)
def test_gram_anisotropic_weights_matches_oracle(rng, backend):
    x = make_path(rng, 6, 20, 3)
    kw = dict(gamma=(0.5, 1.0, 2.0), level_weights=(1.0, 0.5, 0.25, 0.125))
    ref = np.asarray(SK.sig_gram(x, None, 4, route="oracle", backend="jax",
                                 **kw))
    got = np.asarray(SK.sig_gram(x, None, 4, route="tiled", backend=backend,
                                 block_words=48, **kw))
    np.testing.assert_allclose(got, ref, atol=1e-5 * np.abs(ref).max())
    # symmetric input -> symmetric Gram
    np.testing.assert_allclose(got, got.T, atol=1e-5 * np.abs(ref).max())


def test_gram_weight_semantics_channel_scaling(rng):
    """ω_w = Π γ_{w_j} equals scaling path channel i by √γ_i (paper §7.2)."""
    x = make_path(rng, 4, 18, 3)
    y = make_path(rng, 4, 18, 3)
    gamma = (0.5, 1.3, 2.0)
    K = SK.sig_gram(x, y, 3, gamma=gamma)
    scale = jnp.sqrt(jnp.asarray(gamma))[None, None, :]
    K2 = SK.sig_gram(x * scale, y * scale, 3)
    np.testing.assert_allclose(np.asarray(K), np.asarray(K2),
                               atol=1e-5 * float(jnp.abs(K).max()))


def test_gram_explicit_weight_vector(rng):
    x = make_path(rng, 5, 16, 2)
    D = sig_dim(2, 3)
    w = jnp.asarray(np.random.default_rng(1).uniform(0.1, 2.0, D)
                    .astype(np.float32))
    K = SK.sig_gram(x, None, 3, weights=w, block_words=7)
    S = SK.signature_features(x, 3)
    ref = (S * w[None]) @ S.T
    np.testing.assert_allclose(np.asarray(K), np.asarray(ref), atol=1e-5)


def test_gram_psd(rng):
    """K = S diag(ω) Sᵀ with ω > 0 must be PSD for any path batch."""
    x = make_path(rng, 10, 24, 3)
    for kw in (dict(), dict(gamma=(0.5, 1.0, 2.0)),
               dict(level_weights=(1.0, 0.5, 0.25))):
        K = np.asarray(SK.sig_gram(x, None, 3, **kw))
        evals = np.linalg.eigvalsh((K + K.T) / 2)
        assert evals.min() >= -1e-5 * max(evals.max(), 1.0), kw


def test_gram_rejects_bad_args(rng):
    x = make_path(rng, 3, 10, 2)
    with pytest.raises(ValueError):
        SK.sig_gram(x, None)                      # neither depth nor words
    with pytest.raises(ValueError):
        SK.sig_gram(x, None, 3, weights=jnp.ones(5), gamma=(1.0, 1.0))
    with pytest.raises(ValueError):
        SK.sig_gram(x, None, 3, route="nope")
    with pytest.raises(ValueError):               # wrong-length weight vector
        SK.sig_gram(x, None, 3, weights=jnp.ones(5))
    with pytest.raises(ValueError):
        SK.word_weights(2, 2, gamma=(1.0, -1.0))
    with pytest.raises(ValueError):
        SK.word_weights(2, 3, level_weights=(1.0, 0.5))  # too short
    with pytest.raises(ValueError):                      # empty word
        SK.word_weights(words=[(), (0,)], level_weights=(0.5,))


def test_gram_product_rejects_shape_mismatch(rng):
    Sx = jnp.asarray(rng.normal(size=(3, 120)).astype(np.float32))
    Sy = jnp.asarray(rng.normal(size=(4, 120)).astype(np.float32))
    for backend in GRAM_BACKENDS:
        with pytest.raises(ValueError):           # weights too short
            ops.gram(Sx, Sy, jnp.ones(80), backend=backend)
        with pytest.raises(ValueError):           # word-dim mismatch
            ops.gram(Sx, Sy[:, :100], jnp.ones(120), backend=backend)


def test_kernel_head_rejects_logsig_combination():
    from repro.configs import get_config, reduce_config, with_sig_head
    from repro.models.sig_head import feature_dim
    cfg = with_sig_head(reduce_config(get_config("qwen3-4b")), channels=2,
                        depth=2, kernel_landmarks=4, use_logsig=True)
    with pytest.raises(NotImplementedError):
        feature_dim(cfg.sig_head)


# ---------------------------------------------------------------------------
# memory law: the tiled route never materialises (B_x, B_y, D_sig)
# ---------------------------------------------------------------------------

def test_gram_tiled_memory_block_sweep(rng):
    """XLA temp bytes of the tiled route stay far below the full
    (B_x, B_y, D_sig) intermediate for every block size in the sweep."""
    Bx, By, d, N = 48, 40, 4, 5
    D = sig_dim(d, N)                       # 1364
    Sx = jnp.asarray(rng.normal(size=(Bx, D)).astype(np.float32))
    Sy = jnp.asarray(rng.normal(size=(By, D)).astype(np.float32))
    w = jnp.ones((D,), jnp.float32)
    full = Bx * By * D * 4                  # ~10.5 MB would-be intermediate

    def temp_bytes(fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        mem = compiled.memory_analysis()
        return int(getattr(mem, "temp_size_in_bytes", 0))

    measured = {}
    for block in (64, 128, 341, 1364):
        tb = temp_bytes(lambda a, b, c, blk=block: ops.gram(
            a, b, c, backend="jax", block_words=blk), Sx, Sy, w)
        measured[block] = tb
    if all(tb == 0 for tb in measured.values()):
        pytest.skip("XLA memory_analysis reports no temp bytes here")
    for block, tb in measured.items():
        # O(B_x·B_y + B·block) live state, generous constants + padding slack
        bound = 8 * (Bx * By + (Bx + By) * block) * 4 + 2 ** 20
        assert tb < full / 4, (block, tb, full)
        assert tb < bound, (block, tb, bound)


# ---------------------------------------------------------------------------
# gram product dispatch: gradients (incl. weights) across backends
# ---------------------------------------------------------------------------

def test_gram_product_grads_match_reference(rng):
    Sx = jnp.asarray(rng.normal(size=(5, 37)).astype(np.float32))
    Sy = jnp.asarray(rng.normal(size=(4, 37)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.2, 2.0, 37).astype(np.float32))
    g_ref = jax.grad(lambda a, b, c: jnp.sum(((a * c[None]) @ b.T) ** 2),
                     argnums=(0, 1, 2))(Sx, Sy, w)
    for backend in GRAM_BACKENDS:
        g = jax.grad(lambda a, b, c: jnp.sum(ops.gram(
            a, b, c, backend=backend, block_words=16) ** 2),
            argnums=(0, 1, 2))(Sx, Sy, w)
        for got, ref in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# MMD: statistic + differentiability across backends (acceptance)
# ---------------------------------------------------------------------------

def test_mmd_zero_on_identical_samples(rng):
    x = make_path(rng, 6, 20, 3)
    m = float(SK.sig_mmd(x, x, 3, unbiased=False))
    assert abs(m) < 1e-4


def test_mmd_separates_distributions(rng):
    def drifted(drift, n=16):
        steps = rng.normal(size=(n, 24, 2)) * 0.15 + drift
        return jnp.asarray(np.concatenate(
            [np.zeros((n, 1, 2)), np.cumsum(steps, axis=1)],
            axis=1).astype(np.float32))

    x = drifted(+0.1)
    near = float(SK.sig_mmd(x, drifted(+0.1), 3))    # same distribution
    far = float(SK.sig_mmd(x, drifted(-0.1), 3))     # mean-shifted paths
    assert far > 0
    assert far > 10 * abs(near)


def test_mmd_grad_agrees_jax_vs_pallas_interpret(rng):
    """Acceptance: jax.grad of the MMD loss agrees across backends."""
    x = make_path(rng, 5, 18, 3)
    y = make_path(rng, 6, 18, 3)

    def grad_of(backend):
        return jax.grad(lambda a: SK.sig_mmd(
            a, y, 3, gamma=(0.5, 1.0, 1.5), backend=backend))(x)

    g_jax = np.asarray(grad_of("jax"))
    g_pal = np.asarray(grad_of("pallas_interpret"))
    assert np.isfinite(g_jax).all() and np.abs(g_jax).max() > 0
    np.testing.assert_allclose(g_pal, g_jax,
                               atol=1e-5 * np.abs(g_jax).max())


def test_mmd_unbiased_needs_two(rng):
    x = make_path(rng, 1, 10, 2)
    y = make_path(rng, 4, 10, 2)
    with pytest.raises(ValueError):
        SK.sig_mmd(x, y, 2)


# ---------------------------------------------------------------------------
# low-rank features
# ---------------------------------------------------------------------------

def test_random_word_features_exact_when_complete(rng):
    x = make_path(rng, 5, 18, 3)
    y = make_path(rng, 4, 18, 3)
    D = sig_dim(3, 3)
    fm = SK.random_word_features(3, 3, n_features=D, gamma=(0.5, 1.0, 2.0))
    K = np.asarray(fm(x) @ fm(y).T)
    ref = np.asarray(SK.sig_gram(x, y, 3, gamma=(0.5, 1.0, 2.0)))
    np.testing.assert_allclose(K, ref, atol=1e-4 * np.abs(ref).max())


def test_random_word_features_approximates(rng):
    x = make_path(rng, 6, 20, 2)
    D = sig_dim(2, 5)
    ref = np.asarray(SK.sig_gram(x, None, 5))
    # average over seeds: the estimator is unbiased, so the mean converges
    Ks = [np.asarray((f := SK.random_word_features(2, 5, D // 2, seed=s))(x)
                     @ f(x).T) for s in range(8)]
    err = np.abs(np.mean(Ks, axis=0) - ref).max() / np.abs(ref).max()
    assert err < 0.35, err


def test_nystrom_exact_on_landmarks(rng):
    x = make_path(rng, 6, 20, 3)
    ny = SK.nystrom_features(x, 3, level_weights=(1.0, 0.5, 0.25))
    phi = ny(x)
    ref = np.asarray(SK.sig_gram(x, None, 3, level_weights=(1.0, 0.5, 0.25)))
    np.testing.assert_allclose(np.asarray(phi @ phi.T), ref,
                               atol=1e-4 * np.abs(ref).max())


def test_nystrom_generalises_off_landmarks(rng):
    lm = make_path(rng, 24, 20, 2)
    ny = SK.nystrom_features(lm, 3)
    x = make_path(rng, 5, 20, 2)
    y = make_path(rng, 4, 20, 2)
    approx = np.asarray(ny(x) @ ny(y).T)
    ref = np.asarray(SK.sig_gram(x, y, 3))
    assert np.abs(approx - ref).max() / np.abs(ref).max() < 0.3


# ---------------------------------------------------------------------------
# KRR + reference scoring
# ---------------------------------------------------------------------------

def test_krr_interpolates_training_data(rng):
    x = make_path(rng, 12, 20, 2)
    y = jnp.asarray(rng.normal(size=(12,)).astype(np.float32))
    model = SK.fit_sig_krr(x, y, 3, reg=1e-8)
    np.testing.assert_allclose(np.asarray(model.predict(x)), np.asarray(y),
                               atol=1e-2)


def test_krr_multi_output_and_words(rng):
    words = [(0,), (1,), (0, 1), (1, 0), (0, 0, 1)]
    x = make_path(rng, 10, 16, 2)
    y = jnp.asarray(rng.normal(size=(10, 3)).astype(np.float32))
    model = SK.fit_sig_krr(x, y, words=words, reg=1e-6)
    pred = model.predict(make_path(rng, 4, 16, 2))
    assert pred.shape == (4, 3)
    assert np.isfinite(np.asarray(pred)).all()


def test_reference_scores_self_retrieval(rng):
    refs = make_path(rng, 8, 24, 3)
    S = SK.signature_features(refs, 3)
    w = jnp.asarray(SK.word_weights(3, 3))
    scores = np.asarray(SK.reference_scores(S, S, w))
    # RKHS cosine: diagonal is 1 and is the row-max (self-retrieval)
    np.testing.assert_allclose(np.diag(scores), 1.0, atol=1e-4)
    assert (scores.argmax(axis=1) == np.arange(8)).all()


# ---------------------------------------------------------------------------
# serving: SigScoreEngine
# ---------------------------------------------------------------------------

def test_sig_score_engine_streams_match_references(rng):
    from repro.serve import SigScoreEngine
    refs = make_path(rng, 5, 16, 3)
    eng = SigScoreEngine(d=3, depth=3, batch=5, references=refs,
                         backend="jax")
    incs = tops.path_increments(refs)       # stream the references themselves
    scores = np.asarray(eng.push(incs))
    assert scores.shape == (5, 5)
    assert (np.asarray(eng.nearest()) == np.arange(5)).all()
    np.testing.assert_allclose(np.diag(scores), 1.0, atol=1e-4)


def test_sig_score_engine_chunked_equals_one_shot(rng):
    from repro.serve import SigScoreEngine
    refs = make_path(rng, 4, 12, 2)
    incs = jnp.asarray(rng.normal(size=(3, 10, 2)).astype(np.float32) * 0.3)
    one = SigScoreEngine(d=2, depth=3, batch=3, references=refs,
                         backend="jax")
    one_scores = np.asarray(one.push(incs))
    two = SigScoreEngine(d=2, depth=3, batch=3, references=refs,
                         backend="jax")
    two.push(incs[:, :4])
    two_scores = np.asarray(two.push(incs[:, 4:]))
    np.testing.assert_allclose(two_scores, one_scores, atol=1e-5)


def test_sig_score_engine_krr_predict_and_window(rng):
    from repro.serve import SigScoreEngine
    refs = make_path(rng, 6, 14, 2)
    targets = jnp.asarray(rng.normal(size=(6, 2)).astype(np.float32))
    eng = SigScoreEngine(d=2, depth=2, batch=3, references=refs,
                         targets=targets, window=8, backend="jax",
                         level_weights=(1.0, 0.5))
    for _ in range(3):
        eng.push(jnp.asarray(rng.normal(size=(3, 5, 2)).astype(np.float32)))
    assert eng.state.length == 8            # hopping window stays bounded
    pred = eng.predict()
    assert pred.shape == (3, 2) and np.isfinite(np.asarray(pred)).all()
    eng.reset()
    assert eng.state.length == 0


def test_sig_score_engine_requires_targets_for_predict(rng):
    from repro.serve import SigScoreEngine
    refs = make_path(rng, 3, 10, 2)
    eng = SigScoreEngine(d=2, depth=2, batch=2, references=refs,
                         backend="jax")
    with pytest.raises(ValueError):
        eng.predict()


# ---------------------------------------------------------------------------
# model head + trainer loss
# ---------------------------------------------------------------------------

def test_sig_kernel_head_forward_and_grads(rng):
    from repro.configs import get_config, reduce_config, with_sig_head
    from repro.models.sig_head import feature_dim, init_sig_head, sig_pool
    cfg = with_sig_head(reduce_config(get_config("qwen3-4b")), channels=3,
                        depth=3, kernel_landmarks=6, backend="jax")
    assert feature_dim(cfg.sig_head) == 6 + 3
    p = init_sig_head(jax.random.PRNGKey(0), cfg, 5)
    assert p["landmarks"].shape == (6, cfg.sig_head.landmark_steps + 1, 3)
    h = jnp.asarray(rng.normal(size=(2, 12, cfg.d_model)).astype(np.float32))
    out = sig_pool(p, h, cfg)
    assert out.shape == (2, 5)
    g = jax.grad(lambda pp: jnp.sum(sig_pool(pp, h, cfg) ** 2))(p)
    for key in ("proj", "out", "landmarks"):
        assert float(jnp.linalg.norm(g[key])) > 0, key


def test_sig_kernel_head_matches_manual_gram(rng):
    from repro.configs import get_config, reduce_config, with_sig_head
    from repro.models.sig_head import init_sig_head, sig_kernel_pool, \
        _learned_path
    from repro.core import signature
    cfg = with_sig_head(reduce_config(get_config("qwen3-4b")), channels=2,
                        depth=2, kernel_landmarks=4, kernel_normalize=False,
                        kernel_level_decay=0.5, backend="jax")
    p = init_sig_head(jax.random.PRNGKey(1), cfg, 3)
    h = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)).astype(np.float32))
    out = np.asarray(sig_kernel_pool(p, h, cfg))
    path = _learned_path(p, h, cfg.sig_head)
    S = signature(path, 2)
    S_l = signature(p["landmarks"].astype(jnp.float32), 2)
    w = jnp.asarray(SK.word_weights(2, 2, level_weights=(0.5, 0.25)))
    K = (S * w[None]) @ S_l.T
    feats = jnp.concatenate([K, path[:, -1] - path[:, 0]], axis=-1)
    ref = np.asarray(feats @ p["out"])
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_trainer_sig_mmd_loss_decreases(rng):
    import dataclasses
    import repro.models as M
    from repro.configs import get_config, reduce_config, with_sig_head
    from repro.optim import adamw
    from repro.train import make_train_step
    base = reduce_config(get_config("qwen3-4b"))
    cfg = dataclasses.replace(
        with_sig_head(base, channels=2, depth=2, backend="jax"),
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
        vocab_size=64, head_dim=16)
    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    step = jax.jit(make_train_step(cfg, adamw(lr=3e-3), loss="sig_mmd"))
    batch = {"tokens": jnp.asarray(rng.integers(0, 64, size=(4, 12))),
             "paths": make_path(rng, 8, 11, 2)}
    opt_state = adamw(lr=3e-3).init(params)
    losses = []
    for _ in range(8):
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_trainer_rejects_unknown_loss():
    from repro.configs import get_config, reduce_config, with_sig_head
    from repro.optim import adamw
    from repro.train import make_train_step
    cfg = reduce_config(get_config("qwen3-4b"))
    with pytest.raises(ValueError):
        make_train_step(cfg, adamw(lr=1e-3), loss="nope")
    with pytest.raises(ValueError):  # sig_mmd without a sig head config
        make_train_step(cfg, adamw(lr=1e-3), loss="sig_mmd")
    enc = with_sig_head(reduce_config(get_config("whisper-large-v3")),
                        channels=2, depth=2)
    with pytest.raises(ValueError):  # encdec has no backbone trajectory
        make_train_step(enc, adamw(lr=1e-3), loss="sig_mmd")


def test_eval_step_follows_trained_loss(rng):
    import dataclasses
    import repro.models as M
    from repro.configs import get_config, reduce_config, with_sig_head
    from repro.train import make_eval_step
    base = reduce_config(get_config("qwen3-4b"))
    cfg = dataclasses.replace(
        with_sig_head(base, channels=2, depth=2, backend="jax"),
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
        vocab_size=64, head_dim=16)
    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    batch = {"tokens": jnp.asarray(rng.integers(0, 64, size=(4, 12))),
             "paths": make_path(rng, 8, 11, 2)}   # no labels: MMD-only batch
    metrics = make_eval_step(cfg, loss="sig_mmd")(params, batch)
    assert np.isfinite(float(metrics["sig_mmd"]))


def test_sig_stream_features_rejects_kernel_head(rng):
    from repro.configs import get_config, reduce_config, with_sig_head
    from repro.models.sig_head import init_sig_head, sig_stream_features
    cfg = with_sig_head(reduce_config(get_config("qwen3-4b")), channels=2,
                        depth=2, kernel_landmarks=4, backend="jax")
    p = init_sig_head(jax.random.PRNGKey(0), cfg, 3)
    h = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)).astype(np.float32))
    with pytest.raises(NotImplementedError):
        sig_stream_features(p, h, cfg)
