"""The EigenWorms-shaped cell's model (``bench/models/sig_classifier.py``)
at sizes a test run holds: its segment-remat reference against the plain
scan, its counts against the scalar sweep, the program's kernels against
the reference, ``correct`` on the CPU, and the cells that name it."""
import functools
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, harness, reference
from bench.drivers import train_closed
from bench.models import sig_classifier as model
from bench.tests.test_bench_counts import sweep

CELL = "eigenworms_d6n4.train"
SMALL = dict(channels=2, steps=23, depth=3, classes=3, batch_per_chip=16,
             ref_block_rows=8)


def small_cfg():
    cfg = harness.load_cell(CELL).cfg
    cfg.update(SMALL)
    return cfg


def _batch(cfg, seed=0):
    p = model.pool(cfg, jax.random.PRNGKey(seed), 1, cfg["batch_per_chip"])
    return jax.tree.map(lambda a: a[0], p)


def _loss_grad(cfg, sig, params, batch):
    def f(p):
        return jnp.mean(model.row_losses(cfg, p, None, batch, sig))
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(f)(params)


def test_segment_remat_matches_plain_scan():
    cfg = small_cfg()
    params = model.init(cfg, jax.random.PRNGKey(1))
    batch = _batch(cfg)
    plain = lambda x: reference.signature(  # noqa: E731
        model.time_augmented(x), cfg["depth"])
    v1, g1 = _loss_grad(cfg, model.reference_sig(cfg), params, batch)
    v2, g2 = _loss_grad(cfg, plain, params, batch)
    np.testing.assert_allclose(v1, v2, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_time_channel_matches_the_program_transform():
    from repro.core.transforms import augment_increments
    x = _batch(small_cfg())["x"]
    np.testing.assert_allclose(model.time_augmented(x),
                               augment_increments(x, "time_augment"),
                               rtol=1e-6, atol=1e-7)


def test_sweep_counts_match_scalar_sweep():
    """Three channels (two and time) at depth 3 over 3 steps, counted one
    operation at a time; bytes read the raw increments."""
    cfg = dict(SMALL, steps=3)
    incs = np.random.default_rng(0).normal(size=(3, 3)) * 0.5
    words = counts.all_words(3, 3)
    _, fwd_ops, bwd_ops, _ = sweep(incs, words, 3)
    c = model.sweep_counts(cfg, 5)
    assert c["fwd_ops"] == 5 * fwd_ops and c["bwd_ops"] == 5 * bwd_ops
    D = reference.sig_dim(3, 3)
    assert c["fwd_bytes"] == 4 * (5 * 3 * 2 + 5 * D)
    assert c["bwd_bytes"] == 4 * (2 * 5 * 3 * 2 + 2 * 5 * D)


def test_cell_counts():
    """The full step: 7 signature channels, D_sig 2,800, 17,983 steps."""
    cfg = harness.load_cell(CELL).cfg
    assert reference.sig_dim(model.sig_channels(cfg), cfg["depth"]) == 2800
    c = model.sweep_counts(cfg, 128)
    assert c["fwd_bytes"] == 4 * (128 * 17_983 * 6 + 128 * 2800)


@pytest.fixture
def time_chunks(monkeypatch):
    """The program's kernels in chunks of 8 forward steps (4 backward)."""
    st = importlib.import_module("repro.kernels.sig_trunc")
    jax.clear_caches()
    monkeypatch.setattr(st, "TIME_BLOCK_BYTES",
                        8 * 2 * st.block_bytes((1, 8, 8)))
    monkeypatch.setenv("PATHSIG_AUTOTUNE", "off")
    yield
    monkeypatch.undo()
    jax.clear_caches()


def test_program_kernels_match_reference(time_chunks):
    """The Pallas forward and §4.2 backward (interpret mode, 3 chunks
    forward and 6 backward, the last ragged) against the reference: the
    signature, its increments' cotangent and the parameters' gradients."""
    from repro.kernels import ops
    cfg = small_cfg()
    params = model.init(cfg, jax.random.PRNGKey(2))
    batch = _batch(cfg, 3)

    def prog(x):
        return ops.signature(x, cfg["depth"], transform=cfg["transform"],
                             backend="pallas_interpret", backward="inverse")
    ref = model.reference_sig(cfg)
    x = batch["x"]
    s_p, vjp_p = jax.vjp(prog, x)
    s_r, vjp_r = jax.vjp(ref, x)
    np.testing.assert_allclose(s_p, s_r, rtol=1e-5, atol=1e-6)
    co = jax.random.normal(jax.random.PRNGKey(4), s_r.shape)
    dx_p, = vjp_p(co)
    dx_r, = vjp_r(co)
    scale = float(jnp.max(jnp.abs(dx_r)))
    np.testing.assert_allclose(dx_p, dx_r, rtol=1e-4, atol=1e-5 * scale)
    v_p, g_p = _loss_grad(cfg, prog, params, batch)
    v_r, g_r = _loss_grad(cfg, ref, params, batch)
    np.testing.assert_allclose(v_p, v_r, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_p), jax.tree.leaves(g_r)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("plant", [None, "control", "half"])
def test_small_cell_correct(monkeypatch, plant):
    """A run of the cell at a small size on the CPU, with the cell's own
    limits: sound it passes, the bfloat16 control and a step over half of
    each batch do not."""
    if plant is not None:
        kw = ({"precision": "bf16_fp32"} if plant == "control"
              else {"fault": plant})
        monkeypatch.setattr(train_closed, "build",
                            functools.partial(train_closed.build, **kw))
    cell = harness.load_cell(CELL)
    cell.cfg.update(SMALL)
    run = harness.Run(cell, 2**33 + 7, 0.5, False, jax.devices()[:1],
                      time.time())
    out = harness.execute(run)
    assert out["correct"] is (plant is None), out["checks"]


@pytest.mark.parametrize("name,layer", [
    (CELL, "trunc_roofline.train"),
    ("t1_d4n5.train_dp4", "collective_share.train"),
])
def test_new_cells_report_step_ms(name, layer):
    cell = harness.load_cell(name)
    assert {m["name"] for m in cell.e2e} == {"step_ms", "setup_s"}
    assert layer in {m["name"] for m in cell.per_layer}
    assert {"bwd_share.train", "idle_share.train"} <= {
        m["name"] for m in cell.per_layer}



def test_program_sig_at_the_cell_size():
    """The program's signature takes the cell's whole batch of full-length
    series to D_sig features, as the reference does (shapes only)."""
    cfg = harness.load_cell(CELL).cfg
    x = jax.ShapeDtypeStruct((cfg["batch_per_chip"], cfg["steps"],
                              cfg["channels"]), jnp.float32)
    want = (cfg["batch_per_chip"],
            reference.sig_dim(model.sig_channels(cfg), cfg["depth"]))
    assert jax.eval_shape(model.program_sig(cfg), x).shape == want
    assert jax.eval_shape(model.reference_sig(cfg), x).shape == want
