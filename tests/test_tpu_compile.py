"""Compile the Pallas kernels for a described TPU v5e, no chip attached.

Interpret mode runs a kernel but never shows it to Mosaic, the TPU's kernel
compiler, which refuses blocks off the (8, 128) tile, ops it has no lowering
for, and more VMEM than the chip holds.  These tests lower and compile each
kernel of the main path at the widths ``chip_smoke.py`` runs on the chip
and check that the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.transforms import as_transform
from repro.core.words import all_words, anisotropic_words, generated_words
from repro.kernels import ops, sig_trunc_bwd
from repro.kernels.sig_gram import sig_gram_tiles
from repro.kernels.sig_trunc import sig_trunc
from repro.kernels.sig_words import sig_words


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("B,M,d,depth,split", [
    (1024, 500, 4, 5, 0),     # paper Table 1 widths, one cone per tile
    (1024, 100, 6, 5, 1),     # split >= 1: 6 cones of 1568 rows each
    (128, 17_983, 7, 4, 0),   # EigenWorms' channel count, in time chunks
    (128, 17_983, 7, 4, 1),   # its 7 cones, levels of 1, 7, 49, 343 rows
])
def test_sig_trunc_compiles(one_chip, B, M, d, depth, split):
    txt = _compiled_text(
        lambda x: sig_trunc(x, depth, split=split, interpret=False),
        _f32(one_chip, B, M, d))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("B,M,d,depth,kernels", [
    (1024, 500, 4, 5, 2),     # paper Table 1 widths: forward and backward
    (1024, 100, 6, 5, None),  # whichever backward the VMEM check picks
])
def test_inverse_training_step_compiles(one_chip, B, M, d, depth, kernels):
    """§4.2: kernel forward, and the reverse-scan kernel backward where its
    blocks fit VMEM (else the XLA scan)."""
    sig = ops._pallas_sig_inverse(depth, 128, None, False)
    step = jax.value_and_grad(lambda x: jnp.sum(sig(x) ** 2))
    txt = _compiled_text(step, _f32(one_chip, B, M, d))
    if kernels is None:
        kernels = 2 if sig_trunc_bwd.fits(B, M, d, depth, 128) else 1
    assert txt.count("tpu_custom_call") == kernels


@pytest.mark.parametrize("B", [128, 256])
def test_long_fused_training_step_compiles(one_chip, B):
    """EigenWorms' shapes, 17,983 steps of 6 channels and a fused time
    channel at depth 4: both kernels stream the time axis in chunks, and
    the backward is the kernel's, not the scan's."""
    sig = ops._pallas_sig_fused_inverse(4, 128, None, False,
                                        as_transform("time_augment"), "fp32")
    step = jax.value_and_grad(lambda x, t: jnp.sum(sig(x, t) ** 2))
    txt = _compiled_text(step, _f32(one_chip, B, 17_983, 6),
                         _f32(one_chip, B, 2))
    assert txt.count("tpu_custom_call") == 2


def test_dense_projected_training_step_compiles(one_chip):
    """Paper §8's sparse lead-lag words over 5 channels at depth 3, 1,024
    paths of 250 steps: the dense route's cone forward with the lead-lag
    fused, its §4.2 kernel backward and the words' gather."""
    d = 5
    gens = [(d + i,) for i in range(d)] + [
        w for i in range(d) for w in ((i, d + i), (d + i, i))]
    wplan = ops._plan_for_words(tuple(generated_words(gens, 3)), 2 * d)
    assert (len(wplan.words), wplan.closure_size) == (260, 285)
    kspec = as_transform("lead_lag")
    assert ops._trunc_kernels_fit(1024, 250, d, 3, 128, None, "fp32", kspec)
    sig = ops._pallas_sig_fused_inverse(3, 128, None, False, kspec, "fp32")
    step = jax.value_and_grad(lambda x, t: jnp.sum(jnp.sin(
        ops._dense_words(sig(x, t), wplan))))
    txt = _compiled_text(step, _f32(one_chip, 1024, 250, d),
                         _f32(one_chip, 1024, 2))
    assert txt.count("tpu_custom_call") == 2


def test_streamed_sig_trunc_compiles(one_chip):
    """Every prefix signature of the fig3 path: the emission block only
    fits VMEM at a higher split, which the kernel picks."""
    txt = _compiled_text(
        lambda x: sig_trunc(x, 4, interpret=False, stream=True),
        _f32(one_chip, 32, 2048, 4))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("words", ["all", "anisotropic"])
@pytest.mark.parametrize("stream", [False, True])
def test_multi_tile_sig_words_compiles(one_chip, words, stream):
    """The closure plan ``ops.projected`` runs, at its default tile rows."""
    ws = (all_words(4, 4) if words == "all"
          else anisotropic_words((1.0, 1.0, 2.0, 2.0), 6.0))
    tplan = ops._closure_tiled_plan(tuple(ws), 4, 256)
    assert len(tplan.tiles) > 1
    txt = _compiled_text(
        lambda x: sig_words(x, tplan, interpret=False, stream=stream,
                            stream_stride=4),
        _f32(one_chip, 512, 200, 4))
    assert "tpu_custom_call" in txt


def test_sig_gram_tiles_compiles(one_chip):
    D = 1364                   # d=4, depth 5
    txt = _compiled_text(
        lambda a, b, w: sig_gram_tiles(a, b, w, interpret=False),
        _f32(one_chip, 1024, D), _f32(one_chip, 1024, D), _f32(one_chip, D))
    assert "tpu_custom_call" in txt
