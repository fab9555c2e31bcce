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

from repro.core.words import all_words, anisotropic_words
from repro.kernels import ops
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
    (1024, 100, 6, 5, 1),     # split >= 1: 6 cones of 1555 rows each
])
def test_sig_trunc_compiles(one_chip, B, M, d, depth, split):
    txt = _compiled_text(
        lambda x: sig_trunc(x, depth, split=split, interpret=False),
        _f32(one_chip, B, M, d))
    assert "tpu_custom_call" in txt


def test_inverse_training_step_compiles(one_chip):
    """§4.2: kernel forward, inverse-reconstruction backward."""
    sig = ops._pallas_sig_inverse(5, 128, None, False)
    step = jax.value_and_grad(lambda x: jnp.sum(sig(x) ** 2))
    txt = _compiled_text(step, _f32(one_chip, 1024, 100, 6))
    assert "tpu_custom_call" in txt


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
