"""Fused path transforms vs the materialising oracle (kernels/ops dispatch).

Every cell checks the fused route — raw increments + ``transform=`` into the
sweep — against ``apply_transform`` followed by the plain engine, on outputs
AND gradients (the §4.2 reverse sweeps pull the cotangent back through
``fused_adjoint``), across backend × backward × stream × ragged.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.transforms import (apply_transform, as_transform,
                                   transform_dim, transform_lengths)
from repro.core.words import all_words
from repro.kernels import ops
from tests.conftest import projected_engine

B, M, d, DEPTH = 5, 11, 2, 3
LENGTHS = np.asarray([11, 7, 1, 0, 5])


@pytest.fixture(autouse=True)
def _autotune_off(monkeypatch):
    monkeypatch.setenv("PATHSIG_AUTOTUNE", "off")


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    path = jnp.asarray(rng.standard_normal((B, M + 1, d)).astype(np.float32)
                       * 0.3)
    return path, jnp.diff(path, axis=1), path[:, 0]


def _aug(path, spec, lens):
    aug = apply_transform(path, spec, lengths=lens)
    return aug[0] if isinstance(aug, tuple) else aug


def _oracle_incs(path, spec, lens):
    return jnp.diff(_aug(path, spec, None if lens is None else lens), axis=1)


TRANSFORMS = ["time_augment", "lead_lag", "basepoint",
              "time_augment+lead_lag", "basepoint+lead_lag+time_augment"]


@pytest.mark.parametrize("ragged", [False, True], ids=["full", "ragged"])
@pytest.mark.parametrize("backend", ["pallas_interpret", "jax"])
@pytest.mark.parametrize("tname", TRANSFORMS)
def test_fused_signature_matches_materialised(data, tname, backend, ragged):
    path, incs, x0 = data
    spec = as_transform(tname)
    lens = jnp.asarray(LENGTHS) if ragged else None
    al = None if lens is None else transform_lengths(spec, lens)
    ref = ops.signature(_oracle_incs(path, spec, lens), DEPTH, backend="jax",
                        lengths=al)
    got = ops.signature(incs, DEPTH, backend=backend, transform=tname,
                        x0=x0, lengths=lens, batch_tile=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-6)


@pytest.mark.parametrize("bwd", ["inverse", "autodiff", "checkpoint"])
@pytest.mark.parametrize("backend", ["pallas_interpret", "jax"])
def test_fused_signature_grads(data, backend, bwd):
    path, incs, x0 = data
    tname = "basepoint+lead_lag+time_augment"
    spec = as_transform(tname)
    lens = jnp.asarray(LENGTHS)
    al = transform_lengths(spec, lens)
    from repro.core import sig_dim
    co = jnp.asarray(np.random.default_rng(1).standard_normal(
        (B, sig_dim(transform_dim(spec, d), DEPTH))).astype(np.float32))

    def fused(x, x0):
        return jnp.vdot(ops.signature(x, DEPTH, backend=backend, backward=bwd,
                                      transform=tname, x0=x0, lengths=lens,
                                      batch_tile=8), co)

    def oracle(x, x0):
        p = jnp.concatenate([x0[:, None], x0[:, None] + jnp.cumsum(x, 1)], 1)
        return jnp.vdot(ops.signature(_oracle_incs(p, spec, lens), DEPTH,
                                      backend="jax", lengths=al), co)

    gi, gx = jax.grad(fused, argnums=(0, 1))(incs, x0)
    ri, rx = jax.grad(oracle, argnums=(0, 1))(incs, x0)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(ri), atol=3e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=3e-5)


@pytest.mark.parametrize("ragged", [False, True], ids=["full", "ragged"])
@pytest.mark.parametrize("stride", [1, 3])
@pytest.mark.parametrize("backend", ["pallas_interpret", "jax"])
def test_fused_stream_matches_materialised(data, backend, stride, ragged):
    path, incs, x0 = data
    tname = "time_augment+lead_lag"
    spec = as_transform(tname)
    lens = jnp.asarray(LENGTHS) if ragged else None
    al = None if lens is None else transform_lengths(spec, lens)
    ref = ops.signature(_oracle_incs(path, spec, lens), DEPTH, backend="jax",
                        stream=True, stream_stride=stride, lengths=al)
    got = ops.signature(incs, DEPTH, backend=backend, stream=True,
                        stream_stride=stride, transform=tname, x0=x0,
                        lengths=lens, batch_tile=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-6)
    co = jnp.asarray(np.random.default_rng(2).standard_normal(
        ref.shape).astype(np.float32))
    gi = jax.grad(lambda x: jnp.vdot(ops.signature(
        x, DEPTH, backend=backend, stream=True, stream_stride=stride,
        transform=tname, x0=x0, lengths=lens, batch_tile=8), co))(incs)
    ri = jax.grad(lambda x: jnp.vdot(ops.signature(
        _oracle_incs(jnp.concatenate(
            [x0[:, None], x0[:, None] + jnp.cumsum(x, 1)], 1), spec, lens),
        DEPTH, backend="jax", stream=True, stream_stride=stride,
        lengths=al), co))(incs)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(ri), atol=3e-5)


@pytest.mark.parametrize("backend,ragged,engine", [
    *[pytest.param(b, r, "rule", id=f"{b}-{'ragged' if r else 'full'}")
      for b in ("pallas_interpret", "jax", "hybrid") for r in (False, True)],
    *[pytest.param("pallas_interpret", r, "words",
                   id=f"pallas_interpret-{'ragged' if r else 'full'}-words")
      for r in (False, True)],
])
def test_fused_projected_matches_materialised(data, backend, ragged, engine):
    path, incs, x0 = data
    spec = as_transform("time_augment+lead_lag")
    words = tuple(all_words(transform_dim(spec, d), 3))[:40]
    lens = jnp.asarray(LENGTHS) if ragged else None
    al = None if lens is None else transform_lengths(spec, lens)
    ref = ops.projected(_oracle_incs(path, spec, lens), words, backend="jax",
                        lengths=al)
    co = jnp.asarray(np.random.default_rng(3).standard_normal(
        ref.shape).astype(np.float32))
    with projected_engine(engine):
        got = ops.projected(incs, words, backend=backend, transform=spec,
                            lengths=lens, batch_tile=8)
        gi = jax.grad(lambda x: jnp.vdot(ops.projected(
            x, words, backend=backend, transform=spec, lengths=lens,
            batch_tile=8), co))(incs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-6)
    ri = jax.grad(lambda x: jnp.vdot(ops.projected(
        _oracle_incs(jnp.concatenate(
            [path[:, :1], path[:, :1] + jnp.cumsum(x, 1)], 1), spec, lens),
        words, backend="jax", lengths=al), co))(incs)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(ri), atol=3e-5)


@pytest.mark.parametrize("backend", ["pallas_interpret", "jax"])
def test_fused_projected_stream_and_forward_only(data, backend):
    path, incs, x0 = data
    spec = as_transform("time_augment+lead_lag")
    words = tuple(all_words(transform_dim(spec, d), 3))[:40]
    lens = jnp.asarray(LENGTHS)
    al = transform_lengths(spec, lens)
    e = _oracle_incs(path, spec, lens)
    ref = ops.projected(e, words, backend="jax", stream=True,
                        stream_stride=2, lengths=al)
    got = ops.projected(incs, words, backend=backend, stream=True,
                        stream_stride=2, transform=spec, lengths=lens,
                        batch_tile=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-6)
    reff = ops.projected_forward_only(e, words, backend="jax", lengths=al)
    gotf = ops.projected_forward_only(incs, words, backend=backend,
                                      transform=spec, lengths=lens,
                                      batch_tile=8)
    np.testing.assert_allclose(np.asarray(gotf), np.asarray(reff), atol=3e-6)


def test_basepoint_without_x0_raises(data):
    _, incs, _ = data
    with pytest.raises(ValueError, match="x0"):
        ops.signature(incs, DEPTH, backend="pallas_interpret",
                      transform="basepoint")


def test_projected_plan_over_raw_alphabet_raises(data):
    _, incs, _ = data
    from repro.core.words import make_plan
    plan = make_plan(tuple(all_words(d, 2)), d)  # raw alphabet prebuilt
    with pytest.raises(ValueError, match="augmented alphabet"):
        ops.projected(incs, plan, backend="jax",
                      transform="time_augment+lead_lag")


def test_core_signature_passes_x0_automatically(data):
    path, _, _ = data
    spec = as_transform("basepoint+time_augment")
    from repro.core.signature import signature as path_signature
    got = path_signature(path, DEPTH, transform="basepoint+time_augment",
                         backend="pallas_interpret")
    ref = ops.signature(_oracle_incs(path, spec, None), DEPTH, backend="jax")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-6)
