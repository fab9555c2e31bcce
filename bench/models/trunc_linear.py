"""A linear readout of the truncated signature of a learned channel mix.

The pathsig paper's training step at Table 1's shapes: the increments are
mixed by a learned d×d matrix, signed to depth N through
``repro.kernels.ops.signature(backend="auto", backward="inverse")`` (the
cone kernel forward and the §4.2 backward on a TPU) and read out by a
linear map, under a mean squared error.  Batches are random walks of unit
total variance with standard normal targets.

The reference is the same model with the textbook exp/Chen signature of
``bench/reference.py`` in place of the program's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, reference
from bench.generators import walk_increments

SCOPE = "bench_sig"


def init(cfg, key):
    d, D = cfg["channels"], reference.sig_dim(cfg["channels"], cfg["depth"])
    k1, k2 = jax.random.split(key)
    return {"mix": jnp.eye(d) + 0.1 * jax.random.normal(k1, (d, d)),
            "w": jax.random.normal(k2, (D,)) / np.sqrt(D)}


def pool(cfg, key, n_batches: int, batch: int):
    k1, k2 = jax.random.split(key)
    shape = (n_batches, batch, cfg["steps"], cfg["channels"])
    return {"x": walk_increments(k1, shape),
            "y": jax.random.normal(k2, (n_batches, batch))}


def program_sig(cfg, precision: str = "fp32"):
    from repro.kernels import ops

    def sig(x):
        with jax.named_scope(SCOPE):
            return ops.signature(x, cfg["depth"], backend="auto",
                                 backward="inverse", precision=precision)
    return sig


def reference_sig(cfg):
    return lambda x: reference.signature(x, cfg["depth"])


def aux(cfg, params, batch, sig):
    return None


def row_losses(cfg, params, aux_, batch, sig, mm=reference.HIGHEST):
    x = jnp.einsum("bmd,de->bme", batch["x"], params["mix"],
                   precision=mm)
    pred = jnp.dot(sig(x), params["w"], precision=mm)
    return (pred - batch["y"]) ** 2


def sweep_counts(cfg, B: int) -> dict:
    """Ops and bytes of one step's signature work (``bench/counts.py``)."""
    return counts.truncated_sweep(B, cfg["steps"], cfg["channels"],
                                  cfg["depth"])
