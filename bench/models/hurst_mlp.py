"""Hurst estimation from a sparse lead-lag signature (pathsig paper §8).

A learned per-channel scale of the path, the lead-lag transform, the
signature projected onto the sparse lead-lag word set (the concatenations
of the generators (L_i), (ℓ_i, L_i), (L_i, ℓ_i) up to the depth), the
signed log, a whitening frozen at set-up, and an MLP head (GELU) trained
under a mean squared error on the Hurst exponent.  The program's side
goes through ``repro.core.projected_signature(..., transform="lead_lag",
backend="auto")``: the word kernel with the transform fused, and the
projected §4.2 backward.

The reference takes the words' coordinates from the full signature of the
materialised lead-lag path (``bench/reference.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, reference
from bench.generators import fbm_paths_device

SCOPE = "bench_sig"


def words(cfg) -> list:
    return reference.sparse_leadlag_words(cfg["channels"], cfg["depth"])


def init(cfg, key):
    sizes = [len(words(cfg))] + list(cfg["mlp"]) + [1]
    layers = []
    for k, (a, b) in zip(jax.random.split(key, len(sizes) - 1),
                         zip(sizes[:-1], sizes[1:])):
        layers.append({"w": jax.random.normal(k, (a, b)) * np.sqrt(2.0 / a),
                       "b": jnp.zeros((b,))})
    layers[-1]["b"] = layers[-1]["b"] + 0.5   # the prior mean of H
    return {"scale": jnp.ones((cfg["channels"],)), "mlp": layers}


def pool(cfg, key, n_batches: int, batch: int):
    X, H = fbm_paths_device(key, n_batches * batch, cfg["steps"],
                            cfg["channels"], tuple(cfg["hurst_range"]))
    return {"x": X.reshape(n_batches, batch, *X.shape[1:]),
            "y": H.reshape(n_batches, batch)}


def program_sig(cfg, precision: str = "fp32"):
    from repro.core import projected_signature
    ws = tuple(words(cfg))

    def sig(x):
        with jax.named_scope(SCOPE):
            return projected_signature(x, ws, transform="lead_lag",
                                       backend="auto", precision=precision)
    return sig


def reference_sig(cfg):
    ws, d2 = words(cfg), 2 * cfg["channels"]

    def sig(x):
        ll = reference.lead_lag(x)
        return reference.projected(ll[:, 1:] - ll[:, :-1], ws, d2)
    return sig


def _features(params, x, sig):
    f = sig(x * params["scale"][None, None, :])
    return jnp.sign(f) * jnp.log1p(jnp.abs(f))


def aux(cfg, params, batch, sig):
    """The frozen whitening: feature mean and spread over one batch."""
    f = _features(params, batch["x"], sig)
    return {"mu": jnp.mean(f, 0), "sd": jnp.std(f, 0) + 1e-6}


def row_losses(cfg, params, aux_, batch, sig, mm=reference.HIGHEST):
    h = (_features(params, batch["x"], sig) - aux_["mu"]) / aux_["sd"]
    layers = params["mlp"]
    for i, p in enumerate(layers):
        h = jnp.dot(h, p["w"], precision=mm) + p["b"]
        if i < len(layers) - 1:
            h = jax.nn.gelu(h)
    return (h[:, 0] - batch["y"]) ** 2


def sweep_counts(cfg, B: int) -> dict:
    """Ops and bytes of one step's signature work (``bench/counts.py``):
    the sweep runs 2M lead-lag steps over 2d channels and reads the raw
    (B, M, d) increments."""
    d, M = cfg["channels"], cfg["steps"]
    return counts.projected_sweep(B, 2 * M, 2 * d, words(cfg),
                                  in_elems=B * M * d)
