"""A deep-signature classifier of long multichannel series, EigenWorms-shaped.

The job of the UEA archive's EigenWorms set (arXiv:1811.00075) as the
signature methods train on it (arXiv:1905.08494): the series' increments
are mixed by a learned channel matrix, a time channel is fused in, the
truncated signature is taken through
``repro.kernels.ops.signature(transform="time_augment", backend="auto",
backward="inverse")`` (on a TPU, the cone kernel forward and the §4.2
reverse-scan kernel backward, both over time chunks), and a linear readout
gives the class logits under a cross-entropy.  Batches are random walks of
unit total variance with class labels drawn from the seed.

The reference is the same model with the textbook exp/Chen signature of
``bench/reference.py`` over the time-augmented increments, scanned in
about √M segments under ``jax.checkpoint``: the remat changes no
arithmetic, and autodiff through the plain scan would store the whole
(M, D_sig) state history of every row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, reference
from bench.generators import walk_increments

SCOPE = "bench_sig"


def sig_channels(cfg) -> int:
    return cfg["channels"] + 1          # the time channel


def init(cfg, key):
    d, k = cfg["channels"], cfg["classes"]
    D = reference.sig_dim(sig_channels(cfg), cfg["depth"])
    k1, k2 = jax.random.split(key)
    return {"mix": jnp.eye(d) + 0.1 * jax.random.normal(k1, (d, d)),
            "w": jax.random.normal(k2, (D, k)) / np.sqrt(D),
            "b": jnp.zeros((k,))}


def pool(cfg, key, n_batches: int, batch: int):
    k1, k2 = jax.random.split(key)
    shape = (n_batches, batch, cfg["steps"], cfg["channels"])
    return {"x": walk_increments(k1, shape),
            "y": jax.random.randint(k2, (n_batches, batch), 0,
                                    cfg["classes"])}


def program_sig(cfg, precision: str = "fp32"):
    from repro.kernels import ops

    def sig(x):
        with jax.named_scope(SCOPE):
            return ops.signature(x, cfg["depth"], transform=cfg["transform"],
                                 backend="auto", backward="inverse",
                                 precision=precision)
    return sig


def time_augmented(increments):
    """[t, x] increments: the time channel runs from 0 to 1 in M steps."""
    B, M, _ = increments.shape
    dt = jnp.full((B, M, 1), 1.0 / M, increments.dtype)
    return jnp.concatenate([dt, increments], axis=-1)


def segment_signature(increments, depth: int):
    """``reference.signature`` of (B, M, d) increments, its scan cut into
    about √M segments of which autodiff keeps only the boundary states.
    Zero steps pad the last segment: each is the identity update."""
    B, M, d = increments.shape
    seg = max(1, int(np.ceil(np.sqrt(M))))
    n = -(-M // seg)
    x = jnp.pad(increments, ((0, 0), (0, n * seg - M), (0, 0)))
    xs = jnp.moveaxis(x, 1, 0).reshape(n, seg, B, d)

    def step(levels, dx):
        return reference._chen(levels,
                               reference._exp_levels(dx, depth)), None

    @jax.checkpoint
    def segment(levels, chunk):
        return jax.lax.scan(step, levels, chunk)[0], None

    init = [jnp.zeros((B, d ** k), increments.dtype)
            for k in range(1, depth + 1)]
    final, _ = jax.lax.scan(segment, init, xs)
    return jnp.concatenate(final, axis=-1)


def reference_sig(cfg):
    return lambda x: segment_signature(time_augmented(x), cfg["depth"])


def aux(cfg, params, batch, sig):
    return None


def row_losses(cfg, params, aux_, batch, sig, mm=reference.HIGHEST):
    x = jnp.einsum("bmd,de->bme", batch["x"], params["mix"], precision=mm)
    logits = jnp.dot(sig(x), params["w"], precision=mm) + params["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, batch["y"][:, None], axis=1)[:, 0]


def sweep_counts(cfg, B: int) -> dict:
    """Ops and bytes of one step's signature work (``bench/counts.py``):
    a truncated sweep over M steps of the channels and time, which reads
    the raw (B, M, d) increments."""
    d, M, depth = sig_channels(cfg), cfg["steps"], cfg["depth"]
    nodes = counts.truncated_nodes(d, depth)
    return counts.sweep(B, M, d, depth, nodes, nodes[2],
                        in_elems=B * M * cfg["channels"])
