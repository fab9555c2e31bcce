"""Seeded input generators of the benchmark.

Copies of the program's generators (``repro.data.pipeline``), kept here so
that a change to the program cannot move the yardstick:
:func:`geometric_lengths` is copied as it was; :func:`fbm_paths_device` is
``fbm_paths``'s Cholesky construction run on the device in one jitted
call, since a pool of 8,192 paths, each with its own Hurst exponent, takes
the host far longer.  Every draw is a pure function of the seed it is
given.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def geometric_lengths(seed: int, n: int, max_steps: int, min_steps: int = 2,
                      mean_frac: float = 0.25) -> np.ndarray:
    """Deterministic geometric-ish per-request lengths in
    [min_steps, max_steps]; the pre-clip mean is ``mean_frac · max_steps``.
    Same (seed, n, max_steps) -> same lengths, always."""
    if not 1 <= min_steps <= max_steps:
        raise ValueError(f"need 1 <= min_steps <= max_steps, got "
                         f"{min_steps}, {max_steps}")
    rng = np.random.default_rng((7919, seed))  # domain-separated from paths
    p = min(1.0, 1.0 / max(mean_frac * max_steps, 1.0))
    return np.clip(rng.geometric(p, size=n), min_steps,
                   max_steps).astype(np.int64)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, 32 bits or more."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def fbm_paths_device(key, n_paths: int, n_steps: int, d: int,
                     h_range=(0.25, 0.75), T: float = 1.0):
    """(paths (n_paths, n_steps+1, d), H (n_paths,)): exact fBm by the
    Cholesky factor of its covariance, H ~ U(h_range) per path, X_0 = 0,
    components independent.  Call it under ``jax.jit``."""
    kh, kz = jax.random.split(key)
    H = jax.random.uniform(kh, (n_paths,), jnp.float32, *h_range)
    t = jnp.linspace(T / n_steps, T, n_steps, dtype=jnp.float32)
    tt, ss = t[:, None], t[None, :]

    def one(args):
        h, z = args
        cov = 0.5 * (tt ** (2 * h) + ss ** (2 * h)
                     - jnp.abs(tt - ss) ** (2 * h))
        L = jnp.linalg.cholesky(cov + 1e-6 * jnp.eye(n_steps))
        return jnp.einsum("ts,sd->td", L, z,
                          precision=jax.lax.Precision.HIGHEST)

    z = jax.random.normal(kz, (n_paths, n_steps, d), jnp.float32)
    X = jax.lax.map(one, (H, z), batch_size=256)
    X = jnp.concatenate([jnp.zeros((n_paths, 1, d), X.dtype), X], axis=1)
    return X, H


def walk_increments(key, shape) -> jax.Array:
    """(..., M, d) increments of a random walk of unit total variance."""
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])


def open_loop_schedule(seed: int, rate: float, seconds: float,
                       max_len: int, min_len: int, mean_frac: float):
    """Arrival offsets (s) and lengths of an open-loop Poisson mix.

    Every seed gets the same set of inter-arrival gaps (exponential
    quantiles at the mean ``1/rate``) and the same set of lengths (one
    fixed geometric draw); the seed only shuffles their order.  So the work
    in a window is the same on every seed, and the seed changes which
    request comes when."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    lengths = geometric_lengths(0, n, max_len, min_steps=min_len,
                                mean_frac=mean_frac)
    rng = np.random.default_rng((104729, int(seed)))
    due = np.cumsum(rng.permutation(gaps))
    due *= seconds / due[-1]                  # the last request at the close
    return due, lengths[rng.permutation(n)]


def request_paths(seed: int, lengths: np.ndarray, d: int) -> list:
    """One (L+1, d) random-walk path per request, from 0, of unit total
    variance."""
    rng = np.random.default_rng((15485863, int(seed)))
    M = int(lengths.max())
    steps = rng.standard_normal((len(lengths), M, d)).astype(np.float32)
    out = []
    for i, L in enumerate(lengths):
        inc = steps[i, :L] / np.float32(np.sqrt(L))
        out.append(np.concatenate([np.zeros((1, d), np.float32),
                                   np.cumsum(inc, 0)]))
    return out
