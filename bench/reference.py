"""The plain reference: textbook truncated signatures in float32
``jax.numpy``.

It imports nothing of the program.  The signature is the product of the
tensor exponentials of the increments, S = exp(ΔX_1) ⊗ … ⊗ exp(ΔX_M), by
Chen's identity, in the level-major, lexicographic word layout the program
uses (the word (i_1, …, i_n) sits at offset Σ_{k<n} d^k plus its base-d
code).  Gradients come from autodiff through that scan, so they share
nothing with the program's §4.2 backward.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def sig_dim(d: int, depth: int) -> int:
    return sum(d ** n for n in range(1, depth + 1))


def _outer(a, b):
    return (a[..., :, None] * b[..., None, :]).reshape(
        *a.shape[:-1], a.shape[-1] * b.shape[-1])


def _exp_levels(dx, depth):
    out = [dx]
    for n in range(2, depth + 1):
        out.append(_outer(out[-1], dx) / n)
    return out


def _chen(a, b):
    """(1 + a) ⊗ (1 + b), levels 1..depth."""
    depth = len(a)
    out = []
    for n in range(1, depth + 1):
        acc = a[n - 1] + b[n - 1]
        for k in range(1, n):
            acc = acc + _outer(a[k - 1], b[n - k - 1])
        out.append(acc)
    return out


def signature(increments, depth: int):
    """(B, M, d) increments -> (B, D_sig) truncated signature, by
    exp/Chen.  A zero increment leaves the signature exactly unchanged,
    so zero-padded tails give the signature of the unpadded path."""
    B, _, d = increments.shape
    init = [jnp.zeros((B, d ** n), increments.dtype)
            for n in range(1, depth + 1)]

    def step(levels, dx):
        return _chen(levels, _exp_levels(dx, depth)), None

    final, _ = jax.lax.scan(step, init, jnp.moveaxis(increments, 1, 0))
    return jnp.concatenate(final, axis=-1)


def word_index(word, d: int) -> int:
    off = sum(d ** k for k in range(1, len(word)))
    code = 0
    for letter in word:
        code = code * d + letter
    return off + code


def lead_lag(path):
    """(B, M+1, d) -> (B, 2M+1, 2d), channels [lag, lead]: the points
    (X_k, X_k) and (X_k, X_{k+1}) in turn, then (X_M, X_M)."""
    B, M1, d = path.shape
    a, b = path[:, :-1], path[:, 1:]
    even = jnp.concatenate([a, a], -1)
    odd = jnp.concatenate([a, b], -1)
    inter = jnp.stack([even, odd], 2).reshape(B, 2 * (M1 - 1), 2 * d)
    last = jnp.concatenate([path[:, -1:], path[:, -1:]], -1)
    return jnp.concatenate([inter, last], 1)


def sparse_leadlag_words(d: int, depth: int) -> list:
    """Paper §8's sparse lead-lag word set over channels [lag 0..d-1, lead
    d..2d-1]: concatenations of the generators (L_i), (ℓ_i, L_i), (L_i, ℓ_i)
    of length at most ``depth``, sorted by (length, word)."""
    gens = [(d + i,) for i in range(d)]
    gens += [w for i in range(d) for w in ((i, d + i), (d + i, i))]
    seen, frontier = set(), [()]
    while frontier:
        nxt = []
        for base, g in itertools.product(frontier, gens):
            w = base + g
            if len(w) <= depth and w not in seen:
                seen.add(w)
                nxt.append(w)
        frontier = nxt
    return sorted(seen, key=lambda w: (len(w), w))


def projected(increments, words, d: int):
    """The coordinates of ``words`` taken from the full signature."""
    depth = max(len(w) for w in words)
    full = signature(increments, depth)
    return full[:, np.asarray([word_index(w, d) for w in words])]
