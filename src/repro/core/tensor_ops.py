"""Levelwise truncated tensor-algebra operations (paper §2.1-2.2).

Representation: a truncated element of T_{<=N}(R^d) with scalar part 1 is a
``levels`` list ``[a_1, ..., a_N]`` with ``a_n`` of shape ``(..., d**n)``
(level 0 is implicit and equal to 1 unless stated otherwise).  The flat
representation concatenates levels along the last axis into ``(..., D_sig)``,
matching the paper's word-basis layout (level-major, lexicographic within a
level, per Prop. A.2 the base-d encoding IS the lexicographic order).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .words import sig_dim


def levels_to_flat(levels: list[jax.Array]) -> jax.Array:
    return jnp.concatenate(levels, axis=-1)


def flat_to_levels(flat: jax.Array, d: int, depth: int) -> list[jax.Array]:
    out, off = [], 0
    for n in range(1, depth + 1):
        out.append(flat[..., off:off + d**n])
        off += d**n
    assert off == flat.shape[-1], (off, flat.shape)
    return out


def zero_levels(batch_shape: tuple[int, ...], d: int, depth: int,
                dtype=jnp.float32) -> list[jax.Array]:
    return [jnp.zeros((*batch_shape, d**n), dtype) for n in range(1, depth + 1)]


def _outer(a: jax.Array, b: jax.Array) -> jax.Array:
    """Concatenation product of word-basis coefficient blocks.

    a: (..., d^k), b: (..., d^m)  ->  (..., d^(k+m)) with
    out[..., u∘v] = a[..., u] * b[..., v]   (Prop. A.3: index = u*d^m + v).
    """
    return (a[..., :, None] * b[..., None, :]).reshape(*a.shape[:-1],
                                                       a.shape[-1] * b.shape[-1])


def chen_mul(a: list[jax.Array], b: list[jax.Array], *, a0: float = 1.0,
             b0: float = 1.0, min_level_a: int = 0,
             min_level_b: int = 0) -> list[jax.Array]:
    """Truncated tensor product (A ⊗ B)_n = sum_k A_k ⊗ B_{n-k}.

    ``a0``/``b0`` are the scalar (level-0) parts; ``min_level_*`` lets callers
    declare that levels below it are zero (skips work, e.g. powers of A).
    """
    depth = len(a)
    assert len(b) == depth
    out: list[jax.Array] = []
    for n in range(1, depth + 1):
        acc = None
        for k in range(0, n + 1):
            if k < min_level_a and k > 0:
                continue
            if (n - k) < min_level_b and (n - k) > 0:
                continue
            if k == 0:
                term = a0 * b[n - 1] if a0 != 0.0 else None
            elif k == n:
                term = b0 * a[n - 1] if b0 != 0.0 else None
            else:
                term = _outer(a[k - 1], b[n - k - 1])
            if term is not None:
                acc = term if acc is None else acc + term
        if acc is None:
            # a (batched) zero of the right shape
            ref = a[n - 1] if a[n - 1] is not None else b[n - 1]
            acc = jnp.zeros_like(ref)
        out.append(acc)
    return out


def tensor_exp(dx: jax.Array, depth: int) -> list[jax.Array]:
    """exp(dx) levels: (dx^{⊗n} / n!) for n = 1..depth (Prop. 3.1)."""
    out = [dx]
    for n in range(2, depth + 1):
        out.append(_outer(out[-1], dx) / n)
    return out


def tensor_log(s: list[jax.Array]) -> list[jax.Array]:
    """log(1 + A) = sum_{k>=1} (-1)^{k+1} A^{⊗k} / k, truncated (paper §3.3)."""
    depth = len(s)
    power = list(s)                   # A^1, min level 1
    out = [lvl for lvl in s]          # k = 1 term
    for k in range(2, depth + 1):
        power = chen_mul(power, s, a0=0.0, b0=0.0, min_level_a=k - 1,
                         min_level_b=1)
        coef = ((-1) ** (k + 1)) / k
        out = [o + coef * p for o, p in zip(out, power)]
    return out


def tensor_inverse(s: list[jax.Array]) -> list[jax.Array]:
    """(1 + A)^{-1} = sum_{k>=0} (-A)^{⊗k}, truncated.

    For group-like elements this equals the signature of the time-reversed
    path (paper Lemma 4.5).
    """
    depth = len(s)
    neg = [-lvl for lvl in s]
    power = list(neg)
    out = list(neg)
    for k in range(2, depth + 1):
        power = chen_mul(power, neg, a0=0.0, b0=0.0, min_level_a=k - 1,
                         min_level_b=1)
        out = [o + p for o, p in zip(out, power)]
    return out


# ---------------------------------------------------------------------------
# naive reference signature engines (oracles)
# ---------------------------------------------------------------------------

def path_increments(path: jax.Array) -> jax.Array:
    """(B, M+1, d) sampled path -> (B, M, d) increments ΔX_j."""
    return path[..., 1:, :] - path[..., :-1, :]


@partial(jax.jit, static_argnames=("depth",))
def signature_exp_chen(increments: jax.Array, depth: int) -> jax.Array:
    """Naive oracle: materialise exp(ΔX_j) and Chen-multiply along the path.

    This is the textbook recursion (paper eq. (2)) that pathsig's Horner
    scheme avoids; it is the correctness oracle for every other engine.
    Returns the flat (B, D_sig) truncated signature.
    """
    def step(levels, dx):
        e = tensor_exp(dx, depth)
        return chen_mul(levels, e), None

    init = zero_levels(increments.shape[:-2], increments.shape[-1], depth,
                       increments.dtype)
    final, _ = jax.lax.scan(step, init, jnp.moveaxis(increments, -2, 0))
    return levels_to_flat(final)


def horner_step(levels: list[jax.Array], dx: jax.Array) -> list[jax.Array]:
    """One Chen update S <- S ⊗ exp(dx) in Horner form (paper Alg. 1).

    Never materialises exp(dx).  For each target level n:

        acc_1 = dx / n                       (innermost: S[eps]·ΔX^(i_1)/n)
        acc_j = (S^{(j-1)} + acc_{j-1}) ⊗ dx / (n-j+1),   j = 2..n
        S_new^{(n)} = S^{(n)} + acc_n

    which is the levelwise vectorisation of the paper's per-word Horner rule:
    coefficient w = (i_1..i_n) of acc_n equals
    ΔX^(i_n)(S[w_{1:n-1}] + ΔX^(i_{n-1})/2 (… + ΔX^(i_1)/n)).
    """
    depth = len(levels)
    new = []
    for n in range(1, depth + 1):
        acc = dx / n
        for j in range(2, n + 1):
            acc = _outer(levels[j - 2] + acc, dx) / (n - j + 1)
        new.append(levels[n - 1] + acc)
    return new


def inverse_horner_step(levels: list[jax.Array], dx: jax.Array) -> list[jax.Array]:
    """S ⊗ exp(-dx): exact inverse of horner_step (paper Prop. 4.6)."""
    return horner_step(levels, -dx)
