"""Hybrid dense+word-table signature engine (beyond paper, §Perf kernel note).

Computes ALL coefficients of W_{<=N-1} with the dense levelwise-Horner
engine (pure reshape-broadcast — no gathers, and a gather/scatter-free VJP)
and only a prescribed set of level-N words via per-word Horner chains whose
prefixes are *read out of the dense buffer*.  This is exactly the shape of
the paper's §3.3 projected log-signature (all low levels + Lyndon_N), where
the generic word-table engine pays gather/scatter costs on every closure
row even though 40-60%% of the closure is simply "all words below N".

Memory law is unchanged: the custom VJP stores only the terminal state and
reconstructs backward via the group inverse (paper §4.2) — the top-level
coefficients invert as S_top_{j-1} = S_top_j − h(S_dense_{j-1}, ΔX_j).
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from . import tensor_ops as tops
from .signature import COTANGENT_SCOPE, RECONSTRUCT_SCOPE, scoped_vjp
from .words import Word, encode, level_offsets, sig_dim


@lru_cache(maxsize=None)
def _top_tables(d: int, depth: int, top_words: tuple[Word, ...]):
    """letters[K, depth] and dense-flat prefix indices[K, depth-1]."""
    K = len(top_words)
    offs = level_offsets(d, depth)
    letters = np.zeros((K, depth), np.int32)
    pidx = np.zeros((K, max(depth - 1, 1)), np.int32)
    for r, w in enumerate(top_words):
        assert len(w) == depth, (w, depth)
        for j, ch in enumerate(w):
            letters[r, j] = ch
        for j in range(1, depth):            # prefix w_{1:j}, flat index
            pidx[r, j - 1] = offs[j] + encode(w[:j], d)
    return letters, pidx


def _top_increment(flat_prev: jax.Array, dx: jax.Array, letters: np.ndarray,
                   pidx: np.ndarray, depth: int) -> jax.Array:
    """Horner chain h for each top word (paper Alg. 1), prefixes read from
    the dense flat buffer of the PREVIOUS step.  flat_prev: (B, D_{N-1});
    dx: (B, d) -> (B, K)."""
    # j = 1 (innermost): S[eps] = 1
    acc = jnp.take(dx, letters[:, 0], axis=1) / float(depth)
    for j in range(2, depth + 1):
        pfx = jnp.take(flat_prev, pidx[:, j - 2], axis=1)
        dxl = jnp.take(dx, letters[:, j - 1], axis=1)
        acc = (pfx + acc) * dxl / float(depth - j + 1)
    return acc


def _step(levels: list[jax.Array], top: jax.Array, dx: jax.Array,
          letters: np.ndarray, pidx: np.ndarray, depth: int):
    flat_prev = tops.levels_to_flat(levels)
    top = top + _top_increment(flat_prev, dx, letters, pidx, depth)
    levels = tops.horner_step(levels, dx)
    return levels, top


@lru_cache(maxsize=None)
def _make_hybrid(d: int, depth: int, top_words: tuple[Word, ...]):
    letters, pidx = _top_tables(d, depth, top_words)
    K = len(top_words)

    def scan(increments):
        B, M, _ = increments.shape
        init = (tops.zero_levels((B,), d, depth - 1, increments.dtype),
                jnp.zeros((B, K), increments.dtype))

        def body(carry, dx):
            levels, top = carry
            return _step(levels, top, dx, letters, pidx, depth), None

        (levels, top), _ = jax.lax.scan(body, init,
                                        jnp.moveaxis(increments, 1, 0))
        return jnp.concatenate([tops.levels_to_flat(levels), top], axis=1)

    def fwd(increments):
        out = scan(increments)
        return out, (increments, out)

    def bwd(res, g):
        increments, out = res
        B, M, _ = increments.shape
        lown = sig_dim(d, depth - 1)
        S_lv = tops.flat_to_levels(out[:, :lown], d, depth - 1)
        S_top = out[:, lown:]
        G_lv = tops.flat_to_levels(g[:, :lown], d, depth - 1)
        G_top = g[:, lown:]

        def step_fn(levels, top, dx):
            return _step(levels, top, dx, letters, pidx, depth)

        def body(carry, dx):
            (S, T), (Gl, Gt) = carry
            with jax.named_scope(RECONSTRUCT_SCOPE):
                S_prev = tops.horner_step(S, -dx)         # Prop. 4.6
                flat_prev = tops.levels_to_flat(S_prev)
                T_prev = T - _top_increment(flat_prev, dx, letters, pidx,
                                            depth)
            with jax.named_scope(COTANGENT_SCOPE):
                _, vjp_fn = jax.vjp(step_fn, S_prev, T_prev, dx)
                Gl_p, Gt_p, g_dx = vjp_fn((Gl, Gt))
            return ((S_prev, T_prev), (Gl_p, Gt_p)), g_dx

        (_, _), g_rev = jax.lax.scan(body, ((S_lv, S_top), (G_lv, G_top)),
                                     jnp.moveaxis(increments, 1, 0),
                                     reverse=True)
        return (jnp.moveaxis(g_rev, 0, 1),)

    return scoped_vjp(scan, fwd, bwd)


def hybrid_low_plus_top(increments: jax.Array, top_words, depth: int,
                        *, backward: str = "inverse") -> jax.Array:
    """(B, M, d) -> (B, D_{N-1} + K): the full W_{<=N-1} coefficient block
    (level-major flat order) concatenated with the level-N `top_words`.

    `backward="inverse"` uses the O(B·D) reconstruction VJP; "autodiff"
    differentiates through the scan (O(M·B·D) — baseline/testing).
    """
    if depth < 2:
        raise ValueError("hybrid engine needs depth >= 2 (no dense part "
                         "below depth 1)")
    d = increments.shape[-1]
    top_words = tuple(tuple(w) for w in top_words)
    if backward == "autodiff":
        letters, pidx = _top_tables(d, depth, top_words)
        B, M, _ = increments.shape
        init = (tops.zero_levels((B,), d, depth - 1, increments.dtype),
                jnp.zeros((B, len(top_words)), increments.dtype))

        def body(carry, dx):
            levels, top = carry
            return _step(levels, top, dx, letters, pidx, depth), None

        (levels, top), _ = jax.lax.scan(body, init,
                                        jnp.moveaxis(increments, 1, 0))
        return jnp.concatenate([tops.levels_to_flat(levels), top], axis=1)
    return _make_hybrid(d, depth, top_words)(increments)
