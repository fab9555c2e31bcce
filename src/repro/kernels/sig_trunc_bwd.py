"""Pallas TPU kernel: the truncated §4.2 backward as one reverse scan.

The backward of ``S_{0,T} = exp(ΔX_1) ⊗ ... ⊗ exp(ΔX_M)`` (paper §4.2) runs
the path backwards from the terminal state: at each step j = M..1 it
reconstructs ``S_{j-1} = S_j ⊗ exp(-ΔX_j)`` (Prop. 4.6), recomputes the
Horner chains of ``S_j = S_{j-1} ⊗ exp(ΔX_j)`` on it, and pulls the
cotangent ``G = ∂L/∂S_j`` back through them to ``∂L/∂S_{j-1}`` and
``∂L/∂ΔX_j``.  :func:`repro.core.signature.inverse_bwd_scan` does that as
an XLA scan whose carry crosses HBM every step; here a batch tile's S and
G stay in VMEM scratch for the whole time axis, which the grid's trailing
axis walks backwards in chunks of T steps
(:func:`repro.kernels.sig_trunc.time_chunk`).  Only the increments, the
terminal state, its cotangent and the increment cotangent cross HBM —
O(B·D_sig) whatever M is, and the VMEM it takes does not grow with M.

Layout: the cone kernel's (``sig_trunc``) at split 0: batch on the
128-wide lane axis, words on sublanes, within a level in REVERSED word
order (word ``i_1…i_n`` at row ``i_n d^{n-1} + … + i_1``), each level
starting at a row that is a multiple of 8 (``sig_trunc.level_offsets``,
``sig_trunc.reverse_words``).  The Horner outer product
``(t ⊗ ΔX)[u∘i] = t[u]·ΔX^i`` is then d blocks ``t·ΔX^i`` stacked along
sublanes, and both of its cotangents are block sums: no reshape across the
sublane tile anywhere.  The wrapper permutes the terminal state and its
cotangent into this layout once.

Pull-back of one chain (target level n, ``acc_1 = ΔX/n``,
``t_k = S^{(k)} + acc_k``, ``acc_{k+1} = (t_k ⊗ ΔX)/(n-k)``), from
``ā_n = G^{(n)}`` down::

    t̄_{k}   = Σ_i ā_{k+1}[i-block] · ΔX^i / (n-k)      -> G_prev^{(k)} += t̄_k
    ΔX̄^i   += Σ_u ā_{k+1}[i-block][u] · t_k[u] / (n-k)  (ā_k = t̄_k)
    ΔX̄     += ā_1 / n

Chains are pulled back from the lowest target level up, so each reads its
own level of G before a higher chain adds to it.
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core import tensor_ops as tops

from .sig_trunc import VMEM_LIMIT_BYTES, block_bytes, compiler_params, \
    count_time_chunks, lane_tile, level_offsets, reverse_words, time_chunk


def _sum(xs):
    return functools.reduce(operator.add, xs)


def vmem_need(T: int, d: int, depth: int, batch_tile: int,
              buffers: int = 2) -> int:
    """VMEM bytes of one grid step: the (T, d) increment and cotangent
    blocks of one time chunk and the terminal state and cotangent blocks,
    each ``buffers``-deep, the resident S and G, and the top chain's
    temporaries (its outer product and the cotangent it pulls back)."""
    rows = int(level_offsets(d, depth)[-1])
    blocks = 2 * block_bytes((T, d, batch_tile)) \
        + 2 * block_bytes((rows, batch_tile))
    return (buffers * blocks + 2 * block_bytes((rows, batch_tile))
            + 2 * block_bytes((d ** depth, batch_tile)))


def _grid(B: int, M: int, d: int, batch_tile: int):
    """-> (tile, B_pad, T, n_chunks, buffers): the lane tile, the time
    chunks, and the depth of Pallas' buffers, double unless the grid is one
    step."""
    tile, B_pad = lane_tile(B, batch_tile)
    T, n_t = time_chunk(M, 2 * 2 * block_bytes((1, d, tile)))
    return tile, B_pad, T, n_t, 1 if B_pad == tile and n_t == 1 else 2


def fits(B: int, M: int, d: int, depth: int, batch_tile: int) -> bool:
    """Whether the kernel holds this backward: a time axis to sweep, and
    S, G and their blocks within the scoped-VMEM limit."""
    tile, _, T, _, buffers = _grid(B, M, d, batch_tile)
    return M > 0 and depth >= 1 and \
        vmem_need(T, d, depth, tile, buffers) <= VMEM_LIMIT_BYTES


def _to_rows(flat: jax.Array, d: int, depth: int, B_pad: int) -> jax.Array:
    """(B, D_sig) in word order -> (rows, B_pad) in the kernel's layout."""
    B = flat.shape[0]
    offs = level_offsets(d, depth)
    parts = []
    for n, lv in enumerate(tops.flat_to_levels(flat, d, depth), 1):
        rev = reverse_words(lv, d, n, axis=1).T
        parts.append(jnp.pad(rev.astype(jnp.float32),
                             ((0, int(offs[n] - offs[n - 1]) - d ** n),
                              (0, B_pad - B))))
    return jnp.concatenate(parts, axis=0)


def _kernel(incs_ref, s_ref, g_ref, out_ref, S, G, *, d: int, depth: int,
            T: int):
    """Steps T-1..0 of one time chunk; chunks run last to first (grid axis
    1), and the first of them loads the terminal state and cotangent."""
    offs = level_offsets(d, depth)

    def rows(ref, n, lo=0, size=None):  # level n's rows [lo, lo + size)
        start = int(offs[n - 1]) + lo
        return ref[start:start + (d ** n if size is None else size), :]

    def add_rows(ref, n, lo, val):
        start = int(offs[n - 1]) + lo
        ref[start:start + val.shape[0], :] = \
            ref[start:start + val.shape[0], :] + val

    @pl.when(pl.program_id(1) == 0)
    def _init():
        S[...] = s_ref[...]
        G[...] = g_ref[...]

    B = S.shape[1]
    channel = jax.lax.broadcasted_iota(jnp.int32, (d, B), 0)

    def colsum(x):  # (w, B) -> (8, B) when w is whole tiles, else (1, B)
        w = x.shape[0]
        if w % 8 == 0:
            return x.reshape(w // 8, 8, B).sum(axis=0)
        return jnp.sum(x, axis=0, keepdims=True)

    def body(k, carry):
        j = T - 1 - k
        dx = incs_ref[pl.ds(j, 1), :, :][0].astype(jnp.float32)  # (d, B)
        letter = [incs_ref[pl.ds(j, 1), i:i + 1, :][0].astype(jnp.float32)
                  for i in range(d)]  # ΔX^i as (1, B) rows
        # the scaled letters ±ΔX^i / c of every chain link
        pos = {c: [x * (1.0 / c) for x in letter] for c in range(1, depth + 1)}
        neg = {c: [-x for x in pos[c]] for c in range(1, depth + 1)}

        def chain(n, sign):
            """t_1..t_{n-1} of level n's Horner chain for ±ΔX on S:
            t_k = S^{(k)} + acc_k, acc_{k+1} = d blocks t_k·(±ΔX^i)/(n-k)."""
            scaled = pos if sign > 0 else neg
            ts, acc = [], dx * (sign / n)
            for kk in range(1, n):
                ts.append(rows(S, kk) + acc)
                if kk < n - 1:
                    acc = jnp.concatenate(
                        [ts[-1] * x for x in scaled[n - kk]], axis=0)
            return ts

        # S_j -> S_{j-1} = S_j ⊗ exp(-ΔX_j), top level first: each chain
        # reads only lower levels, still S_j's
        for n in range(depth, 1, -1):
            t, w = chain(n, -1)[-1], d ** (n - 1)
            for i in range(d):
                add_rows(S, n, i * w, t * neg[1][i])
        add_rows(S, 1, 0, -dx)

        # pull G back through S_j = S_{j-1} ⊗ exp(ΔX_j), lowest chain first
        parts = [[] for _ in range(d)]   # partial sums of ΔX̄^i
        firsts = []                      # ā_1 / n of every chain
        for n in range(1, depth + 1):
            ts = chain(n, 1)
            abar = None                  # ā_n is G^{(n)}, read by block
            for kk in range(n - 1, 0, -1):   # the link t_kk -> acc_{kk+1}
                w, c = d ** kk, n - kk
                blocks = [rows(G, n, i * w, w) if abar is None
                          else abar[i * w:(i + 1) * w] for i in range(d)]
                abar = _sum(b * x for b, x in zip(blocks, pos[c]))
                for i in range(d):
                    parts[i].append(colsum(blocks[i] * ts[kk - 1])
                                    * (1.0 / c))
                add_rows(G, kk, 0, abar)
            firsts.append((rows(G, 1) if abar is None else abar) * (1.0 / n))
        gdx = _sum(firsts)
        for i, ps in enumerate(parts):
            if not ps:
                continue
            tiles = [p for p in ps if p.shape[0] == 8]
            ps = [p for p in ps if p.shape[0] == 1]
            if tiles:
                ps.append(jnp.sum(_sum(tiles), axis=0, keepdims=True))
            gdx = gdx + jnp.where(channel == i, _sum(ps), 0.0)
        out_ref[pl.ds(j, 1), :, :] = gdx[None].astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, T, body, 0)


@functools.partial(jax.jit, static_argnames=("depth", "batch_tile",
                                             "interpret"))
def sig_trunc_inverse_bwd(increments: jax.Array, out_flat: jax.Array,
                          g_flat: jax.Array, depth: int, *, interpret: bool,
                          batch_tile: int = 128) -> jax.Array:
    """§4.2 backward of the truncated signature as one Pallas kernel.

    ``increments`` (B, M, d) as the forward saw them, ``out_flat`` the
    terminal signature (B, D_sig), ``g_flat`` its cotangent; returns the
    increments' cotangent (B, M, d), as
    :func:`repro.core.signature.inverse_bwd_scan` does.  Raises
    ``ValueError`` where :func:`fits` is false.  ``interpret=True`` runs
    the kernel in Pallas interpret mode (any backend).
    """
    obs.count_trace("sig_trunc_inverse_bwd", increments, depth=depth,
                    batch_tile=batch_tile)
    B, M, d = increments.shape
    if not fits(B, M, d, depth, batch_tile):
        raise ValueError(
            f"sig_trunc_inverse_bwd: (M={M}, d={d}, depth={depth}, "
            f"batch_tile={batch_tile}) does not fit the "
            f"{VMEM_LIMIT_BYTES / 2**20:.0f} MiB VMEM limit; use "
            "repro.core.signature.inverse_bwd_scan")
    tile, B_pad, T, n_t, _ = _grid(B, M, d, batch_tile)
    count_time_chunks("sig_trunc_bwd", n_t)
    n_rows = int(level_offsets(d, depth)[-1])
    # zero steps pad the last chunk, and the sweep meets them first: a zero
    # increment leaves S and G as they are, and its cotangent is cut off
    x = jnp.moveaxis(increments, 0, -1).astype(jnp.float32)  # (M, d, B)
    x = jnp.pad(x, ((0, n_t * T - M), (0, 0), (0, B_pad - B)))
    state_spec = pl.BlockSpec((n_rows, tile), lambda b, t: (0, b))
    steps_spec = pl.BlockSpec((T, d, tile),
                              lambda b, t: (n_t - 1 - t, 0, b))
    out = pl.pallas_call(
        functools.partial(_kernel, d=d, depth=depth, T=T),
        grid=(B_pad // tile, n_t),
        in_specs=[steps_spec, state_spec, state_spec],
        out_specs=steps_spec,
        out_shape=jax.ShapeDtypeStruct((n_t * T, d, B_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_rows, tile), jnp.float32),
                        pltpu.VMEM((n_rows, tile), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(x, _to_rows(out_flat, d, depth, B_pad),
      _to_rows(g_flat, d, depth, B_pad))
    return jnp.moveaxis(out[:M, :, :B], -1, 0).astype(increments.dtype)
