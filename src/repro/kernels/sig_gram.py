"""Pallas TPU kernel: weighted signature Gram tiles.

The truncated signature kernel is a *weighted inner product over word
coordinates*:  k_ω(x, y) = Σ_w ω_w ⟨S(x), w⟩⟨S(y), w⟩ = (S_x diag(ω) S_yᵀ).
This kernel computes the (B_x, B_y) Gram matrix **blocked over the word
axis**: grid cell (i, j, k) loads the (bx_tile, k_tile) / (by_tile, k_tile)
signature slabs of word block k, fuses the weighting ω into the left operand
on the VPU, and accumulates the partial product into the (bx_tile, by_tile)
output block on the MXU.  The (B_x, B_y, D_sig) elementwise intermediate of
the textbook formula is never materialised — live state per cell is the
output tile plus two signature slabs, O(B_x·B_y + B·D_tile).

The word axis is the innermost grid dimension, so the output block is
revisited across k and the accumulation is the standard Pallas reduction
pattern (init at k == 0, += after).  Zero-padding the *weights* (not just
the signatures) makes padded word columns exact no-ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(sx_ref, sy_ref, w_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    sxw = sx_ref[...] * w_ref[...]          # (bx, kt) * (1, kt): fused ω
    out_ref[...] += jax.lax.dot_general(    # contract the word block on MXU
        sxw, sy_ref[...], dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # f32, not one bf16 pass
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bx_tile", "by_tile", "k_tile",
                                             "interpret"))
def sig_gram_tiles(Sx: jax.Array, Sy: jax.Array, weights: jax.Array, *,
                   interpret: bool, bx_tile: int = 128, by_tile: int = 128,
                   k_tile: int = 512) -> jax.Array:
    """Weighted Gram of signature coordinate matrices.

    Sx: (B_x, D), Sy: (B_y, D), weights: (D,)  ->  (B_x, B_y) float32 with
    G[i, j] = Σ_k Sx[i, k] · weights[k] · Sy[j, k].  ``bx_tile`` and
    ``by_tile`` follow the lane-tile rule of
    :func:`repro.kernels.sig_trunc.lane_tile`; ``interpret=True`` runs in
    Pallas interpret mode, ``False`` compiles for the TPU.
    """
    from repro import obs
    from repro.kernels.sig_trunc import lane_tile
    obs.count_trace("sig_gram_tiles", Sx, Sy, bx_tile=bx_tile,
                    by_tile=by_tile, k_tile=k_tile)
    Bx, D = Sx.shape
    By, D2 = Sy.shape
    if D2 != D or weights.shape != (D,):
        raise ValueError(f"shape mismatch: Sx {Sx.shape}, Sy {Sy.shape}, "
                         f"weights {weights.shape}")
    bx, Bx_p = lane_tile(Bx, bx_tile)
    by, By_p = lane_tile(By, by_tile)
    kt = min(_round_up(k_tile, 128), _round_up(D, 128))  # lane axis
    D_p = _round_up(D, kt)
    x = jnp.pad(Sx, ((0, Bx_p - Bx), (0, D_p - D))).astype(jnp.float32)
    y = jnp.pad(Sy, ((0, By_p - By), (0, D_p - D))).astype(jnp.float32)
    w = jnp.pad(weights.astype(jnp.float32), (0, D_p - D))[None, :]

    out = pl.pallas_call(
        _kernel,
        grid=(Bx_p // bx, By_p // by, D_p // kt),   # word blocks innermost
        in_specs=[
            pl.BlockSpec((bx, kt), lambda i, j, k: (i, k)),
            pl.BlockSpec((by, kt), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, kt), lambda i, j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((bx, by), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bx_p, By_p), jnp.float32),
        interpret=interpret,
    )(x, y, w)
    return out[:Bx, :By]
