"""Pallas TPU kernel: signature coefficients over arbitrary word sets.

Implements the paper's word projections (§3.1-3.2, §7) on TPU.  The requested
set I is prefix-closed and partitioned host-side into prefix-closed tiles
(:func:`repro.core.words.make_tiled_plan`), each of which is updated
independently — the tile-level analogue of the paper's thread-per-``P_w``
CUDA assignment, including the redundant shared-ancestor rows.

TPU twist (DESIGN.md §2): per-row prefix *gathers* (cheap per CUDA thread,
slow/unsupported along TPU sublanes) are recast as one-hot matmuls on the
MXU: ``pfx = P_j @ S`` with ``P_j`` the (rows × rows) prefix-selection
matrix of Horner step j.  FLOPs go up by the tile width; wall-clock goes
down because the MXU is ~50× the VPU and the gather disappears.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.words import TiledPlan, WordPlan, make_tiled_plan


_EXACT = jax.lax.Precision.HIGHEST


def _tile_tables(plan: WordPlan, W_pad: int, depth_pad: int):
    """One-hot tables for a tile, padded to (depth_pad, W_pad, ...) ."""
    W = plan.closure_size
    P = np.zeros((depth_pad, W_pad, 1 + W_pad), np.float32)
    L = np.zeros((depth_pad, W_pad, plan.d), np.float32)
    inv = np.zeros((depth_pad, W_pad), np.float32)
    emit = np.zeros((depth_pad, W_pad), np.float32)
    for j in range(plan.depth):
        for r in range(W):
            if j < plan.lengths[r]:
                P[j, r, plan.prefix_idx[r, j]] = 1.0
                L[j, r, plan.letters[r, j]] = 1.0
                inv[j, r] = plan.inv[r, j]
                emit[j, r] = plan.emit[r, j]
    return P, L, inv, emit


def tile_footprint(W_pad: int, depth: int, d: int, batch_tile: int,
                   itemsize: int = 4) -> int:
    """Per-tile VMEM bytes: the (1+W, B) closure state plus the one-hot
    tables.  ``itemsize`` is the element byte width of the state dtype (4
    for fp32, 2 for bf16) — the table bytes follow the same width so mixed-
    precision budgeting stays correct (mirrors sig_trunc.state_footprint)."""
    state = (1 + W_pad) * batch_tile * itemsize
    tables = depth * W_pad * (1 + W_pad + d + 2) * itemsize
    return state + tables


def _kernel(incs_ref, p_ref, l_ref, inv_ref, emit_ref, *refs,
            M: int, depth: int, stream_stride: int = 0,
            fuse_ll: bool = False, fuse_time: bool = False):
    """Tile update loop.  Non-streamed: ``out_ref`` IS the running closure
    buffer.  Streamed (``stream_stride >= 1``): the buffer lives in the
    trailing VMEM scratch ref and strided snapshots are stored into
    ``out_ref`` (one (1+W, B) slab per emitted step).

    Fused transforms (``fuse_ll`` / ``fuse_time``): the input block holds
    RAW increments (M, d_raw, B); each augmented increment ([t?, lag, lead]
    channels, matching ``core.transforms``) is built in VMEM per sub-step —
    the tables are over the AUGMENTED alphabet and emission is strided over
    the augmented step axis.  ``fuse_time`` reads a (2, B) aux ref
    ``[dt; n_valid_aug]``."""
    refs = list(refs)
    taux_ref = refs.pop(0) if fuse_time else None
    out_ref = refs.pop(0)
    scratch = refs
    stream = bool(scratch)
    state_ref = scratch[0] if stream else out_ref
    W1 = state_ref.shape[0]  # 1 + W_pad
    B = state_ref.shape[1]
    sub = 2 if fuse_ll else 1
    M_aug = M * sub
    # S[eps] = 1 on row 0: an iota compare, since Mosaic has no scatter
    row = jax.lax.broadcasted_iota(jnp.int32, (W1, B), 0)
    state_ref[...] = (row == 0).astype(state_ref.dtype)

    def body(j, _):
        g = incs_ref[pl.ds(j, 1), :, :][0].astype(state_ref.dtype)  # (d_raw, B)
        for p in range(sub):
            ja = sub * j + p  # augmented step index
            if fuse_ll or fuse_time:
                parts = ([jnp.zeros_like(g), g] if p == 0 else
                         [g, jnp.zeros_like(g)]) if fuse_ll else [g]
                if fuse_time:
                    trow = taux_ref[0:1, :] * (
                        ja < taux_ref[1:2, :]).astype(state_ref.dtype)
                    parts = [trow] + parts
                dx = jnp.concatenate(parts, axis=0)  # (d_aug, B) in VMEM
            else:
                dx = g
            S = state_ref[...]                      # (1+W, B), old values
            acc = jnp.zeros((W1 - 1, B), S.dtype)
            h = acc
            for jj in range(depth):                 # Horner steps (Alg. 1)
                # one-hot gathers on the MXU; HIGHEST keeps every f32 bit
                # (one bf16 pass would round S to 8 mantissa bits)
                pfx = jnp.dot(p_ref[0, jj], S, precision=_EXACT,
                              preferred_element_type=S.dtype)
                dxl = jnp.dot(l_ref[0, jj], dx, precision=_EXACT,
                              preferred_element_type=S.dtype)
                acc = (pfx + acc) * dxl * inv_ref[0, jj][:, None]
                h = h + acc * emit_ref[0, jj][:, None]
            state_ref[1:, :] = S[1:, :] + h
            if stream:
                q = ja // stream_stride

                @pl.when((((ja + 1) % stream_stride) == 0) | (ja == M_aug - 1))
                def _emit():
                    # bf16 emission buffer under bf16_fp32: round on store,
                    # the fp32 accumulator state is untouched
                    out_ref[pl.ds(q, 1), :, :] = \
                        state_ref[...].astype(out_ref.dtype)[None]
        return 0

    jax.lax.fori_loop(0, M, body, 0)


@functools.partial(jax.jit, static_argnames=("tplan", "batch_tile", "interpret",
                                             "stream", "stream_stride",
                                             "transform", "precision"))
def sig_words(increments: jax.Array, tplan: TiledPlan, *, interpret: bool,
              batch_tile: int = 128, stream: bool = False,
              stream_stride: int = 1, transform=None, taux=None,
              precision: str = "fp32") -> jax.Array:
    """Projected signature via the Pallas tile kernel.

    increments: (B, M, d)  ->  (B, |I|) coefficients in tplan.words order.
    ``stream=True`` emits every ``stream_stride``-th prefix state (terminal
    step always included): (B, M, d) -> (B, M_out, |I|).

    ``transform`` (a basepoint-free :class:`repro.core.transforms.Transform`)
    fuses lead_lag / time_augment into the time loop: ``increments`` stay raw
    (B, M, d_raw) while ``tplan`` is over the AUGMENTED alphabet
    (``tplan.d == transform_dim(transform, d_raw)``); ``taux`` is the (B, 2)
    ``transform_time_aux`` array, required iff the transform has a time
    channel.  ``precision="bf16_fp32"`` stores the increments block in bf16
    with fp32 accumulation.  ``interpret=True`` runs the kernel in Pallas
    interpret mode, ``False`` compiles it for the TPU; blocks that cannot
    fit VMEM raise ``ValueError``.
    """
    from repro import obs
    from repro.kernels.sig_trunc import (_fuse_flags, _storage_dtype,
                                         block_bytes, check_vmem,
                                         compiler_params, lane_tile)
    obs.count_trace("sig_words", increments, tiles=len(tplan.tiles),
                    batch_tile=batch_tile, stream=stream,
                    precision=precision)
    B, M, d_raw = increments.shape
    fuse_ll, fuse_time = _fuse_flags(transform)
    if fuse_time and taux is None:
        raise ValueError("transform with a time channel needs taux= "
                         "(see repro.core.transforms.transform_time_aux)")
    sub = 2 if fuse_ll else 1
    d = (2 * d_raw if fuse_ll else d_raw) + (1 if fuse_time else 0)
    M_aug = M * sub
    assert d == tplan.d, (d, tplan.d)
    if stream_stride < 1:
        raise ValueError(f"stream_stride must be >= 1, got {stream_stride}")
    tiles = tplan.tiles
    T = len(tiles)
    W_pad = max(8, -(-max(p.closure_size for p in tiles) // 8) * 8)
    depth = max(p.depth for p in tiles)

    Ps, Ls, invs, emits = [], [], [], []
    for p in tiles:
        P, L, inv, emit = _tile_tables(p, W_pad, depth)
        Ps.append(P); Ls.append(L); invs.append(inv); emits.append(emit)
    Pt = jnp.asarray(np.stack(Ps))      # (T, depth, W, 1+W)
    Lt = jnp.asarray(np.stack(Ls))      # (T, depth, W, d)
    invt = jnp.asarray(np.stack(invs))  # (T, depth, W)
    emitt = jnp.asarray(np.stack(emits))

    batch_tile, B_pad = lane_tile(B, batch_tile)
    M_out = -(-M_aug // stream_stride) if stream else 0
    in_itemsize = jnp.dtype(_storage_dtype(precision)).itemsize
    W1 = 1 + W_pad
    tables = (block_bytes((depth, W_pad, W1)) + block_bytes((depth, W_pad, d))
              + 2 * block_bytes((depth, W_pad)))
    out_block = (block_bytes((M_out, W1, batch_tile), in_itemsize)
                 + block_bytes((W1, batch_tile)) if stream
                 else block_bytes((W1, batch_tile)))
    buffers = 1 if B_pad // batch_tile * T == 1 else 2
    check_vmem("sig_words",
               buffers * (block_bytes((M, d_raw, batch_tile), in_itemsize)
                          + tables + out_block),
               f"(M={M}, d={d_raw}, depth={depth}, tile rows={W1}, "
               f"batch_tile={batch_tile}, M_out={M_out})",
               "The kernel holds the whole time axis in VMEM; cut the path "
               "into windows, or for stream=True raise stream_stride")
    x = jnp.moveaxis(increments, 0, -1)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, B_pad - B))).astype(
        _storage_dtype(precision))

    in_specs = [
        pl.BlockSpec((M, d_raw, batch_tile), lambda bi, t: (0, 0, bi)),
        pl.BlockSpec((1, depth, W_pad, 1 + W_pad), lambda bi, t: (t, 0, 0, 0)),
        pl.BlockSpec((1, depth, W_pad, d), lambda bi, t: (t, 0, 0, 0)),
        pl.BlockSpec((1, depth, W_pad), lambda bi, t: (t, 0, 0)),
        pl.BlockSpec((1, depth, W_pad), lambda bi, t: (t, 0, 0)),
    ]
    inputs = [x, Pt, Lt, invt, emitt]
    if fuse_time:
        ta = jnp.pad(jnp.asarray(taux, jnp.float32).T,
                     ((0, 0), (0, B_pad - B)))  # (2, B_pad)
        inputs.append(ta)
        in_specs.append(pl.BlockSpec((2, batch_tile), lambda bi, t: (0, bi)))
    kern = functools.partial(_kernel, M=M, depth=depth,
                             fuse_ll=fuse_ll, fuse_time=fuse_time)
    tile_idx = jnp.asarray([t for t, _ in tplan.gather], dtype=jnp.int32)
    row_idx = jnp.asarray(
        [tiles[t].out_rows[k] for t, k in tplan.gather], dtype=jnp.int32)

    # the tile axis is a squeezed block dim (see sig_trunc): each block's
    # trailing (1 + W_pad, batch_tile) dims span the whole row extent
    if not stream:
        out = pl.pallas_call(
            kern,
            grid=(B_pad // batch_tile, T),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, 1 + W_pad, batch_tile),
                                   lambda bi, t: (t, 0, bi)),
            out_shape=jax.ShapeDtypeStruct((T, 1 + W_pad, B_pad),
                                           jnp.float32),
            compiler_params=compiler_params(),
            interpret=interpret,
        )(*inputs)
        vals = out[tile_idx, row_idx, :B]   # (n_words, B)
        return vals.T.astype(increments.dtype)

    out = pl.pallas_call(
        functools.partial(kern, stream_stride=stream_stride),
        grid=(B_pad // batch_tile, T),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((M_out, None, 1 + W_pad, batch_tile),
                               lambda bi, t: (0, t, 0, bi)),
        # bf16_fp32: streamed emission buffer at the storage dtype, fp32
        # accumulator scratch (same discipline as sig_trunc's stream cell)
        out_shape=jax.ShapeDtypeStruct((M_out, T, 1 + W_pad, B_pad),
                                       _storage_dtype(precision)),
        scratch_shapes=[pltpu.VMEM((1 + W_pad, batch_tile), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*inputs)
    vals = out[:, tile_idx, row_idx, :B]    # (M_out, n_words, B)
    return jnp.moveaxis(vals, -1, 0).astype(increments.dtype)
