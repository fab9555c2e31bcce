"""Pallas TPU kernel: truncated signature via prefix-cone decomposition.

TPU adaptation of the paper's §3.1-3.2 CUDA design (see DESIGN.md §2).  The
word basis W_{<=N} is partitioned into *prefix cones*: grid cell ``c`` owns
the level-``s`` prefix word ``u = digits_d(c)`` together with every
descendant ``u∘v`` up to depth N, plus (redundantly) u's ancestor path —
a prefix-closed set, the tile-granularity analogue of the paper's
thread-per-``P_w`` assignment.  Each cell scans the whole time axis with its
coefficients resident in VMEM.

Layout: batch on the 128-wide lane axis, words on sublanes, so the per-word
Horner rule (paper Alg. 1) vectorises across the batch and the level-raising
outer product is a sublane reshape-broadcast — no gathers anywhere.

Per-cell state block (rows × B_TILE), rows =
  [ path: levels 1..s-1 along u ] ++ [ cone levels s..N: d^0, d^1, ..., d^{N-s} rows ]

Streaming (``stream=True``): the running state lives in a VMEM scratch block
and every ``stream_stride``-th step (plus the terminal step) is copied into an
(M_out, rows, B_TILE) output block *inside* the time loop — the kernel emits
all prefix signatures S_{0,t_j} in one pass.  ``stream_stride`` bounds the
output block so VMEM/HBM stays proportional to M_out = ceil(M / stride), not
M; the emitted step indices are ``repro.core.signature.stream_emit_steps``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.words import sig_dim


def cone_base_level(s: int) -> int:
    """Lowest global level stored in the cone (eps is never stored)."""
    return max(s, 1)


def cone_offsets(d: int, depth: int, s: int) -> np.ndarray:
    """Row offsets of cone global levels n = base..depth inside the block."""
    base = cone_base_level(s)
    sizes = [d ** (n - s) for n in range(base, depth + 1)]
    return np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)


def cone_rows(d: int, depth: int, s: int) -> int:
    return int(cone_offsets(d, depth, s)[-1])


def state_footprint(d: int, depth: int, s: int, batch_tile: int,
                    itemsize: int = 4) -> int:
    """Per-cell VMEM bytes at split ``s``: the resident state block plus the
    chain temporaries (which roughly double the top cone level).  ``itemsize``
    is the element byte width of the state dtype — 4 for fp32, 2 for bf16 —
    so VMEM budgeting stays correct under mixed precision."""
    rows = max(0, s - 1) + cone_rows(d, depth, s)
    lanes = -(-batch_tile // 128) * 128  # VMEM pads the lane axis to 128
    return (rows + d ** (depth - s)) * lanes * itemsize


def choose_split(d: int, depth: int, batch_tile: int,
                 vmem_budget: int = 6 * 2**20, itemsize: int = 4) -> int:
    """Smallest split level s whose per-cell state fits the VMEM budget."""
    for s in range(0, depth):
        if state_footprint(d, depth, s, batch_tile, itemsize) <= vmem_budget:
            return s
    return depth - 1


# Scoped-VMEM limit the kernels compile with: v4, v5e, v5p and v6e hold
# 128 MiB of VMEM per TensorCore, and Mosaic keeps the rest for its own
# scratch.  Mosaic's default limit (16 MiB on v5e) refuses the pipelined
# blocks of long streamed paths that do fit.
VMEM_LIMIT_BYTES = 100 * 2**20


def compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def block_bytes(shape, itemsize: int = 4) -> int:
    """VMEM bytes of one block: its two minor dims are padded to the
    (8, 128) tile, 16 sublanes for 2-byte dtypes."""
    *lead, r, c = shape
    sub = 8 * 4 // itemsize
    return (math.prod(lead) * (-(-r // sub) * sub) * (-(-c // 128) * 128)
            * itemsize)


def check_vmem(kernel: str, need: int, what: str) -> None:
    """Raise when a kernel's VMEM blocks cannot fit the scoped limit."""
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"{kernel}: {what} needs {need / 2**20:.1f} MiB of VMEM, over "
            f"the {VMEM_LIMIT_BYTES / 2**20:.0f} MiB limit.  The kernel holds "
            "the whole time axis in VMEM; cut the path into windows, or for "
            "stream=True raise stream_stride")


def lane_tile(B: int, batch_tile: int) -> tuple[int, int]:
    """-> (tile, B_pad) for a batch on the 128-wide lane axis.  Mosaic takes
    a lane block only as a multiple of 128 or as the whole padded axis, so
    the requested tile rounds up to a multiple of 128, and a tile at least
    as wide as the batch shrinks to the batch padded to 8."""
    full = max(8, -(-B // 8) * 8)
    tile = -(-batch_tile // 128) * 128
    if tile >= full:
        return full, full
    return tile, -(-B // tile) * tile


def vmem_footprint(M: int, d_raw: int, d: int, depth: int, s: int,
                   batch_tile: int, *, M_out: int = 0, in_itemsize: int = 4,
                   out_itemsize: int = 4, buffers: int = 2) -> int:
    """VMEM bytes of one grid step at split ``s``: the (M, d_raw) increment
    block and the output block (the (M_out, rows) emission block when
    streamed), each ``buffers``-deep, plus the streamed state scratch and
    the top cone level's chain temporary."""
    rows = max(0, s - 1) + cone_rows(d, depth, s)
    blocks = block_bytes((M, d_raw, batch_tile), in_itemsize)
    if M_out:
        blocks += block_bytes((M_out, rows, batch_tile), out_itemsize)
        scratch = block_bytes((rows, batch_tile))
    else:
        blocks += block_bytes((rows, batch_tile))
        scratch = 0
    return (buffers * blocks + scratch
            + block_bytes((d ** (depth - s), batch_tile)))


def _kernel(incs_ref, *refs, d: int, depth: int, s: int, M: int,
            stream_stride: int = 0, fuse_ll: bool = False,
            fuse_time: bool = False):
    """Cone update loop.  Non-streamed: ``out_ref`` IS the running state.
    Streamed (``stream_stride >= 1``): the state lives in the trailing VMEM
    scratch ref and strided snapshots are stored into ``out_ref``.

    Fused transforms (``fuse_ll`` / ``fuse_time``): the input block holds RAW
    increments (M, d_raw, B) and each augmented increment — channel layout
    [t?, lag, lead] matching ``core.transforms`` — is built in VMEM right
    here, ``sub = 2 if fuse_ll else 1`` Horner sub-steps per raw step.  The
    (M_aug, d_aug, B) block never exists; ``d`` is the AUGMENTED channel
    count and streamed emission is strided over the augmented step axis.
    ``fuse_time`` reads a (2, B) aux ref ``[dt; n_valid_aug]`` (zero time
    increments past each example's true augmented end)."""
    refs = list(refs)
    taux_ref = refs.pop(0) if fuse_time else None
    out_ref = refs.pop(0)
    scratch = refs
    stream = bool(scratch)
    state_ref = scratch[0] if stream else out_ref
    n_path = max(0, s - 1)
    base = cone_base_level(s)
    co = cone_offsets(d, depth, s)
    sub = 2 if fuse_ll else 1
    M_aug = M * sub

    def cone_slice(n):  # rows of global level n (n >= base)
        k = n - base
        return slice(n_path + int(co[k]), n_path + int(co[k + 1]))

    c = pl.program_id(1)
    # letters of the cell's prefix word u (traced scalars, most significant first)
    letters = [(c // d ** (s - 1 - k)) % d for k in range(s)]

    state_ref[...] = jnp.zeros(state_ref.shape, state_ref.dtype)
    # one-hot row masks of the prefix letters: Mosaic has no dynamic_slice
    # on values, so ΔX^{(u_k)} is a masked sublane sum over the d channels
    channel = jax.lax.broadcasted_iota(jnp.int32, (d, state_ref.shape[1]), 0)
    letter_mask = [(channel == letters[k]).astype(state_ref.dtype)
                   for k in range(s)]

    def update(dx):
        """One augmented-increment Horner update.  dx: (d, B) in state dtype."""
        B = dx.shape[-1]
        # per-path-step increment components ΔX^{(u_k)}  -> (1, B)
        dxl = [jnp.sum(dx * letter_mask[k], axis=0, keepdims=True)
               for k in range(s)]

        def path_val(lev):  # old value of ancestor u_{1:lev}, lev in 1..s-1
            return state_ref[lev - 1:lev, :]

        def chain(n):
            """Horner accumulator for target level n (paper Alg. 1):
            acc_j = (S[w_{1:j-1}] + acc_{j-1}) · ΔX^{(i_j)} / (n-j+1)."""
            acc = None
            for jj in range(1, n + 1):
                inv = 1.0 / (n - jj + 1)
                if jj == 1:          # innermost: S[eps] = 1
                    acc = (dxl[0] if s >= 1 else dx) * inv
                elif jj <= s:        # on-path step, width 1
                    acc = (path_val(jj - 1) + acc) * dxl[jj - 1] * inv
                else:                # cone expansion: width d^{jj-1-s} -> d^{jj-s}
                    prev = state_ref[cone_slice(jj - 1), :]
                    t = prev + acc
                    w = t.shape[0]
                    acc = (t[:, None, :] * dx[None, :, :]).reshape(w * d, B) * inv
            return acc

        # top-down over global target levels: reads touch strictly lower levels
        for n in range(depth, base - 1, -1):
            acc = chain(n)
            sl = cone_slice(n)
            state_ref[sl, :] = state_ref[sl, :] + acc
        # ancestor path levels n = s-1 .. 1 (width-1 chains)
        for n in range(min(s - 1, depth), 0, -1):
            acc = dxl[0] * (1.0 / n)
            for jj in range(2, n + 1):
                acc = (path_val(jj - 1) + acc) * dxl[jj - 1] * (1.0 / (n - jj + 1))
            state_ref[n - 1:n, :] = state_ref[n - 1:n, :] + acc

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
                e = jnp.concatenate(parts, axis=0)  # (d_aug, B) in VMEM
            else:
                e = g
            update(e)
            if stream:
                # strided per-step emission over the augmented axis: slot q
                # holds S_{0,t_{ja+1}}; the terminal step is always emitted
                q = ja // stream_stride

                @pl.when((((ja + 1) % stream_stride) == 0) | (ja == M_aug - 1))
                def _emit():
                    # the emission buffer may be bf16 (precision="bf16_fp32"):
                    # round on store, the fp32 accumulator state is untouched
                    out_ref[pl.ds(q, 1), :, :] = \
                        state_ref[...].astype(out_ref.dtype)[None]
        return 0

    jax.lax.fori_loop(0, M, body, 0)


def _reassemble(out, d, depth, s, B):
    """(n_cells, n_path+cone, B_pad) -> flat (B, D_sig)."""
    n_cells = d**s
    n_path = max(0, s - 1)
    base = cone_base_level(s)
    co = cone_offsets(d, depth, s)
    levels = []
    for lev in range(1, s):  # ancestor levels, gathered from owning cells
        idx = np.arange(d**lev) * d ** (s - lev)
        levels.append(out[idx, lev - 1, :])  # (d^lev, B_pad)
    for n in range(base, depth + 1):  # cone global levels
        k = n - base
        blk = out[:, n_path + int(co[k]):n_path + int(co[k + 1]), :]
        levels.append(blk.reshape(n_cells * d ** (n - s), -1))
    flat = jnp.concatenate(levels, axis=0)  # (D_sig, B_pad)
    return flat[:, :B].T


def _reassemble_stream(out, d, depth, s, B):
    """(M_out, n_cells, n_path+cone, B_pad) -> (B, M_out, D_sig)."""
    T = out.shape[0]
    n_cells = d**s
    n_path = max(0, s - 1)
    base = cone_base_level(s)
    co = cone_offsets(d, depth, s)
    levels = []
    for lev in range(1, s):  # ancestor levels, gathered from owning cells
        idx = np.arange(d**lev) * d ** (s - lev)
        levels.append(out[:, idx, lev - 1, :])  # (T, d^lev, B_pad)
    for n in range(base, depth + 1):  # cone global levels
        k = n - base
        blk = out[:, :, n_path + int(co[k]):n_path + int(co[k + 1]), :]
        levels.append(blk.reshape(T, n_cells * d ** (n - s), -1))
    flat = jnp.concatenate(levels, axis=1)  # (T, D_sig, B_pad)
    return jnp.moveaxis(flat[:, :, :B], -1, 0)  # (B, T, D_sig)


def _fuse_flags(transform):
    """Validate a kernel-level transform spec -> (fuse_ll, fuse_time)."""
    if transform is None:
        return False, False
    if transform.basepoint:
        raise ValueError("kernel-level transform must not include basepoint "
                         "(dispatch prepends the x0 increment first)")
    return transform.lead_lag, transform.time


def _storage_dtype(precision: str):
    """Increments-block storage dtype: bf16 halves the VMEM/HBM traffic of
    the input block while accumulation stays fp32 in the state block."""
    if precision == "bf16_fp32":
        return jnp.bfloat16
    if precision == "fp32":
        return jnp.float32
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("depth", "batch_tile", "split",
                                             "interpret", "vmem_budget",
                                             "stream", "stream_stride",
                                             "transform", "precision"))
def sig_trunc(increments: jax.Array, depth: int, *, interpret: bool,
              batch_tile: int = 128, split: int | None = None,
              vmem_budget: int = 6 * 2**20, stream: bool = False,
              stream_stride: int = 1, transform=None, taux=None,
              precision: str = "fp32") -> jax.Array:
    """Truncated signature via the Pallas cone kernel.  (B, M, d) -> (B, D_sig).

    ``stream=True`` emits every ``stream_stride``-th prefix signature (the
    terminal step always included): (B, M, d) -> (B, M_out, D_sig) with
    M_out = ceil(M_aug / stream_stride).

    ``transform`` (a :class:`repro.core.transforms.Transform` WITHOUT
    basepoint — dispatch prepends the x0 increment) fuses lead_lag /
    time_augment into the time loop: ``increments`` stay raw (B, M, d_raw)
    and the augmented increment is built in VMEM per sub-step.  ``taux`` is
    the (B, 2) ``transform_time_aux`` array, required iff the transform has
    a time channel.  ``precision="bf16_fp32"`` stores the increments block
    in bf16 (halved VMEM/HBM traffic) with fp32 accumulators.

    ``split=None`` takes the smallest split whose state fits ``vmem_budget``
    and, when streamed, whose emission block fits the VMEM limit; a shape
    whose blocks fit at no split raises ``ValueError``.  ``interpret=True``
    runs the kernel in Pallas interpret mode (any backend), ``False``
    compiles it for the TPU.
    """
    obs.count_trace("sig_trunc", increments, depth=depth,
                    batch_tile=batch_tile, split=split, stream=stream,
                    precision=precision)
    B, M, d_raw = increments.shape
    if depth < 1:
        raise ValueError("depth must be >= 1")
    if stream_stride < 1:
        raise ValueError(f"stream_stride must be >= 1, got {stream_stride}")
    fuse_ll, fuse_time = _fuse_flags(transform)
    if fuse_time and taux is None:
        raise ValueError("transform with a time channel needs taux= "
                         "(see repro.core.transforms.transform_time_aux)")
    sub = 2 if fuse_ll else 1
    d = (2 * d_raw if fuse_ll else d_raw) + (1 if fuse_time else 0)
    M_aug = M * sub
    M_out = -(-M_aug // stream_stride) if stream else 0
    batch_tile, B_pad = lane_tile(B, batch_tile)
    in_itemsize = jnp.dtype(_storage_dtype(precision)).itemsize

    def need(s):  # Pallas double-buffers blocks unless the grid is 1 step
        steps = B_pad // batch_tile * d**s
        return vmem_footprint(M, d_raw, d, depth, s, batch_tile, M_out=M_out,
                              in_itemsize=in_itemsize,
                              out_itemsize=in_itemsize if stream else 4,
                              buffers=1 if steps == 1 else 2)

    if split is None:
        s = choose_split(d, depth, batch_tile, vmem_budget)
        while s < depth - 1 and need(s) > VMEM_LIMIT_BYTES:
            s += 1
    else:
        s = split
    if not 0 <= s < depth:
        raise ValueError(f"split {s} outside [0, {depth})")
    check_vmem("sig_trunc", need(s),
               f"(M={M}, d={d_raw}, depth={depth}, split={s}, "
               f"batch_tile={batch_tile}, M_out={M_out})")
    n_cells = d**s
    n_path = max(0, s - 1)
    rows = n_path + cone_rows(d, depth, s)

    x = jnp.moveaxis(increments, 0, -1)  # (M, d_raw, B)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, B_pad - B))).astype(
        _storage_dtype(precision))
    kern = functools.partial(_kernel, d=d, depth=depth, s=s, M=M,
                             fuse_ll=fuse_ll, fuse_time=fuse_time)
    inputs = [x]
    in_specs = [pl.BlockSpec((M, d_raw, batch_tile),
                             lambda bi, c: (0, 0, bi))]
    if fuse_time:
        ta = jnp.pad(jnp.asarray(taux, jnp.float32).T,
                     ((0, 0), (0, B_pad - B)))  # (2, B_pad)
        inputs.append(ta)
        in_specs.append(pl.BlockSpec((2, batch_tile), lambda bi, c: (0, bi)))

    # the cell axis is a squeezed block dim, so each block's trailing
    # (rows, batch_tile) dims span the whole row extent: Mosaic accepts any
    # row count there, where a (n_cells * rows)-row array would need rows % 8
    if not stream:
        out = pl.pallas_call(
            kern,
            grid=(B_pad // batch_tile, n_cells),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, rows, batch_tile),
                                   lambda bi, c: (c, 0, bi)),
            out_shape=jax.ShapeDtypeStruct((n_cells, rows, B_pad),
                                           jnp.float32),
            compiler_params=compiler_params(),
            interpret=interpret,
        )(*inputs)
        return _reassemble(out, d, depth, s, B).astype(increments.dtype)

    out = pl.pallas_call(
        functools.partial(kern, stream_stride=stream_stride),
        grid=(B_pad // batch_tile, n_cells),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((M_out, None, rows, batch_tile),
                               lambda bi, c: (0, c, 0, bi)),
        # bf16_fp32: the (M_out, ·, ·) emission buffer is stored at the
        # precision's storage dtype (halving its VMEM/HBM footprint) while
        # the running state scratch stays a full fp32 accumulator
        out_shape=jax.ShapeDtypeStruct((M_out, n_cells, rows, B_pad),
                                       _storage_dtype(precision)),
        scratch_shapes=[pltpu.VMEM((rows, batch_tile), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*inputs)
    return _reassemble_stream(out, d, depth, s, B).astype(increments.dtype)
