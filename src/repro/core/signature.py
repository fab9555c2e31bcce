"""Truncated path signatures with O(1)-in-length backprop (paper §3-4).

Public API
----------
``signature(path, depth, ...)``              (B, M+1, d) -> (B, D_sig)
``signature_from_increments(incs, depth)``   (B, M, d)   -> (B, D_sig)
``signature(..., stream=True)``              -> (B, M_out, D_sig) prefix
signatures at every ``stream_stride``-th step (terminal step always emitted;
see :func:`stream_emit_steps`).  Streaming is a first-class axis: every
backend routes through the engine dispatch, and the ``inverse`` backward is
the §4.2 reverse sweep generalised to cotangents arriving at every emitted
step (:func:`stream_inverse_bwd_scan`) — one reverse scan, O(B·D_sig) live
memory.

Three backward modes:

- ``"inverse"`` (default, the paper's §4.2): store only the terminal
  signature; reconstruct S_{0,t_{j-1}} = S_{0,t_j} ⊗ exp(-ΔX_j) during the
  backward sweep.  Memory O(B·D_sig), independent of M.
- ``"checkpoint"`` (beyond paper): O(√M) chunk boundaries are stored and the
  backward recomputes within chunks — immune to inverse-reconstruction drift
  on very long/large-increment paths.
- ``"autodiff"``: plain scan autodiff, O(M·B·D_sig) memory (keras_sig-style
  scaling).
"""
from __future__ import annotations

import math
from functools import lru_cache, partial, wraps

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import DEVICE_SCOPES

from . import tensor_ops as tops
from .words import sig_dim

(FWD_SCOPE, BWD_SCOPE, RECONSTRUCT_SCOPE, COTANGENT_SCOPE,
 PROJ_INDEX_SCOPE) = DEVICE_SCOPES


def _in_scope(name: str, fn):
    @wraps(fn)
    def scoped(*args):
        with jax.named_scope(name):
            return fn(*args)
    return scoped


def scoped_vjp(primal, fwd, bwd):
    """``jax.custom_vjp(primal)`` with the rule ``(fwd, bwd)``: the primal
    and ``fwd`` run under the device scope ``pathsig.fwd``, ``bwd`` under
    ``pathsig.bwd``, so the compiled program names its own phases.  Every
    signature custom VJP is made here; ``fwd`` calls the plain forward, not
    the returned function, so the scope is entered once."""
    f = jax.custom_vjp(_in_scope(FWD_SCOPE, primal))
    f.defvjp(_in_scope(FWD_SCOPE, fwd), _in_scope(BWD_SCOPE, bwd))
    return f


def stream_emit_steps(M: int, stride: int = 1) -> np.ndarray:
    """0-based scan steps emitted by a streamed forward: stride-1, 2·stride-1,
    ..., with the terminal step M-1 always included.  len == ceil(M/stride);
    step j holds the prefix signature S_{0,t_{j+1}} (over j+1 increments)."""
    if stride < 1:
        raise ValueError(f"stream_stride must be >= 1, got {stride}")
    if M == 0:
        return np.zeros((0,), np.int64)
    steps = np.arange(stride - 1, M, stride, dtype=np.int64)
    if steps.size == 0 or steps[-1] != M - 1:
        steps = np.append(steps, M - 1)
    return steps


# ---------------------------------------------------------------------------
# ragged (variable-length) support: the length axis as masks over a padded
# batch.  A zero increment is the identity Chen update, so zero-masking the
# padded tail makes the terminal signature of a padded batch EXACTLY the
# per-example unpadded signature on every engine — and because the mask
# multiply is the outermost op, cotangents w.r.t. padded steps are exactly
# zero through any custom VJP underneath.
# ---------------------------------------------------------------------------

def as_lengths(lengths, B: int) -> jax.Array:
    """Normalise a ``lengths=`` argument to a (B,) int32 array (a scalar
    broadcasts across the batch)."""
    lengths = jnp.asarray(lengths)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (B,))
    if lengths.shape != (B,):
        raise ValueError(f"lengths must be scalar or shape ({B},), got "
                         f"{lengths.shape}")
    return lengths.astype(jnp.int32)


def length_mask(lengths: jax.Array, M: int) -> jax.Array:
    """(B,) per-example increment counts -> (B, M) bool, True where the scan
    step index lies inside the example's true path."""
    return jnp.arange(M, dtype=jnp.int32)[None, :] < lengths[:, None]


def mask_increments(increments: jax.Array, lengths) -> jax.Array:
    """Zero every increment at or past each example's true end.  Exact: a
    zero increment is the identity update, and ∂(x·mask)/∂x = mask zeroes
    the padded-tail cotangents."""
    if lengths is None:
        return increments
    B, M, _ = increments.shape
    m = length_mask(as_lengths(lengths, B), M)
    return increments * m[..., None].astype(increments.dtype)


def stream_emit_slots(M: int, stride: int, lengths: jax.Array) -> jax.Array:
    """(B,) emitted-step slot holding each example's TRUE terminal signature.

    Emitted slot j covers min((j+1)·stride, M) increments; with the padded
    tail zero-masked, the first slot covering >= length increments already
    equals the example's terminal state.  That slot is
    ceil(length / stride) - 1, clamped into [0, M_out).
    """
    M_out = -(-M // stride)
    slots = (lengths + (stride - 1)) // stride - 1
    return jnp.clip(slots, 0, max(M_out - 1, 0)).astype(jnp.int32)


def stream_emit_mask(M: int, stride: int, lengths: jax.Array) -> jax.Array:
    """(B, M_out) bool: True up to and including each example's true-terminal
    slot (:func:`stream_emit_slots`); emissions past the end are masked."""
    M_out = -(-M // stride)
    slots = stream_emit_slots(M, stride, lengths)
    return jnp.arange(M_out, dtype=jnp.int32)[None, :] <= slots[:, None]


def ragged_terminal(stream_out: jax.Array, lengths, stride: int = 1,
                    M: int | None = None) -> jax.Array:
    """Gather each example's true terminal state from a streamed output.

    ``stream_out`` is (B, M_out, D) as emitted by ``stream=True``;
    ``M`` is the padded increment count (default: inferred from M_out·stride,
    exact whenever stride == 1).  Returns (B, D).
    """
    B, M_out, _ = stream_out.shape
    if M is None:
        M = M_out * stride
    slots = stream_emit_slots(M, stride, as_lengths(lengths, B))
    return jnp.take_along_axis(stream_out, slots[:, None, None],
                               axis=1)[:, 0]


def _as_batched(x: jax.Array) -> tuple[jax.Array, bool]:
    if x.ndim == 2:
        return x[None], True
    if x.ndim == 3:
        return x, False
    raise ValueError(f"expected (M, d) or (B, M, d), got {x.shape}")


def _unpack_ragged(path):
    """Duck-typed :class:`repro.ragged.RaggedPaths` unpacking (kept import-
    free: ``repro.ragged`` imports this module)."""
    if hasattr(path, "values") and hasattr(path, "lengths"):
        return path.values, path.lengths
    return path, None


# ---------------------------------------------------------------------------
# forward scan
# ---------------------------------------------------------------------------

def _scan_forward(increments: jax.Array, depth: int,
                  stream: bool) -> jax.Array:
    """Plain levelwise-Horner Chen scan.  increments: (B, M, d)."""
    B, M, d = increments.shape

    def step(levels, dx):
        new = tops.horner_step(levels, dx)
        return new, (tops.levels_to_flat(new) if stream else None)

    init = tops.zero_levels((B,), d, depth, increments.dtype)
    final, ys = jax.lax.scan(step, init, jnp.moveaxis(increments, 1, 0))
    if stream:
        return jnp.moveaxis(ys, 0, 1)  # (B, M, D_sig)
    return tops.levels_to_flat(final)


# ---------------------------------------------------------------------------
# precision: "fp32" | "bf16_fp32" (bf16-quantised increments, fp32
# accumulation).  The quantisation IS the semantics: every engine computes
# fp32 Horner updates on bf16-rounded increments, so engines agree to float
# tolerance and the error vs the fp32 oracle is bounded per level (~ n·2^-8
# at level n; see tests/test_precision.py).
# ---------------------------------------------------------------------------

PRECISIONS = ("fp32", "bf16_fp32")


def canon_precision(precision: str) -> str:
    p = {"bf16": "bf16_fp32"}.get(precision, precision)
    if p not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}: expected one of "
                         f"{PRECISIONS}")
    return p


def quantise_increments(x: jax.Array, precision: str) -> jax.Array:
    """Round increments to the storage dtype of ``precision`` (returned in
    the original dtype so downstream fp32 accumulation is unchanged)."""
    if canon_precision(precision) == "bf16_fp32":
        return jax.lax.stop_gradient(
            x.astype(jnp.bfloat16)).astype(x.dtype) + (x - jax.lax.stop_gradient(x))
    return x


# ---------------------------------------------------------------------------
# fused-transform forward: the augmented increment is built in registers per
# Horner sub-step, so the (B, M_aug, d_aug) intermediate never exists and the
# scan runs M (not M_aug) iterations.  ``increments`` must already include
# the basepoint increment (dispatch prepends x0); ``taux`` is
# transforms.transform_time_aux output (pass zeros when spec.time is False).
# ---------------------------------------------------------------------------

def _fused_build_increment(dx: jax.Array, taux: jax.Array, spec, phase: int,
                           ja) -> jax.Array:
    """One augmented increment (B, d_aug) from a raw increment (B, d)."""
    parts = []
    if spec.time:
        dt, n_valid = taux[:, :1], taux[:, 1:]
        parts.append(dt * (ja < n_valid).astype(dx.dtype))
    if spec.lead_lag:
        z = jnp.zeros_like(dx)
        parts += [z, dx] if phase == 0 else [dx, z]   # [lag, lead] channels
    else:
        parts.append(dx)
    return jnp.concatenate(parts, axis=-1)


def _fused_scan_forward(increments: jax.Array, taux: jax.Array, spec,
                        depth: int, stream: bool) -> jax.Array:
    """Fused levelwise-Horner Chen scan: ``spec.sub_steps`` Horner sub-steps
    per scan iteration.  increments: (B, M, d) raw; output over the
    *augmented* axis when streamed: (B, M_aug, D_sig)."""
    from .transforms import transform_dim
    B, M, d = increments.shape
    sub = spec.sub_steps
    d_aug = transform_dim(dataclasses_replace_nobp(spec), d)

    def step(levels, xs):
        dx, j = xs
        ys = []
        for p in range(sub):
            e = _fused_build_increment(dx, taux, spec, p,
                                       (sub * j + p).astype(taux.dtype))
            levels = tops.horner_step(levels, e)
            if stream:
                ys.append(tops.levels_to_flat(levels))
        return levels, (jnp.stack(ys, 0) if stream else None)

    init = tops.zero_levels((B,), d_aug, depth, increments.dtype)
    idx = jnp.arange(M, dtype=jnp.int32)
    final, ys = jax.lax.scan(step, init, (jnp.moveaxis(increments, 1, 0), idx))
    if stream:  # ys: (M, sub, B, D) -> (B, M_aug, D)
        return jnp.moveaxis(ys.reshape(M * sub, B, -1), 0, 1)
    return tops.levels_to_flat(final)


def dataclasses_replace_nobp(spec):
    """The kernel-level view of a transform spec: basepoint is an increment
    prepend handled by dispatch, so the scan/kernels only see lead_lag/time."""
    import dataclasses
    if spec.basepoint:
        return dataclasses.replace(spec, basepoint=False)
    return spec


@lru_cache(maxsize=None)
def _make_fused_inverse_vjp(depth: int, spec):
    """Fused forward + §4.2 reverse sweep.  The backward transiently
    materialises the augmented increments (reusing :func:`inverse_bwd_scan`
    unchanged), then pulls the cotangent back through the transform's linear
    adjoint (:func:`repro.core.transforms.fused_adjoint`)."""
    def sig(increments, taux):
        return _fused_scan_forward(increments, taux, spec, depth, False)

    def fwd(increments, taux):
        out = sig(increments, taux)
        return out, (increments, taux, out)

    def bwd(res, g_flat):
        from .transforms import fused_augment, fused_adjoint
        increments, taux, out_flat = res
        e = fused_augment(increments, taux, spec)
        g_e = inverse_bwd_scan(e, out_flat, g_flat, depth)
        g_incs = fused_adjoint(g_e, spec, increments.shape[-1])
        return g_incs, jnp.zeros_like(taux)

    return scoped_vjp(sig, fwd, bwd)


@lru_cache(maxsize=None)
def _make_fused_stream_inverse_vjp(depth: int, spec, stride: int):
    """Streamed fused forward (emissions strided over the AUGMENTED step
    axis) + the generalised §4.2 reverse sweep."""
    def sig(increments, taux):
        out = _fused_scan_forward(increments, taux, spec, depth, True)
        return _subsample_stream(out, out.shape[1], stride)

    def fwd(increments, taux):
        out = sig(increments, taux)
        return out, (increments, taux, out[:, -1])

    def bwd(res, g_steps):
        from .transforms import fused_augment, fused_adjoint
        increments, taux, terminal = res
        e = fused_augment(increments, taux, spec)
        g_e = stream_inverse_bwd_scan(e, terminal, g_steps, depth, stride)
        g_incs = fused_adjoint(g_e, spec, increments.shape[-1])
        return g_incs, jnp.zeros_like(taux)

    return scoped_vjp(sig, fwd, bwd)


# ---------------------------------------------------------------------------
# custom VJP: inverse reconstruction (paper §4.2)
# ---------------------------------------------------------------------------

def inverse_bwd_scan(increments: jax.Array, out_flat: jax.Array,
                     g_flat: jax.Array, depth: int) -> jax.Array:
    """§4.2 backward sweep: reconstruct S_{0,t_{j-1}} = S_{0,t_j} ⊗ exp(-ΔX_j)
    and accumulate cotangents in one reverse scan.

    Engine-agnostic: any forward producing ``out_flat`` (pure-JAX scan or a
    Pallas kernel) can pair with this backward — memory stays O(B·D_sig).
    """
    B, M, d = increments.shape
    S_T = tops.flat_to_levels(out_flat, d, depth)
    G_T = tops.flat_to_levels(g_flat, d, depth)

    def step(carry, dx):
        S, G = carry  # S = S_{0,t_j}, G = ∂L/∂S_{0,t_j}
        with jax.named_scope(RECONSTRUCT_SCOPE):
            S_prev = tops.horner_step(S, -dx)      # Prop. 4.6
        with jax.named_scope(COTANGENT_SCOPE):
            _, vjp_fn = jax.vjp(tops.horner_step, S_prev, dx)
            G_prev, g_dx = vjp_fn(G)
        return (S_prev, G_prev), g_dx

    (_, _), g_rev = jax.lax.scan(
        step, (S_T, G_T), jnp.moveaxis(increments, 1, 0), reverse=True)
    return jnp.moveaxis(g_rev, 0, 1)


@lru_cache(maxsize=None)
def _make_inverse_vjp(depth: int):
    def sig(increments):
        return _scan_forward(increments, depth, stream=False)

    def fwd(increments):
        out = sig(increments)
        return out, (increments, out)

    def bwd(res, g_flat):
        increments, out_flat = res
        return (inverse_bwd_scan(increments, out_flat, g_flat, depth),)

    return scoped_vjp(sig, fwd, bwd)


# ---------------------------------------------------------------------------
# streamed custom VJP: §4.2 generalised to cotangents at every emitted step
# ---------------------------------------------------------------------------

def stream_inverse_bwd_scan(increments: jax.Array, terminal_flat: jax.Array,
                            g_steps: jax.Array, depth: int,
                            stride: int = 1) -> jax.Array:
    """§4.2 reverse sweep for a *streamed* forward: cotangents ``g_steps``
    (B, M_out, D_sig) arrive at every emitted step, and one reverse scan
    reconstructs S_{0,t_{j-1}} = S_{0,t_j} ⊗ exp(-ΔX_j) while folding in the
    step-j cotangent just before pulling it back — still O(B·D_sig) live
    memory.  The non-streamed :func:`inverse_bwd_scan` is the special case
    where only the terminal cotangent is non-zero.

    Engine-agnostic: any forward emitting :func:`stream_emit_steps` (the JAX
    scan or the streamed Pallas kernel) pairs with this backward; only the
    terminal signature ``terminal_flat`` (B, D_sig) is needed as residual.
    """
    B, M, d = increments.shape
    steps = stream_emit_steps(M, stride)
    if len(steps) == M:
        g_dense = g_steps
    else:  # scatter strided cotangents onto the full time axis
        g_dense = jnp.zeros((B, M, g_steps.shape[-1]), g_steps.dtype
                            ).at[:, jnp.asarray(steps)].set(g_steps)
    S_T = tops.flat_to_levels(terminal_flat, d, depth)
    G_T = [jnp.zeros_like(a) for a in S_T]

    def step(carry, xs):
        S, G = carry  # S = S_{0,t_j}, G = ∂L/∂S_{0,t_j} from steps > j
        dx, g_j = xs
        with jax.named_scope(RECONSTRUCT_SCOPE):
            S_prev = tops.horner_step(S, -dx)      # Prop. 4.6
        with jax.named_scope(COTANGENT_SCOPE):
            G = [a + b for a, b in zip(G, tops.flat_to_levels(g_j, d,
                                                               depth))]
            _, vjp_fn = jax.vjp(tops.horner_step, S_prev, dx)
            G_prev, g_dx = vjp_fn(G)
        return (S_prev, G_prev), g_dx

    (_, _), g_rev = jax.lax.scan(
        step, (S_T, G_T), (jnp.moveaxis(increments, 1, 0),
                           jnp.moveaxis(g_dense, 1, 0)), reverse=True)
    return jnp.moveaxis(g_rev, 0, 1)


def _subsample_stream(out: jax.Array, M: int, stride: int) -> jax.Array:
    """(B, M, D) full stream -> (B, M_out, D) at the emitted steps."""
    if stride == 1:
        return out
    return out[:, jnp.asarray(stream_emit_steps(M, stride))]


@lru_cache(maxsize=None)
def _make_stream_inverse_vjp(depth: int, stride: int):
    def sig(increments):
        out = _scan_forward(increments, depth, stream=True)
        return _subsample_stream(out, increments.shape[1], stride)

    def fwd(increments):
        out = sig(increments)
        return out, (increments, out[:, -1])  # terminal step always emitted

    def bwd(res, g_steps):
        increments, terminal = res
        return (stream_inverse_bwd_scan(increments, terminal, g_steps, depth,
                                        stride),)

    return scoped_vjp(sig, fwd, bwd)


# ---------------------------------------------------------------------------
# custom VJP: sqrt(M) checkpointing (beyond paper)
# ---------------------------------------------------------------------------

def _chunk_scan(levels, incs, depth: int):
    """Advance a levels state through one chunk of increments (c, B, d)."""
    def step(lv, dx):
        return tops.horner_step(lv, dx), None
    out, _ = jax.lax.scan(step, levels, incs)
    return out


def _fold_chunks(increments: jax.Array, chunk: int):
    """(B, M, d) -> time-major (n_chunks, chunk, B, d), zero-padded."""
    B, M, d = increments.shape
    n_chunks = -(-M // chunk)
    pad = n_chunks * chunk - M
    incs = jnp.pad(increments, ((0, 0), (0, pad), (0, 0)))  # zero incs = identity
    return jnp.moveaxis(incs, 1, 0).reshape(n_chunks, chunk, B, d)


def checkpoint_bwd_scan(increments: jax.Array, boundaries, g_flat: jax.Array,
                        depth: int, chunk: int) -> jax.Array:
    """√M-checkpoint backward: recompute within chunks from stored boundary
    states (levels stacked along a leading n_chunks axis).  Shared by the
    pure-JAX and Pallas-forward checkpoint VJPs."""
    B, M, d = increments.shape
    incs = _fold_chunks(increments, chunk)
    n_chunks = incs.shape[0]
    G = tops.flat_to_levels(g_flat, d, depth)

    def chunk_fn(levels, c_incs):
        return _chunk_scan(levels, c_incs, depth)

    def outer(G, xs):
        bound, c_incs = xs
        with jax.named_scope(COTANGENT_SCOPE):  # the replay is the pull-back's
            _, vjp_fn = jax.vjp(chunk_fn, bound, c_incs)
            G_prev, g_incs = vjp_fn(G)
        return G_prev, g_incs

    _, g_rev = jax.lax.scan(outer, G, (boundaries, incs), reverse=True)
    g = jnp.moveaxis(g_rev.reshape(n_chunks * chunk, B, d), 0, 1)
    return g[:, :M]


@lru_cache(maxsize=None)
def _make_checkpoint_vjp(depth: int, chunk: int):
    def sig(increments):
        return _scan_forward(increments, depth, stream=False)

    def fwd(increments):
        B, M, d = increments.shape
        incs = _fold_chunks(increments, chunk)

        def outer(levels, c_incs):
            new = _chunk_scan(levels, c_incs, depth)
            return new, [lv for lv in levels]  # boundary BEFORE the chunk

        init = tops.zero_levels((B,), d, depth, increments.dtype)
        final, boundaries = jax.lax.scan(outer, init, incs)
        return tops.levels_to_flat(final), (increments, boundaries)

    def bwd(res, g_flat):
        increments, boundaries = res
        return (checkpoint_bwd_scan(increments, boundaries, g_flat, depth,
                                    chunk),)

    return scoped_vjp(sig, fwd, bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def default_chunk(M: int) -> int:
    """√M chunk length for the checkpoint backward (paper-adjacent default)."""
    return max(1, int(math.isqrt(max(M, 1))))


def unsupported_stream_backward(backward: str) -> NotImplementedError:
    """The error raised for stream=True × backward cells without a kernel
    (kept in one place so dispatch and the pure-JAX route agree)."""
    return NotImplementedError(
        f"stream=True does not support backward={backward!r}: the streamed "
        "output already materialises every emitted prefix, so use "
        "backward='inverse' (one generalised §4.2 reverse scan, O(B·D_sig) "
        "live memory) or backward='autodiff'")


def _fused_jax_signature(increments: jax.Array, depth: int, spec, *, x0,
                         stream: bool, stream_stride: int, backward: str,
                         lengths, precision: str) -> jax.Array:
    """Fused-transform route of the pure-JAX engine: basepoint is an
    increment prepend, lead_lag/time are built per sub-step inside the scan.
    Streaming, lengths masking, and emissions are over the AUGMENTED axis."""
    from .transforms import (fused_augment, transform_dim, transform_lengths,
                             transform_time_aux)
    B, M, d = increments.shape
    increments = quantise_increments(increments, precision)
    if lengths is not None:
        lengths = as_lengths(lengths, B)
        increments = mask_increments(increments, lengths)
    if spec.basepoint:
        if x0 is None:
            raise ValueError("transform with basepoint needs x0= (the path "
                             "start point, shape (B, d)); repro.core."
                             "signature.signature passes it automatically")
        x0 = quantise_increments(jnp.asarray(x0).astype(increments.dtype),
                                 precision)
        increments = jnp.concatenate([x0[:, None, :], increments], axis=1)
    kspec = dataclasses_replace_nobp(spec)
    M_bp = increments.shape[1]
    lengths_bp = None if lengths is None else lengths + int(spec.basepoint)
    taux = transform_time_aux(kspec, B, M_bp, lengths_bp)
    M_aug = M_bp * kspec.sub_steps
    aug_lengths = transform_lengths(spec, lengths)
    if stream:
        if M_aug == 0:  # no steps -> no emissions
            out = jnp.zeros((B, 0, sig_dim(transform_dim(kspec, d), depth)),
                            increments.dtype)
        elif backward == "inverse":
            out = _make_fused_stream_inverse_vjp(depth, kspec, stream_stride)(
                increments, taux)
        elif backward == "autodiff":
            out = _subsample_stream(
                _fused_scan_forward(increments, taux, kspec, depth, True),
                M_aug, stream_stride)
        elif backward == "checkpoint":
            raise unsupported_stream_backward(backward)
        else:
            raise ValueError(f"unknown backward mode {backward!r}")
        if lengths is not None and M_aug:
            out = out * stream_emit_mask(M_aug, stream_stride,
                                         aug_lengths)[..., None].astype(out.dtype)
    elif backward == "inverse":
        out = _make_fused_inverse_vjp(depth, kspec)(increments, taux)
    elif backward == "autodiff":
        out = _fused_scan_forward(increments, taux, kspec, depth, False)
    elif backward == "checkpoint":
        # materialise-then-sweep fallback (documented in the ops support
        # matrix): the augment is linear, so autodiff through it IS the
        # transform adjoint, and the √M-checkpoint VJP is reused unchanged.
        e = fused_augment(increments, taux, kspec)
        out = _make_checkpoint_vjp(depth, default_chunk(M_aug))(e)
    else:
        raise ValueError(f"unknown backward mode {backward!r}")
    return out


def signature_from_increments(increments: jax.Array, depth: int, *,
                              stream: bool = False, stream_stride: int = 1,
                              backward: str = "inverse",
                              backend: str = "jax",
                              lengths=None, transform=None, x0=None,
                              precision: str = "fp32") -> jax.Array:
    """Truncated signature from increments (B, M, d) -> (B, D_sig).

    ``backend`` other than ``"jax"`` routes through the engine dispatch in
    :mod:`repro.kernels.ops` (Pallas kernels with the same custom VJPs) —
    including ``stream=True``, which emits every ``stream_stride``-th prefix
    signature as (B, M_out, D_sig).  ``stream`` with ``backward="checkpoint"``
    raises (see the support matrix in :mod:`repro.kernels.ops`).

    ``lengths`` (B,) makes the batch ragged: increments at or past each
    example's length are zero-masked (exact — zero is the identity update),
    so the terminal output is the per-example unpadded signature, gradients
    past the true end are exactly zero, and streamed emissions are masked
    after each example's true-terminal slot (:func:`stream_emit_slots`).

    ``transform`` (see :func:`repro.core.transforms.as_transform`) fuses
    ``basepoint`` / ``lead_lag`` / ``time_augment`` into the sweep: each
    augmented increment is built in registers per Horner sub-step, the
    (B, M_aug, d_aug) intermediate never exists, and streamed emissions /
    lengths are over the AUGMENTED step axis.  ``x0`` (B, d) is the path
    start, required iff the transform includes ``basepoint``.  ``precision``
    is ``"fp32"`` | ``"bf16_fp32"`` (bf16-quantised increments, fp32
    accumulation).
    """
    increments, squeeze = _as_batched(increments)
    if depth < 1:
        raise ValueError("depth must be >= 1")
    precision = canon_precision(precision)
    if backend != "jax":
        from repro.kernels import ops  # deferred: ops imports this module
        out = ops.signature(increments, depth, backend=backend,
                            backward=backward, stream=stream,
                            stream_stride=stream_stride, lengths=lengths,
                            transform=transform, x0=x0, precision=precision)
        return out[0] if squeeze else out
    from .transforms import as_transform
    spec = as_transform(transform)
    if spec is not None:
        out = _fused_jax_signature(increments, depth, spec, x0=x0,
                                   stream=stream, stream_stride=stream_stride,
                                   backward=backward, lengths=lengths,
                                   precision=precision)
        return out[0] if squeeze else out
    increments = quantise_increments(increments, precision)
    if lengths is not None:
        lengths = as_lengths(lengths, increments.shape[0])
        increments = mask_increments(increments, lengths)
    if stream:
        M = increments.shape[1]
        if M == 0:  # no steps -> no emissions (the custom VJPs need M >= 1)
            out = jnp.zeros((increments.shape[0], 0, sig_dim(
                increments.shape[-1], depth)), increments.dtype)
        elif backward == "inverse":
            out = _make_stream_inverse_vjp(depth, stream_stride)(increments)
        elif backward == "autodiff":
            out = _subsample_stream(_scan_forward(increments, depth,
                                                  stream=True),
                                    M, stream_stride)
        elif backward == "checkpoint":
            raise unsupported_stream_backward(backward)
        else:
            raise ValueError(f"unknown backward mode {backward!r}")
        if lengths is not None and M:
            out = out * stream_emit_mask(M, stream_stride,
                                         lengths)[..., None].astype(out.dtype)
    elif backward == "inverse":
        out = _make_inverse_vjp(depth)(increments)
    elif backward == "checkpoint":
        M = increments.shape[1]
        out = _make_checkpoint_vjp(depth, default_chunk(M))(increments)
    elif backward == "autodiff":
        out = _scan_forward(increments, depth, stream=False)
    else:
        raise ValueError(f"unknown backward mode {backward!r}")
    return out[0] if squeeze else out


def signature(path: jax.Array, depth: int, *, stream: bool = False,
              stream_stride: int = 1, basepoint: bool = False,
              backward: str = "inverse", backend: str = "jax",
              lengths=None, transform=None,
              precision: str = "fp32") -> jax.Array:
    """Truncated signature of a piecewise-linear path (B, M+1, d).

    ``basepoint=True`` prepends X_0 = 0 (so translation information is kept).
    ``backend`` selects the compute engine via :mod:`repro.kernels.ops`
    (``"jax"`` | ``"pallas"`` | ``"pallas_interpret"`` | ``"auto"``).
    ``stream=True`` returns all prefix signatures, strided by
    ``stream_stride`` (terminal always included).  ``lengths`` (B,) gives
    each example's true increment count for ragged batches (the padded tail
    is zero-masked — exact; ``basepoint=True`` adds one increment, which is
    accounted for here).  A :class:`repro.ragged.RaggedPaths` may be passed
    directly as ``path`` (its lengths are used unless overridden).

    ``transform`` (``"time_augment"`` / ``"lead_lag"`` / ``"basepoint"``,
    composable — see :func:`repro.core.transforms.as_transform`) applies the
    path transforms FUSED into the sweep; the basepoint start ``x0`` is taken
    from the path automatically.  ``precision`` is ``"fp32"`` |
    ``"bf16_fp32"``.  ``basepoint=True`` is the legacy point-prepend; prefer
    ``transform="basepoint"``.
    """
    values, rl = _unpack_ragged(path)
    if rl is not None and lengths is None:
        lengths = rl
    path, squeeze = _as_batched(values)
    if lengths is not None:
        lengths = as_lengths(lengths, path.shape[0])
    if basepoint:
        path = jnp.concatenate([jnp.zeros_like(path[:, :1]), path], axis=1)
        if lengths is not None:
            lengths = lengths + 1
    incs = tops.path_increments(path)
    from .transforms import as_transform
    spec = as_transform(transform)
    x0 = path[:, 0] if spec is not None and spec.basepoint else None
    out = signature_from_increments(incs, depth, stream=stream,
                                    stream_stride=stream_stride,
                                    backward=backward, backend=backend,
                                    lengths=lengths, transform=spec, x0=x0,
                                    precision=precision)
    return out[0] if squeeze else out


def signature_combine(flat_a: jax.Array, flat_b: jax.Array, d: int,
                      depth: int) -> jax.Array:
    """Chen combine: sig of concatenated paths from the two parts' sigs."""
    a = tops.flat_to_levels(flat_a, d, depth)
    b = tops.flat_to_levels(flat_b, d, depth)
    return tops.levels_to_flat(tops.chen_mul(a, b))


def signature_inverse(flat: jax.Array, d: int, depth: int) -> jax.Array:
    """Group inverse (= signature of the time-reversed path, Lemma 4.5)."""
    s = tops.flat_to_levels(flat, d, depth)
    return tops.levels_to_flat(tops.tensor_inverse(s))
