"""Signature projections onto arbitrary word sets (paper §3.1, §7).

The engine updates the coefficients of the *prefix closure* of a requested
word set I with the per-word Horner rule (paper Alg. 1), exactly as the CUDA
kernels do, but vectorised over (batch, closure-rows).  All index tables come
from :func:`repro.core.words.make_plan` on the host.

Coefficient buffer layout: ``S`` has shape (B, 1 + W) where row 0 is the
constant S[eps] = 1 and row 1..W are the closure words in level-major order.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from .signature import (COTANGENT_SCOPE, PROJ_INDEX_SCOPE,
                        RECONSTRUCT_SCOPE, _fold_chunks, _subsample_stream,
                        as_lengths, default_chunk, mask_increments,
                        scoped_vjp, stream_emit_mask, stream_emit_steps,
                        unsupported_stream_backward)
from .words import WordPlan, make_plan
from . import tensor_ops as tops


def _plan_tables(plan: WordPlan):
    # NB: numpy, not jnp — these tables are captured by lru_cached closures,
    # and jnp constants materialised inside a jit trace would leak tracers.
    return (np.asarray(plan.prefix_idx), np.asarray(plan.letters),
            np.asarray(plan.inv), np.asarray(plan.emit))


def projected_step(S: jax.Array, dx: jax.Array, prefix_idx, letters, inv,
                   emit) -> jax.Array:
    """One Chen update of all closure coefficients (paper Alg. 1, batched).

    S: (B, 1+W) with S[:, 0] == 1;  dx: (B, d).
    """
    depth = prefix_idx.shape[1]
    B = S.shape[0]
    acc = jnp.zeros((B, prefix_idx.shape[0]), S.dtype)
    h = acc
    for j in range(depth):  # static unroll over Horner steps
        with jax.named_scope(PROJ_INDEX_SCOPE):
            pfx = jnp.take(S, prefix_idx[:, j], axis=1)   # S_old[w_{1:j}]
            dxl = jnp.take(dx, letters[:, j], axis=1)     # ΔX^(i_{j+1})
        acc = (pfx + acc) * dxl * inv[None, :, j]         # /(n - j)
        h = h + acc * emit[None, :, j]                    # collect at j = n-1
    return S.at[:, 1:].add(h)


def _scan_projected(increments: jax.Array, plan: WordPlan,
                    stream: bool, stream_stride: int = 1) -> jax.Array:
    B, M, d = increments.shape
    tables = _plan_tables(plan)

    def step(S, dx):
        new = projected_step(S, dx, *tables)
        return new, (new if stream else None)

    S0 = jnp.concatenate([jnp.ones((B, 1), increments.dtype),
                          jnp.zeros((B, plan.closure_size), increments.dtype)],
                         axis=1)
    final, ys = jax.lax.scan(step, S0, jnp.moveaxis(increments, 1, 0))
    out_rows = jnp.asarray(plan.out_rows)
    if stream:
        out = jnp.moveaxis(jnp.take(ys, out_rows, axis=2), 0, 1)
        return _subsample_stream(out, M, stream_stride)
    return jnp.take(final, out_rows, axis=1)


def _closure_init(B: int, plan: WordPlan, dtype) -> jax.Array:
    return jnp.concatenate([jnp.ones((B, 1), dtype),
                            jnp.zeros((B, plan.closure_size), dtype)], axis=1)


def projected_inverse_bwd_scan(increments: jax.Array, S_T: jax.Array,
                               g_out: jax.Array, plan: WordPlan) -> jax.Array:
    """§4.2 backward for word projections: invert the closure update step by
    step (the closure is prefix-closed, so the inverse step is exact) while
    accumulating cotangents.  ``S_T`` is the terminal closure buffer
    (B, 1 + W); any forward that produces it (JAX scan or the Pallas word
    kernel run over the closure) can pair with this backward."""
    tables = _plan_tables(plan)

    def step_fn(S, dx):
        return projected_step(S, dx, *tables)

    # scatter the projection cotangent back onto the closure buffer
    G_T = jnp.zeros_like(S_T).at[:, jnp.asarray(plan.out_rows)].add(g_out)

    def step(carry, dx):
        S, G = carry
        with jax.named_scope(RECONSTRUCT_SCOPE):
            S_prev = step_fn(S, -dx)
        with jax.named_scope(COTANGENT_SCOPE):
            _, vjp_fn = jax.vjp(step_fn, S_prev, dx)
            G_prev, g_dx = vjp_fn(G)
        return (S_prev, G_prev), g_dx

    (_, _), g_rev = jax.lax.scan(step, (S_T, G_T),
                                 jnp.moveaxis(increments, 1, 0),
                                 reverse=True)
    return jnp.moveaxis(g_rev, 0, 1)


@lru_cache(maxsize=None)
def _make_projected_vjp(plan: WordPlan):
    tables = _plan_tables(plan)

    def step_fn(S, dx):
        return projected_step(S, dx, *tables)

    def proj(increments):
        return _scan_projected(increments, plan, stream=False)

    def fwd(increments):
        B, M, d = increments.shape

        def step(S, dx):
            return step_fn(S, dx), None

        S0 = _closure_init(B, plan, increments.dtype)
        S_T, _ = jax.lax.scan(step, S0, jnp.moveaxis(increments, 1, 0))
        out = jnp.take(S_T, jnp.asarray(plan.out_rows), axis=1)
        return out, (increments, S_T)

    def bwd(res, g_out):
        increments, S_T = res
        return (projected_inverse_bwd_scan(increments, S_T, g_out, plan),)

    return scoped_vjp(proj, fwd, bwd)


def projected_stream_inverse_bwd_scan(increments: jax.Array, S_T: jax.Array,
                                      g_steps: jax.Array, plan: WordPlan,
                                      stride: int = 1) -> jax.Array:
    """§4.2 backward for *streamed* word projections: cotangents arrive at
    every emitted step; one reverse scan inverts the closure update while
    folding each step's (closure-scattered) cotangent in just before the
    pull-back.  ``S_T`` is the terminal closure buffer (B, 1 + W) — the only
    residual besides the increments, whichever forward produced it (JAX scan
    or the streamed Pallas word kernel over the closure)."""
    B, M, d = increments.shape
    tables = _plan_tables(plan)
    out_rows = jnp.asarray(plan.out_rows)

    def step_fn(S, dx):
        return projected_step(S, dx, *tables)

    # scatter the per-step projection cotangents onto closure buffers, then
    # (for stride > 1) onto the full time axis
    g_close = jnp.zeros((*g_steps.shape[:2], S_T.shape[-1]), g_steps.dtype
                        ).at[:, :, out_rows].add(g_steps)
    steps = stream_emit_steps(M, stride)
    if len(steps) == M:
        g_dense = g_close
    else:
        g_dense = jnp.zeros((B, M, S_T.shape[-1]), g_steps.dtype
                            ).at[:, jnp.asarray(steps)].set(g_close)

    def step(carry, xs):
        S, G = carry
        dx, g_j = xs
        with jax.named_scope(RECONSTRUCT_SCOPE):
            S_prev = step_fn(S, -dx)
        with jax.named_scope(COTANGENT_SCOPE):
            _, vjp_fn = jax.vjp(step_fn, S_prev, dx)
            G_prev, g_dx = vjp_fn(G + g_j)
        return (S_prev, G_prev), g_dx

    (_, _), g_rev = jax.lax.scan(step, (S_T, jnp.zeros_like(S_T)),
                                 (jnp.moveaxis(increments, 1, 0),
                                  jnp.moveaxis(g_dense, 1, 0)), reverse=True)
    return jnp.moveaxis(g_rev, 0, 1)


@lru_cache(maxsize=None)
def _make_projected_stream_vjp(plan: WordPlan, stride: int):
    tables = _plan_tables(plan)

    def proj(increments):
        return _scan_projected(increments, plan, stream=True,
                               stream_stride=stride)

    def fwd(increments):
        B, M, d = increments.shape
        out_rows = jnp.asarray(plan.out_rows)

        def step(S, dx):
            new = projected_step(S, dx, *tables)
            return new, jnp.take(new, out_rows, axis=1)

        S_T, ys = jax.lax.scan(step, _closure_init(B, plan, increments.dtype),
                               jnp.moveaxis(increments, 1, 0))
        out = _subsample_stream(jnp.moveaxis(ys, 0, 1), M, stride)
        return out, (increments, S_T)

    def bwd(res, g_steps):
        increments, S_T = res
        return (projected_stream_inverse_bwd_scan(increments, S_T, g_steps,
                                                  plan, stride),)

    return scoped_vjp(proj, fwd, bwd)


@lru_cache(maxsize=None)
def _make_projected_checkpoint_vjp(plan: WordPlan, chunk: int):
    """√M-checkpoint VJP for projections (beyond paper): store closure states
    at chunk boundaries, recompute within chunks on the backward — immune to
    inverse-reconstruction drift on very long paths."""
    tables = _plan_tables(plan)

    def chunk_fn(S, incs):  # incs: (c, B, d)
        def step(S, dx):
            return projected_step(S, dx, *tables), None
        out, _ = jax.lax.scan(step, S, incs)
        return out

    def fold(increments):
        return _fold_chunks(increments, chunk)

    def proj(increments):
        return _scan_projected(increments, plan, stream=False)

    def fwd(increments):
        B, M, d = increments.shape
        incs = fold(increments)

        def outer(S, c_incs):
            return chunk_fn(S, c_incs), S  # boundary BEFORE the chunk

        S_T, boundaries = jax.lax.scan(outer, _closure_init(
            B, plan, increments.dtype), incs)
        out = jnp.take(S_T, jnp.asarray(plan.out_rows), axis=1)
        return out, (increments, boundaries)

    def bwd(res, g_out):
        increments, boundaries = res
        B, M, d = increments.shape
        incs = fold(increments)
        n_chunks = incs.shape[0]
        G = jnp.zeros((B, 1 + plan.closure_size), g_out.dtype
                      ).at[:, jnp.asarray(plan.out_rows)].add(g_out)

        def outer(G, xs):
            bound, c_incs = xs
            with jax.named_scope(COTANGENT_SCOPE):
                _, vjp_fn = jax.vjp(chunk_fn, bound, c_incs)
                G_prev, g_incs = vjp_fn(G)
            return G_prev, g_incs

        _, g_rev = jax.lax.scan(outer, G, (boundaries, incs), reverse=True)
        g = jnp.moveaxis(g_rev.reshape(n_chunks * chunk, B, d), 0, 1)
        return (g[:, :M],)

    return scoped_vjp(proj, fwd, bwd)


def projected_signature_from_increments(increments: jax.Array,
                                        plan: WordPlan, *,
                                        stream: bool = False,
                                        stream_stride: int = 1,
                                        backward: str = "inverse",
                                        backend: str = "jax",
                                        lengths=None, transform=None,
                                        x0=None,
                                        precision: str = "fp32") -> jax.Array:
    """π_I(S_{0,T}(X)) for the plan's word set I.  (B, M, d) -> (B, |I|).

    ``backend`` other than ``"jax"`` routes through the engine dispatch in
    :mod:`repro.kernels.ops` — including ``stream=True``, which emits every
    ``stream_stride``-th per-step projection as (B, M_out, |I|).
    ``lengths`` (B,) makes the batch ragged (zero-masked padded tails,
    exact terminals, masked post-end emissions, zero grads past the end).
    ``transform`` fuses path transforms into the sweep (the plan must be
    over the AUGMENTED alphabet; ``x0=`` is the path start, needed iff the
    transform has a basepoint); ``precision`` is ``"fp32"`` | ``"bf16_fp32"``
    — both route through :func:`repro.kernels.ops.projected`.
    """
    from .transforms import as_transform
    from .signature import canon_precision
    increments, squeeze = _as_batched(increments)
    spec = as_transform(transform)
    precision = canon_precision(precision)
    if backend != "jax" or spec is not None or precision != "fp32":
        from repro.kernels import ops  # deferred: ops imports this module
        out = ops.projected(increments, plan, backend=backend,
                            backward=backward, stream=stream,
                            stream_stride=stream_stride, lengths=lengths,
                            transform=spec, x0=x0, precision=precision)
        return out[0] if squeeze else out
    if lengths is not None:
        lengths = as_lengths(lengths, increments.shape[0])
        increments = mask_increments(increments, lengths)
    if stream:
        if backward == "inverse":
            out = _make_projected_stream_vjp(plan, stream_stride)(increments)
        elif backward == "autodiff":
            out = _scan_projected(increments, plan, stream=True,
                                  stream_stride=stream_stride)
        elif backward == "checkpoint":
            raise unsupported_stream_backward(backward)
        else:
            raise ValueError(f"unknown backward mode {backward!r}")
        if lengths is not None and increments.shape[1]:
            out = out * stream_emit_mask(
                increments.shape[1], stream_stride,
                lengths)[..., None].astype(out.dtype)
    elif backward == "autodiff":
        out = _scan_projected(increments, plan, stream=False)
    elif backward == "inverse":
        out = _make_projected_vjp(plan)(increments)
    elif backward == "checkpoint":
        out = _make_projected_checkpoint_vjp(
            plan, default_chunk(increments.shape[1]))(increments)
    else:
        raise ValueError(f"unknown backward mode {backward!r}")
    return out[0] if squeeze else out


def projected_signature(path: jax.Array, words, d: int | None = None, *,
                        plan: WordPlan | None = None, stream: bool = False,
                        stream_stride: int = 1, backward: str = "inverse",
                        backend: str = "jax", lengths=None, transform=None,
                        precision: str = "fp32") -> jax.Array:
    """Signature coefficients of an arbitrary word set (paper §7.1).

    ``words`` is an iterable of letter tuples (0-based) or a prebuilt plan.
    ``lengths`` (B,) makes the batch ragged; a
    :class:`repro.ragged.RaggedPaths` may be passed directly as ``path``.
    ``transform`` fuses path transforms into the sweep; the words (and any
    prebuilt ``plan``) must be over the AUGMENTED alphabet — when ``d`` is
    omitted it defaults to the augmented channel count.  The basepoint start
    ``x0`` is taken from the path automatically.  ``precision`` is
    ``"fp32"`` | ``"bf16_fp32"``.
    """
    from .signature import _unpack_ragged
    from .transforms import as_transform, transform_dim
    values, rl = _unpack_ragged(path)
    if rl is not None and lengths is None:
        lengths = rl
    path, squeeze = _as_batched(values)
    spec = as_transform(transform)
    if plan is None:
        if d is None:
            d = transform_dim(spec, path.shape[-1])
        plan = make_plan(tuple(tuple(w) for w in words), d)
    incs = tops.path_increments(path)
    x0 = path[:, 0] if spec is not None and spec.basepoint else None
    out = projected_signature_from_increments(incs, plan, stream=stream,
                                              stream_stride=stream_stride,
                                              backward=backward,
                                              backend=backend,
                                              lengths=lengths, transform=spec,
                                              x0=x0, precision=precision)
    return out[0] if squeeze else out


def _as_batched(x: jax.Array) -> tuple[jax.Array, bool]:
    if x.ndim == 2:
        return x[None], True
    if x.ndim == 3:
        return x, False
    raise ValueError(f"expected (M, d) or (B, M, d), got {x.shape}")
