"""Windowed signature computation (paper §5) with unified route selection.

Given index pairs (l_i, r_i), pathsig returns all S_{t_{l_i}, t_{r_i}}(X) in a
single evaluation.  Two physical routes compute the same answer:

- ``"fold"``  — materialise per-window increment slices (zero-padded to the
  longest window; zero increments are identity Chen updates, so padding is
  exact) and fold the window axis into the batch axis: windows become an
  extra axis of parallelism, exactly the paper's saturation argument.
  Work ∝ Σ-of-padded-lengths = K · L_max.
- ``"chen"``  — the Signatory-style identity S_{l,r} = S_{0,l}^{-1} ⊗ S_{0,r}
  over ONE streamed forward pass of the whole path (the engine dispatch's
  ``stream=True`` axis, so it runs on every backend and stays differentiable
  through the streamed §4.2 reverse sweep).  Work ∝ M + c·K — for heavily
  overlapping sliding windows this is O(M + K) instead of O(Σ L_i).

``route="auto"`` picks between them with a host-side cost model (windows are
host arrays, so the choice is static and free): the chen route wins when the
total padded sliced length exceeds the streamed pass plus the per-window
combines by a safety factor (the paper notes the chen route is numerically
delicate, so ties go to fold).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import tensor_ops as tops
from .projection import projected_signature_from_increments
from .signature import (_unpack_ragged, as_lengths, mask_increments,
                        signature_combine, signature_from_increments,
                        signature_inverse)
from .transforms import as_transform
from .words import WordPlan, flat_index, sig_dim

ROUTES = ("auto", "fold", "chen")

# cost-model constants, fitted to a grid of CPU wall times of the jax engine
# (tests/data/windows_route_grid_cpu.json; tests/test_windows.py::
# test_auto_route_within_15pct_of_best_on_fig3_grid re-checks the fit against
# it).  They are not measured on the chip:
#   * a streamed chen-route step costs ~_CHEN_STEP_COST fold-route scan steps
#     (the streamed pass emits + stores a prefix signature per stride, the
#     fold pass only accumulates) — implied unit costs from the grid's records
#     are 1.45/2.17/2.33/3.22, median ~2.4;
#   * a window's inverse + Chen combine costs ~_CHEN_COMBINE_STEPS steps;
#   * the chen route must still win by _CHEN_ADVANTAGE before we accept its
#     numerics (S^{-1} ⊗ S cancellation on long prefixes) — a margin, not a
#     cost, now that _CHEN_STEP_COST carries the physics;
#   * the fold route pays a fixed dispatch cost the streamed pass does not
#     (window gather + (B, K, L, d) -> (B*K, L, d) reshape + fold-into-batch
#     launch), ~0.3 ms on that grid ≈ _FOLD_OVERHEAD_STEPS fold steps.
#     Without it the sub-millisecond records (small M·K) are unfittable: the
#     measured grid has chen winning at M=48 but losing at M=144, which no
#     pure work-ratio rule can reproduce.
_CHEN_COMBINE_STEPS = 4
_CHEN_STEP_COST = 2.5
_CHEN_ADVANTAGE = 1.5
_FOLD_OVERHEAD_STEPS = 256


def _check_windows(windows, M: int) -> np.ndarray:
    """Validate (K, 2) index pairs against a path of M increments."""
    windows_np = np.asarray(windows, dtype=np.int32).reshape(-1, 2)
    if windows_np.shape[0]:
        if (windows_np[:, 0] < 0).any() or (windows_np[:, 1] > M).any():
            raise ValueError(
                f"window indices must lie in [0, {M}] (M = number of path "
                f"increments); got {windows_np.tolist()}")
        if (windows_np[:, 0] > windows_np[:, 1]).any():
            raise ValueError(f"windows must satisfy l <= r; got "
                             f"{windows_np.tolist()}")
    return windows_np


def select_route(route: str, windows_np: np.ndarray, M: int,
                 chen_cost_scale: float = 1.0,
                 backward: str = "inverse") -> str:
    """Host-side cost model: fold work = K · L_max padded scan steps plus a
    fixed _FOLD_OVERHEAD_STEPS dispatch charge, chen work = one length-M
    streamed pass + ~_CHEN_COMBINE_STEPS steps per window, with each chen
    step costing _CHEN_STEP_COST fold steps (fitted to CPU wall times,
    ``tests/data/windows_route_grid_cpu.json``; scaled by
    ``chen_cost_scale`` when the streamed pass runs over a larger basis than
    the fold route, e.g. full truncation vs a small closure).

    ``backward="checkpoint"`` pins ``"auto"`` to the fold route: the chen
    route rides the streamed forward, which has no checkpoint backward (the
    support matrix in :mod:`repro.kernels.ops`)."""
    if route not in ROUTES:
        raise ValueError(f"unknown route {route!r}; expected one of {ROUTES}")
    if route != "auto":
        return route
    if windows_np.shape[0] == 0 or backward == "checkpoint":
        return "fold"
    lengths = windows_np[:, 1] - windows_np[:, 0]
    K, L_max = len(lengths), int(lengths.max())
    fold_work = K * max(L_max, 1) + _FOLD_OVERHEAD_STEPS
    chen_work = _CHEN_STEP_COST * (M + _CHEN_COMBINE_STEPS * K) \
        * chen_cost_scale
    return "chen" if fold_work > _CHEN_ADVANTAGE * chen_work else "fold"


def _window_increments(path: jax.Array, windows_np: np.ndarray,
                       lengths=None) -> jax.Array:
    """(B, M+1, d) x validated (K, 2) -> (B, K, L_max, d) zero-padded slices.

    ``windows_np`` must come from :func:`_check_windows` (host-side: shapes
    are static).  With ``lengths``, increments past each example's true end
    read as zero, so every window is exactly clipped to [l, min(r, L_b)].
    """
    L_max = int((windows_np[:, 1] - windows_np[:, 0]).max())
    windows = jnp.asarray(windows_np)
    K = windows.shape[0]
    incs = mask_increments(tops.path_increments(path), lengths)  # (B, M, d)
    M = incs.shape[1]
    lengths = windows[:, 1] - windows[:, 0]                # (K,)
    # gather indices: l_i + t, clamped; mask t >= length
    t = jnp.arange(L_max)[None, :]                         # (1, L)
    idx = jnp.clip(windows[:, :1] + t, 0, M - 1)           # (K, L)
    mask = (t < lengths[:, None]).astype(incs.dtype)       # (K, L)
    g = jnp.take(incs, idx.reshape(-1), axis=1)            # (B, K*L, d)
    g = g.reshape(incs.shape[0], K, L_max, incs.shape[2])
    return g * mask[None, :, :, None]


def _fold_window_ctx(path: jax.Array, windows_np: np.ndarray, spec,
                     lengths):
    """Per-window ragged context for the transform-fused fold route:
    -> (wlen (B, K) clipped window lengths, x0 (B, K, d) window starts).

    The transform applies PER WINDOW (each window is its own sub-path: time
    restarts at 0, lead-lag pairs don't straddle the window boundary, the
    basepoint is the window's first path value) — identical to calling
    ``signature(window_slice, transform=...)`` window by window.  Clipping
    follows the ragged semantics: window [l, r] on example b reads
    [min(l, L_b), min(r, L_b)].
    """
    B = path.shape[0]
    windows = jnp.asarray(windows_np)
    l_idx = jnp.broadcast_to(windows[None, :, 0], (B, windows.shape[0]))
    r_idx = jnp.broadcast_to(windows[None, :, 1], (B, windows.shape[0]))
    if lengths is not None:
        l_idx = jnp.minimum(l_idx, lengths[:, None])
        r_idx = jnp.minimum(r_idx, lengths[:, None])
    wlen = r_idx - l_idx
    x0 = None
    if spec is not None and spec.basepoint:
        x0 = jnp.take_along_axis(path, l_idx[..., None], axis=1)  # (B, K, d)
    return wlen, x0


def _chen_endpoint_states(path: jax.Array, windows_np: np.ndarray, depth: int,
                          backward: str, backend: str, lengths=None,
                          precision: str = "fp32"):
    """One streamed forward over the whole path -> (S_{0,l}, S_{0,r}) flats
    of shape (B, K, D_sig) each.  Differentiable on every backend via the
    streamed custom VJP in the dispatch layer.  With ``lengths``, increments
    are zero-masked first, so the streamed state freezes at each example's
    true terminal and S_{0,t} for t > L_b reads S_{0,L_b} — exactly the
    clipped-window semantics of the fold route."""
    incs = mask_increments(tops.path_increments(path), lengths)
    stream = signature_from_increments(incs, depth, stream=True,
                                       backward=backward, backend=backend,
                                       precision=precision)  # (B, M, D)
    # prepend the identity signature so index t reads S_{0,t} (t = 0 valid)
    ident = jnp.zeros_like(stream[:, :1])
    stream = jnp.concatenate([ident, stream], axis=1)       # (B, M+1, D)
    windows = jnp.asarray(windows_np)
    s_l = jnp.take(stream, windows[:, 0], axis=1)           # (B, K, D)
    s_r = jnp.take(stream, windows[:, 1], axis=1)
    return s_l, s_r


def _chen_route_signature(path: jax.Array, windows_np: np.ndarray, depth: int,
                          backward: str, backend: str, lengths=None,
                          precision: str = "fp32") -> jax.Array:
    """S_{l,r} = S_{0,l}^{-1} ⊗ S_{0,r} from the streamed forward."""
    d = path.shape[-1]
    s_l, s_r = _chen_endpoint_states(path, windows_np, depth, backward,
                                     backend, lengths, precision=precision)
    D = s_l.shape[-1]
    inv = signature_inverse(s_l.reshape(-1, D), d, depth)
    out = signature_combine(inv, s_r.reshape(-1, D), d, depth)
    return out.reshape(s_l.shape)


def _pin_transform_route(route: str, spec) -> str:
    """Transforms pin ``"auto"`` to the fold route: Chen's identity is over
    prefix signatures of ONE transformed path, but the per-window transform
    restarts time / lead-lag / basepoint at each window's own start, so
    S_{0,l}^{-1} ⊗ S_{0,r} of the transformed whole path is a DIFFERENT
    object than the transformed window's signature."""
    if spec is None:
        return route
    if route == "chen":
        raise NotImplementedError(
            "route='chen' cannot apply per-window transforms (the streamed "
            "prefix states are of the whole transformed path, not of each "
            "window's own transformed sub-path); use route='fold' or 'auto'")
    return "fold"


def windowed_signature(path: jax.Array, windows, depth: int, *,
                       route: str = "auto", backward: str = "inverse",
                       backend: str = "jax", lengths=None, transform=None,
                       precision: str = "fp32") -> jax.Array:
    """(B, M+1, d) x (K, 2) -> (B, K, D_sig) in one batched evaluation.

    ``route`` picks the physical plan (see module docstring): ``"fold"``
    slices + folds windows into the batch axis, ``"chen"`` combines endpoint
    states of one streamed pass, ``"auto"`` chooses by the host-side cost
    model.  Both routes ride the engine dispatch (:mod:`repro.kernels.ops`),
    so every backend's kernel forward + O(1)-in-length backward applies.  An
    empty window set yields an empty (B, 0, D_sig) result.

    ``lengths`` (B,) makes the batch ragged: window [l, r] is exactly
    clipped to [min(l, L_b), min(r, L_b)] per example on BOTH routes (a
    :class:`repro.ragged.RaggedPaths` may be passed directly as ``path``).

    ``transform`` applies a path transform PER WINDOW, fused into the fold
    route's engine sweep (the (B, K, L_aug, d_aug) augmented intermediate
    never materialises; the spec rides into the dispatch with each window's
    own clipped length and basepoint): identical to calling
    ``signature(window_slice, transform=...)`` per window.  Transforms pin
    ``route="auto"`` to fold; an explicit ``route="chen"`` raises.
    ``precision`` threads through to the engines on both routes.
    """
    values, rl = _unpack_ragged(path)
    if rl is not None and lengths is None:
        lengths = rl
    path = values
    if path.ndim == 2:
        return windowed_signature(path[None], windows, depth, route=route,
                                  backward=backward, backend=backend,
                                  lengths=lengths, transform=transform,
                                  precision=precision)[0]
    spec = as_transform(transform)
    route = _pin_transform_route(route, spec)
    B, d = path.shape[0], path.shape[-1]
    M = path.shape[1] - 1
    if lengths is not None:
        lengths = as_lengths(lengths, B)
    windows = _check_windows(windows, M)
    if windows.shape[0] == 0:
        from .transforms import transform_dim
        d_eff = transform_dim(spec, d) if spec else d
        return jnp.zeros((B, 0, sig_dim(d_eff, depth)), path.dtype)
    if select_route(route, windows, M, backward=backward) == "chen":
        return _chen_route_signature(path, windows, depth, backward, backend,
                                     lengths, precision=precision)
    g = _window_increments(path, windows, lengths)         # (B, K, L, d)
    K, L, d = g.shape[1:]
    if spec is None:
        flat = signature_from_increments(g.reshape(B * K, L, d), depth,
                                         backward=backward, backend=backend,
                                         precision=precision)
        return flat.reshape(B, K, -1)
    wlen, x0 = _fold_window_ctx(path, windows, spec, lengths)
    flat = signature_from_increments(
        g.reshape(B * K, L, d), depth, backward=backward, backend=backend,
        lengths=wlen.reshape(-1), transform=spec,
        x0=None if x0 is None else x0.reshape(B * K, d),
        precision=precision)
    return flat.reshape(B, K, -1)


def windowed_projection(path: jax.Array, windows, plan: WordPlan, *,
                        route: str = "auto", backward: str = "inverse",
                        backend: str = "jax", lengths=None, transform=None,
                        precision: str = "fp32") -> jax.Array:
    """Windowed + word-projected signatures in one call (B, K, |I|).

    The chen route computes the FULL truncated streamed signature at the
    plan's depth and projects the combined windows onto the requested words
    (Chen's identity needs all suffix coefficients, which an arbitrary word
    set does not retain), so its cost model is scaled by D_sig / closure —
    ``route="auto"`` only takes it when the overlap still pays for that.
    ``lengths`` clips windows per example exactly like
    :func:`windowed_signature`.

    ``transform`` / ``precision`` mirror :func:`windowed_signature`: the
    transform applies per window, fused into the fold route's sweep (the
    plan's words index the AUGMENTED alphabet); transforms pin ``"auto"``
    to fold and an explicit ``route="chen"`` raises.
    """
    values, rl = _unpack_ragged(path)
    if rl is not None and lengths is None:
        lengths = rl
    path = values
    if path.ndim == 2:
        return windowed_projection(path[None], windows, plan, route=route,
                                   backward=backward, backend=backend,
                                   lengths=lengths, transform=transform,
                                   precision=precision)[0]
    spec = as_transform(transform)
    route = _pin_transform_route(route, spec)
    B, d = path.shape[0], path.shape[-1]
    M = path.shape[1] - 1
    if lengths is not None:
        lengths = as_lengths(lengths, B)
    windows = _check_windows(windows, M)
    if windows.shape[0] == 0:
        return jnp.zeros((B, 0, len(plan.words)), path.dtype)
    scale = sig_dim(d, plan.depth) / float(1 + plan.closure_size)
    if select_route(route, windows, M, chen_cost_scale=scale,
                    backward=backward) == "chen":
        full = _chen_route_signature(path, windows, plan.depth, backward,
                                     backend, lengths, precision=precision)
        idx = jnp.asarray([flat_index(w, d) for w in plan.words])
        return jnp.take(full, idx, axis=-1)
    g = _window_increments(path, windows, lengths)
    K, L, d = g.shape[1:]
    if spec is None:
        out = projected_signature_from_increments(g.reshape(B * K, L, d),
                                                  plan, backward=backward,
                                                  backend=backend,
                                                  precision=precision)
        return out.reshape(B, K, -1)
    wlen, x0 = _fold_window_ctx(path, windows, spec, lengths)
    out = projected_signature_from_increments(
        g.reshape(B * K, L, d), plan, backward=backward, backend=backend,
        lengths=wlen.reshape(-1), transform=spec,
        x0=None if x0 is None else x0.reshape(B * K, d),
        precision=precision)
    return out.reshape(B, K, -1)


def windowed_signature_chen(path: jax.Array, windows, depth: int, *,
                            backward: str = "inverse",
                            backend: str = "jax", lengths=None) -> jax.Array:
    """Signatory-style alternative: S_{l,r} = S_{0,l}^{-1} ⊗ S_{0,r}.

    Equivalent to ``windowed_signature(..., route="chen")`` — kept as a
    public name with the same ``backend=``/``backward=``/``lengths=``
    surface as the other windowed entry points.
    """
    return windowed_signature(path, windows, depth, route="chen",
                              backward=backward, backend=backend,
                              lengths=lengths)


def expanding_windows(M: int, stride: int = 1) -> np.ndarray:
    """[0, stride], [0, 2·stride], ..., always ending with the full [0, M]
    window (the path tail is never silently dropped when stride ∤ M)."""
    if M < 1 or stride < 1:
        raise ValueError(f"need M >= 1 and stride >= 1, got M={M}, "
                         f"stride={stride}")
    r = np.arange(stride, M + 1, stride, dtype=np.int32)
    if r.size == 0 or r[-1] != M:
        r = np.concatenate([r, np.asarray([M], np.int32)])
    return np.stack([np.zeros_like(r), r], axis=1)


def sliding_windows(M: int, length: int, stride: int = 1) -> np.ndarray:
    if not 1 <= length <= M:
        raise ValueError(f"window length must satisfy 1 <= length <= M; got "
                         f"length={length}, M={M}")
    if stride < 1:
        raise ValueError(f"stride must be >= 1, got {stride}")
    l = np.arange(0, M - length + 1, stride, dtype=np.int32)
    return np.stack([l, l + length], axis=1)


def dyadic_windows(M: int, levels: int) -> np.ndarray:
    """Dyadic hierarchy of windows as in the generalised signature method."""
    out = []
    for lev in range(levels):
        k = 2 ** lev
        bounds = np.linspace(0, M, k + 1).astype(np.int32)
        for i in range(k):
            if bounds[i + 1] > bounds[i]:
                out.append((bounds[i], bounds[i + 1]))
    return np.asarray(out, dtype=np.int32)
