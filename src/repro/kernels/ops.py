"""The engine-dispatch layer: every public signature entry point routes here.

``repro.core.signature``, ``repro.core.projection``, ``repro.core.windows``,
``repro.core.logsignature`` and ``repro.models.sig_head`` all funnel their
``backend=`` / ``backward=`` arguments into :func:`signature` and
:func:`projected`, so kernel selection and differentiation policy live in
exactly one place.

``backend`` selection:

- ``"jax"``      — pure-JAX levelwise Horner scan (works everywhere).
- ``"pallas"``   — Pallas TPU kernels, compiled for the accelerator;
                   raises when JAX's default backend is not a TPU.
- ``"pallas_interpret"`` — same kernels executed in interpret mode (CPU
                   validation of the kernels).
- ``"auto"``     — pallas on TPU, jax elsewhere.
- ``"hybrid"``   — projected sets only: dense levelwise Horner for all of
                   W_{<=N-1} plus per-word chains for the requested level-N
                   words (``repro.core.hybrid``), with the §4.2 inverse VJP.
                   The §3.3 log-signature shape; wasteful for sets sparse at
                   low levels.

Backend × backward × stream support matrix
------------------------------------------

Every supported cell is differentiable via ``jax.grad``; cells marked (jax)
fall back to the pure-JAX engine because the Pallas forward cannot supply the
residuals that backward mode needs (no autodiff rule through ``pallas_call``;
no chunk-boundary output for the word kernel); cells marked ✗ raise
``NotImplementedError``:

=====================  ======  ============================  =====================  ==========
engine                 stream  backward="inverse"            "checkpoint"           "autodiff"
=====================  ======  ============================  =====================  ==========
jax, truncated         False   scan fwd + §4.2 reverse       √M boundaries + replay scan AD
jax, truncated         True    streamed scan fwd +           ✗                      scan AD
                               streamed §4.2 reverse
jax, projected         False   scan fwd + §4.2 reverse       √M boundaries + replay scan AD
jax, projected         True    streamed scan fwd +           ✗                      scan AD
                               streamed §4.2 reverse
pallas, truncated      False   kernel fwd + kernel §4.2      kernel chunk fwd,      (jax)
                               reverse (scan where the       Chen-combined, √M bwd
                               blocks do not fit VMEM)
pallas, truncated      True    streamed kernel fwd +         ✗                      (jax)
                               streamed §4.2 reverse
pallas, projected      False   dense route: kernel fwd +     (jax)                  (jax)
                               kernel §4.2 reverse to the
                               set's depth, then a gather;
                               else closure-kernel fwd +
                               §4.2 reverse scan
pallas, projected      True    streamed closure-kernel fwd   ✗                      (jax)
                               + streamed §4.2 reverse
hybrid, projected      False   dense+top fwd + §4.2 reverse  (jax)                  top-level
                                                                                    scan AD
hybrid, projected      True    ✗                             ✗                      ✗
=====================  ======  ============================  =====================  ==========

``sig_gram`` row (:func:`gram`): the weighted Gram product
G = S_x diag(ω) S_yᵀ is one extra dispatch cell layered on the signature
engines above.  Backends: ``jax`` runs a word-blocked fori-loop (live state
O(B_x·B_y + B·block_words)); ``pallas``/``pallas_interpret`` run the tile
kernel in :mod:`repro.kernels.sig_gram` (same memory law, MXU contraction);
``hybrid`` falls back to jax.  Every backend is differentiable in all three
operands through one closed-form product VJP (dS_x = (g S_y)·ω,
dS_y = (gᵀ S_x)·ω, dω = Σ_ij g_ij S_x S_y) — the signature *legs* feeding it
carry whichever §4.2 inverse/checkpoint VJP the caller picked, so a full
kernel-method loss trains in O(B·D_sig) signature memory end to end.

The Pallas ``inverse`` rows are the paper's headline training path: the
kernel computes the forward, the backward reconstructs
S_{0,t_{j-1}} = S_{0,t_j} ⊗ exp(−ΔX_j) in O(B·D_sig) memory, independent of
sequence length (§4.2).  For plain truncated signatures that backward is a
second kernel (:mod:`repro.kernels.sig_trunc_bwd`), which holds S and its
cotangent in VMEM for the whole reverse sweep, with or without a fused
transform; the XLA scan ``inverse_bwd_scan`` runs instead where S and its
cotangent do not fit VMEM.  Both truncated kernels stream the time axis
through VMEM in chunks, so path length alone never forces the scan.  The
``checkpoint`` row for truncated signatures runs the kernel over
√M-length chunks folded into the batch axis, Chen-combines the chunk
signatures (storing the √M boundary states), and replays chunks on the
backward — drift-immune on very long paths.

The projected ``pallas`` × ``inverse`` row (not streamed, plain or with a
fused transform) picks one of two engines per call, by shape alone.  The
dense route runs the two truncated kernels to the word set's depth and
gathers the words' coordinates from the ``(B, D_sig)`` output; it is taken
where both kernels hold the shape (the forward's split fits VMEM and the
backward runs the kernel, not the scan) and the truncated tensor has at
most :data:`DENSE_ROWS_PER_CLOSURE_ROW` 8-row-aligned rows per row of the
set's prefix closure.  Every other set runs the word kernel over the
closure and the projected §4.2 reverse scan.  The counter
``pathsig_projected_engine_total{engine="dense"|"words"}`` ticks once per
traced call by the engine picked.

``lengths`` column: EVERY cell above additionally accepts ``lengths=`` (B,)
for ragged batches — orthogonal to backend × backward × stream because it is
resolved before the engine runs: padded-tail increments are zero-masked (a
zero increment is the identity Chen update, so terminal outputs are exactly
the per-example unpadded signatures, to the bit), the outermost mask multiply
zeroes cotangents past each example's true end, and streamed outputs are
masked after the true-terminal slot (gather it back with
``repro.core.signature.ragged_terminal``).  ``gram`` has no time axis;
ragged batches enter it through the signature legs
(``repro.sigkernel.sig_gram(..., x_lengths=, y_lengths=)``).

``transform`` column: every cell above additionally accepts ``transform=``
(a :class:`repro.core.transforms.Transform` or spec string such as
``"time_augment+lead_lag"``) applied to the path before signing, with the
``(B, M', 2d+1)``-sized augmented increment tensor **never materialised** on
the fast rows.  How each cell gets there:

- ``pallas`` × ``inverse`` (truncated and projected, streamed or not): FUSED.
  The raw ``(B, M, d_raw)`` increments enter the kernel and each augmented
  increment ([t?, lag, lead] channels) is built in VMEM per time sub-step
  (``sig_trunc`` / ``sig_words`` ``transform=`` path).  The §4.2 backward
  reconstructs the augmented increments transiently level-by-step and
  :func:`repro.core.transforms.fused_adjoint` pulls the cotangent back to
  the raw channels — still O(B·D_sig) live memory.
- ``jax`` engines: the augment is fused into the scan step
  (``core.signature._fused_jax_signature``) — XLA fuses the per-step
  concat into the Horner update, no (M, 2d+1) intermediate in HBM.
- ``checkpoint`` / ``time_chunks>1`` / ``mesh`` / ``hybrid``: materialise
  fallback — ``augment_increments`` builds the augmented tensor once, then
  the plain cell runs (the augment is linear, ordinary AD transposes it).
- ``basepoint``: resolved at dispatch by prepending the ``x0=`` increment
  (lengths shift by one); a basepoint-only transform recurses into the
  plain cell, exactly.

``precision`` column: every cell above (and :func:`gram`) accepts
``precision="fp32"`` (default) or ``"bf16_fp32"``.  Mixed precision is
quantise-once-at-dispatch: increments are rounded to bf16 with a
straight-through-estimator cast *before* any engine runs, so every
backend × backward combination sees identical inputs and agrees
bit-for-bit; the Pallas kernels then *store* their increment blocks in
bf16 (halving VMEM state/block footprint — see
``sig_trunc.state_footprint(..., itemsize=2)``) while all Horner/Chen
accumulation stays fp32.  Gradients flow at fp32 through the STE.  The
per-level forward error against the fp32 oracle is bounded by
``level · 2^-8`` relative (bf16 has 8 mantissa bits; products of ``n``
rounded increments compound n rounding errors — ``tests/test_precision.py``
checks the measured bound at depth ≤ 6).

``mesh`` column: EVERY cell above (and :func:`gram`) is additionally
SPMD-capable — orthogonal to backend × backward × stream × lengths because
it is resolved OUTSIDE the engine.  Installing
``repro.distributed.ctx.sharding_ctx(mesh)`` whose rules map the "batch"
logical axis onto mesh axes (the default rules do, via 'data'/'pod';
``repro.launch.mesh.make_sig_mesh()`` builds the 1-axis case) wraps the
single-device cell in ``shard_map`` over that axis: each shard rebuilds the
same custom-VJP closure on its local batch, so gradients shard identically
to the primals; batches are zero-padded up to a multiple of the axis size
(zero increments are identity updates, padded rows are sliced off, their
cotangents are exactly zero); ``lengths`` ride along batch-sharded.
:func:`gram` instead runs the cross-device ring of :func:`_gram_ring`
(local X rows, Y tiles rotating by ``jax.lax.ppermute``, O(B·D_sig)
communication, no replicated Gram-sized intermediate).  Outside any context
every entry point is bit-identical to the single-device path.  The logical
axes "path_time" and "sig_words" exist in the default rules (unsharded) so
launchers can annotate time/word dims without touching the batch split.

``stream=True`` rows emit every ``stream_stride``-th prefix signature inside
the time loop — (B, M_out, D) with M_out = ceil(M / stride), terminal step
always included (``repro.core.signature.stream_emit_steps``).  Their
``inverse`` backward is the §4.2 reverse sweep generalised to cotangents
arriving at every emitted step: still ONE reverse scan with O(B·D_sig) live
memory, with only the terminal state kept as residual.  ``checkpoint`` is
pointless there (the output already materialises the boundary states) and
raises.

Also provides ``signature_time_parallel``: a beyond-paper optimisation that
splits the time axis into C chunks, computes chunk signatures independently
(folded into the batch axis — more parallel work, the paper's windowing
argument applied to *one* signature) and Chen-combines them in a log-depth
tree.  The paper explicitly does not parallelise over sequence length
(§6.1); on TPU this recovers utilisation for long paths at small batch.
"""
from __future__ import annotations

import functools
import weakref
from collections import OrderedDict, namedtuple
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro import obs
from repro.core import tensor_ops as tops
from repro.distributed.ctx import current_mesh, logical_axes
from repro.distributed.ctx import shard as shard_constraint
from repro.core.signature import (COTANGENT_SCOPE, PROJ_INDEX_SCOPE,
                                  as_lengths,
                                  canon_precision,
                                  checkpoint_bwd_scan,
                                  dataclasses_replace_nobp, default_chunk,
                                  inverse_bwd_scan, mask_increments,
                                  quantise_increments, scoped_vjp,
                                  signature_from_increments,
                                  stream_emit_mask, stream_inverse_bwd_scan,
                                  unsupported_stream_backward)
from repro.core.transforms import (as_transform, augment_increments,
                                   fused_adjoint, fused_augment,
                                   transform_dim, transform_steps,
                                   transform_time_aux)
from repro.kernels import autotune
from repro.core.projection import (projected_inverse_bwd_scan,
                                   projected_signature_from_increments,
                                   projected_stream_inverse_bwd_scan)
from repro.core.words import (TiledPlan, WordPlan, flat_index, make_plan,
                              make_tiled_plan, sig_dim)
from .sig_gram import sig_gram_tiles
from . import sig_trunc_bwd
from .sig_trunc import (VMEM_LIMIT_BYTES, block_bytes, choose_split,
                        lane_tile, level_offsets, sig_trunc, time_chunk,
                        vmem_footprint)
from .sig_words import sig_words

BACKENDS = ("jax", "pallas", "pallas_interpret", "auto", "hybrid")
BACKWARDS = ("inverse", "checkpoint", "autodiff")


# ---------------------------------------------------------------------------
# plan caches: one shared bounded policy.  Every interned plan / compiled
# closure / shard_map wrapper in this module is registered here, so serving
# traffic with an unbounded stream of word sets evicts old entries instead of
# growing without limit.  Eviction is always safe — entries are pure
# functions of their keys, so a rebuilt entry produces bit-identical results
# (jit recompiles, nothing else changes).
# ---------------------------------------------------------------------------

PLAN_CACHE_MAXSIZE = 256          # default per-cache bound

_PLAN_CACHE_FNS: dict = {}        # cache name -> undecorated fn


CacheInfo = namedtuple("CacheInfo",
                       ("hits", "misses", "maxsize", "currsize", "evictions"))


class _CountingLru:
    """``functools.lru_cache`` semantics plus an eviction counter
    (``lru_cache`` itself never reports how many entries it dropped, which
    is exactly the number serving traffic needs to see).  Same key rule as
    ``lru_cache``: positional args plus sorted kwargs, all hashable."""

    def __init__(self, fn, maxsize):
        self._fn = fn
        self._maxsize = maxsize
        self._data: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        functools.update_wrapper(self, fn)

    def __call__(self, *args, **kwargs):
        key = (args, tuple(sorted(kwargs.items()))) if kwargs else args
        data = self._data
        if key in data:
            data.move_to_end(key)
            self.hits += 1
            return data[key]
        self.misses += 1
        val = self._fn(*args, **kwargs)
        data[key] = val
        if self._maxsize is not None:
            while len(data) > self._maxsize:
                data.popitem(last=False)
                self.evictions += 1
        return val

    def cache_info(self) -> CacheInfo:
        return CacheInfo(self.hits, self.misses, self._maxsize,
                         len(self._data), self.evictions)

    def cache_clear(self) -> None:
        self._data.clear()


def plan_cache(fn):
    """Register ``fn`` under the shared bounded-LRU plan-cache policy."""
    _PLAN_CACHE_FNS[fn.__name__] = fn
    return _CountingLru(fn, PLAN_CACHE_MAXSIZE)

# name -> WeakSet of live BoundedCache instances sharing that report line
_INSTANCE_CACHES: dict[str, weakref.WeakSet] = {}


class BoundedCache:
    """Per-instance LRU under the shared plan-cache policy.

    The serving layer keeps jitted per-shape computes on *instances*
    (``DynamicBatcher``, ``SessionStore``) rather than module functions, so
    ``lru_cache`` can't bound them.  A ``BoundedCache`` follows
    ``PLAN_CACHE_MAXSIZE`` dynamically (``set_plan_cache_maxsize`` re-trims,
    ``clear_plan_caches`` clears) and reports — aggregated per ``name``
    across live instances — through ``plan_cache_info()``.  Eviction is
    always safe: entries are jit wrappers, pure functions of their shape
    key, so a rebuilt entry recompiles to bit-identical results.
    """

    def __init__(self, name: str):
        self.name = name
        self._data: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        _INSTANCE_CACHES.setdefault(name, weakref.WeakSet()).add(self)

    def get(self, key, make):
        """Cached value for ``key``, building (and possibly evicting) via
        ``make()`` on a miss."""
        if key in self._data:
            self._data.move_to_end(key)
            self.hits += 1
            return self._data[key]
        self.misses += 1
        val = make()
        self._data[key] = val
        self.trim()
        return val

    def trim(self) -> None:
        if PLAN_CACHE_MAXSIZE is None:
            return
        while len(self._data) > PLAN_CACHE_MAXSIZE:
            self._data.popitem(last=False)
            self.evictions += 1

    def clear(self) -> None:
        self._data.clear()

    def info(self) -> CacheInfo:
        return CacheInfo(self.hits, self.misses, PLAN_CACHE_MAXSIZE,
                         len(self._data), self.evictions)


def set_plan_cache_maxsize(maxsize: int | None) -> None:
    """Rebuild every registered plan cache with a new bound (None =
    unbounded).  Existing entries are dropped — safe, see above.  Live
    instance caches (``BoundedCache``) are re-trimmed to the new bound."""
    global PLAN_CACHE_MAXSIZE
    PLAN_CACHE_MAXSIZE = maxsize
    g = globals()
    for name, fn in _PLAN_CACHE_FNS.items():
        g[name] = _CountingLru(fn, maxsize)
    for caches in _INSTANCE_CACHES.values():
        for c in caches:
            c.trim()


def clear_plan_caches() -> None:
    """Drop every cached plan / kernel closure / shard_map wrapper (the
    serving-side pressure valve; results are unaffected)."""
    g = globals()
    for name in _PLAN_CACHE_FNS:
        g[name].cache_clear()
    for caches in _INSTANCE_CACHES.values():
        for c in caches:
            c.clear()


def plan_cache_info() -> dict:
    """{cache name: CacheInfo} for every registered cache — the module-level
    ``@plan_cache`` functions plus each live ``BoundedCache`` family
    (hits/misses/currsize/evictions summed over instances)."""
    g = globals()
    out = {name: g[name].cache_info() for name in _PLAN_CACHE_FNS}
    for name, caches in _INSTANCE_CACHES.items():
        infos = [c.info() for c in caches]
        out[name] = CacheInfo(sum(i.hits for i in infos),
                              sum(i.misses for i in infos),
                              PLAN_CACHE_MAXSIZE,
                              sum(i.currsize for i in infos),
                              sum(i.evictions for i in infos))
    return out


def _plan_cache_collector(reg) -> None:
    """Pull collector: publish ``plan_cache_info()`` as
    ``pathsig_plan_cache{cache=,stat=}`` gauges at snapshot time — the hot
    path never mirrors increments into the registry."""
    g = reg.gauge("pathsig_plan_cache",
                  "plan cache accounting (hits/misses/currsize/evictions "
                  "per cache family)", ("cache", "stat"))
    for name, ci in plan_cache_info().items():
        g.set(ci.hits, cache=name, stat="hits")
        g.set(ci.misses, cache=name, stat="misses")
        g.set(ci.currsize, cache=name, stat="currsize")
        g.set(ci.evictions, cache=name, stat="evictions")


obs.register_collector(_plan_cache_collector)


# ---------------------------------------------------------------------------
# dispatch observability: per-entry call counters + tracer spans
# ---------------------------------------------------------------------------

def _dispatch_calls():
    return obs.counter(
        "pathsig_dispatch_calls_total",
        "public dispatch entry calls; ctx distinguishes eager host calls "
        "from trace-time calls inside an outer jit",
        ("op", "backend", "ctx"))


def _obs_entry(fn):
    """Wrap a public dispatch entry with call accounting and a tracer span.

    Costs two flag checks when observability is fully off.  Inside an outer
    ``jit`` the wrapper runs at trace time only (the body is staged out), so
    counts are labelled ``ctx="trace"`` there — one tick per compiled
    variant — versus ``ctx="eager"`` per host-level call.
    """
    site = fn.__name__

    @functools.wraps(fn)
    def wrapper(x, *args, **kwargs):
        metrics_on = obs.REGISTRY._enabled
        trace_on = obs.TRACER._active
        if not metrics_on and not trace_on:
            return fn(x, *args, **kwargs)
        backend = str(kwargs.get("backend", "auto"))
        ctx = "trace" if isinstance(x, jax.core.Tracer) else "eager"
        if metrics_on:
            _dispatch_calls().inc(op=site, backend=backend, ctx=ctx)
        if not trace_on:
            return fn(x, *args, **kwargs)
        with obs.span(f"kernels.{site}", backend=backend, ctx=ctx,
                      shapes=obs.shape_key(x)):
            return fn(x, *args, **kwargs)

    wrapper.__wrapped__ = fn
    return wrapper


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str) -> tuple[str, bool]:
    """backend string -> (engine, interpret).  ``"pallas"`` never falls back:
    without a TPU it raises, so no run mistakes interpret mode for the
    compiled kernels."""
    if backend == "auto":
        return ("pallas", False) if _on_tpu() else ("jax", False)
    if backend == "pallas":
        if not _on_tpu():
            raise RuntimeError(
                "backend='pallas' compiles the kernels for a TPU, but JAX's "
                f"default backend is {jax.default_backend()!r}; use "
                "backend='pallas_interpret' to run the kernels in interpret "
                "mode, or 'jax' for the reference engine")
        return "pallas", False
    if backend == "pallas_interpret":
        return "pallas", True
    if backend == "jax":
        return "jax", False
    if backend == "hybrid":
        return "hybrid", False
    raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")


_resolve = resolve_backend  # back-compat alias


def _tuned(engine: str, interpret: bool) -> str:
    """The backend string an autotune cell is keyed and timed under."""
    return "pallas_interpret" if interpret else engine


def _check_backward(backward: str) -> None:
    if backward not in BACKWARDS:
        raise ValueError(
            f"unknown backward mode {backward!r}; expected one of {BACKWARDS}")


# ---------------------------------------------------------------------------
# truncated signatures: Pallas forwards, §4.2 custom VJPs
# ---------------------------------------------------------------------------

@plan_cache
def _pallas_sig_inverse(depth: int, batch_tile: int, split: int | None,
                        interpret: bool, precision: str = "fp32"):
    """Kernel forward + inverse-reconstruction backward (paper §4.2).

    ``precision`` only selects the kernel's storage dtype: the dispatch layer
    quantises the increments BEFORE they reach any custom VJP, so forward and
    backward sweeps see identical (already-rounded) values."""
    def kernel(increments):
        return sig_trunc(increments, depth, batch_tile=batch_tile,
                         split=split, interpret=interpret,
                         precision=precision)

    def sig(increments):
        return kernel(increments)

    def fwd(increments):
        out = kernel(increments)
        return out, (increments, out)

    def bwd(res, g_flat):
        increments, out_flat = res
        return (_trunc_inverse_bwd(increments, out_flat, g_flat, depth,
                                   batch_tile, interpret),)

    return scoped_vjp(sig, fwd, bwd)


def _trunc_inverse_bwd(increments, out_flat, g_flat, depth: int,
                       batch_tile: int, interpret: bool) -> jax.Array:
    """The truncated §4.2 backward of the Pallas row: the reverse-scan
    kernel, or the XLA scan where S and G do not fit VMEM."""
    B, M, d = increments.shape
    kernel = sig_trunc_bwd.fits(B, M, d, depth, batch_tile)
    obs.counter("pathsig_inverse_bwd_engine_total",
                "traced truncated inverse backwards, by engine (kernel: "
                "the Pallas reverse scan; scan: the XLA fallback where "
                "its blocks do not fit VMEM)", ("engine",)).inc(
        engine="kernel" if kernel else "scan")
    if not kernel:
        return inverse_bwd_scan(increments, out_flat, g_flat, depth)
    with jax.named_scope(COTANGENT_SCOPE):  # reconstruct fused inside
        return sig_trunc_bwd.sig_trunc_inverse_bwd(
            increments, out_flat, g_flat, depth, interpret=interpret,
            batch_tile=batch_tile)


@plan_cache
def _pallas_sig_checkpoint(depth: int, chunk: int, batch_tile: int,
                           split: int | None, interpret: bool,
                           precision: str = "fp32"):
    """Kernel chunk forward + √M-checkpoint backward.

    Forward: fold √M-length time chunks into the batch axis, run the Pallas
    kernel once over all chunks, Chen-combine the chunk signatures in a scan
    whose carry traces out exactly the boundary states the backward needs.
    Backward: the shared chunk-replay sweep from ``repro.core.signature``.
    """
    def kernel(increments):
        return sig_trunc(increments, depth, batch_tile=batch_tile,
                         split=split, interpret=interpret,
                         precision=precision)

    def sig(increments):
        out, _ = _forward(increments)
        return out

    def _forward(increments):
        B, M, d = increments.shape
        n_chunks = -(-M // chunk)
        pad = n_chunks * chunk - M
        x = jnp.pad(increments, ((0, 0), (0, pad), (0, 0)))  # zero = identity
        folded = x.reshape(B, n_chunks, chunk, d).reshape(B * n_chunks,
                                                          chunk, d)
        chunk_flat = kernel(folded)                         # (B*C, D_sig)
        chunk_lv = tops.flat_to_levels(chunk_flat, d, depth)
        # -> time-major levels: each (n_chunks, B, d**n)
        chunk_lv = [jnp.moveaxis(a.reshape(B, n_chunks, -1), 1, 0)
                    for a in chunk_lv]

        def combine(levels, c_lv):
            new = tops.chen_mul(levels, c_lv)
            return new, [lv for lv in levels]  # boundary BEFORE the chunk

        init = tops.zero_levels((B,), d, depth, chunk_flat.dtype)
        final, boundaries = jax.lax.scan(combine, init, chunk_lv)
        return tops.levels_to_flat(final), boundaries

    def fwd(increments):
        out, boundaries = _forward(increments)
        return out, (increments, boundaries)

    def bwd(res, g_flat):
        increments, boundaries = res
        return (checkpoint_bwd_scan(increments, boundaries, g_flat, depth,
                                    chunk),)

    return scoped_vjp(sig, fwd, bwd)


@plan_cache
def _pallas_sig_stream(depth: int, stride: int, batch_tile: int,
                       split: int | None, interpret: bool,
                       precision: str = "fp32"):
    """Streamed kernel forward + generalised §4.2 backward: cotangents arrive
    at every emitted step, one reverse scan, O(B·D_sig) live memory."""
    def kernel(increments):
        return sig_trunc(increments, depth, batch_tile=batch_tile,
                         split=split, interpret=interpret, stream=True,
                         stream_stride=stride, precision=precision)

    def sig(increments):
        return kernel(increments)

    def fwd(increments):
        out = kernel(increments)
        return out, (increments, out[:, -1])  # terminal step always emitted

    def bwd(res, g_steps):
        increments, terminal = res
        return (stream_inverse_bwd_scan(increments, terminal, g_steps, depth,
                                        stride),)

    return scoped_vjp(sig, fwd, bwd)


# ---------------------------------------------------------------------------
# fused-transform cells: raw increments + time-aux in, augmented signature
# out.  The transform never materialises on the forward — the kernel builds
# each augmented increment in VMEM per Horner sub-step.  The backward
# transiently materialises the augmented increments ONCE (O(B·M_aug·d_aug),
# freed after the sweep), reuses the standard §4.2 reconstruction over them,
# then pulls the cotangent back through the transform's linear adjoint.
# ``taux`` ((B, 2) = [dt, n_valid_aug]) is data-independent: its cotangent
# is identically zero.
# ---------------------------------------------------------------------------

@plan_cache
def _pallas_sig_fused_inverse(depth: int, batch_tile: int, split: int | None,
                              interpret: bool, kspec, precision: str):
    """Fused-transform kernel forward + §4.2 backward through the transform
    adjoint.  ``kspec`` is a basepoint-free Transform (basepoint is handled
    as an increment prepend in the dispatch, outside the custom VJP, so x0
    gradients ride the concat's transpose)."""
    def kernel(increments, taux):
        return sig_trunc(increments, depth, batch_tile=batch_tile,
                         split=split, interpret=interpret, transform=kspec,
                         taux=taux, precision=precision)

    def sig(increments, taux):
        return kernel(increments, taux)

    def fwd(increments, taux):
        out = kernel(increments, taux)
        return out, (increments, taux, out)

    def bwd(res, g_flat):
        increments, taux, out_flat = res
        e = fused_augment(increments, taux, kspec)
        g_e = _trunc_inverse_bwd(e, out_flat, g_flat, depth, batch_tile,
                                 interpret)
        return (fused_adjoint(g_e, kspec, increments.shape[-1]),
                jnp.zeros_like(taux))

    return scoped_vjp(sig, fwd, bwd)


@plan_cache
def _pallas_sig_fused_stream(depth: int, stride: int, batch_tile: int,
                             split: int | None, interpret: bool, kspec,
                             precision: str):
    """Fused-transform streamed forward + streamed §4.2 backward through the
    transform adjoint (strides and emissions are over the AUGMENTED step
    axis, matching the kernel's in-VMEM sub-steps)."""
    def kernel(increments, taux):
        return sig_trunc(increments, depth, batch_tile=batch_tile,
                         split=split, interpret=interpret, stream=True,
                         stream_stride=stride, transform=kspec, taux=taux,
                         precision=precision)

    def sig(increments, taux):
        return kernel(increments, taux)

    def fwd(increments, taux):
        out = kernel(increments, taux)
        return out, (increments, taux, out[:, -1])

    def bwd(res, g_steps):
        increments, taux, terminal = res
        e = fused_augment(increments, taux, kspec)
        g_e = stream_inverse_bwd_scan(e, terminal, g_steps, depth, stride)
        return (fused_adjoint(g_e, kspec, increments.shape[-1]),
                jnp.zeros_like(taux))

    return scoped_vjp(sig, fwd, bwd)


# ---------------------------------------------------------------------------
# plan normalisation + caches — keyed by plan CONTENT (d, words), never by
# WordPlan/TiledPlan object identity, so rebuilding an identical plan hits
# the same compiled kernels instead of recompiling and growing the caches
# ---------------------------------------------------------------------------

@plan_cache
def _plan_for_words(words: tuple, d: int) -> WordPlan:
    """The interned WordPlan for a word set: one canonical object per
    (words, d) content, shared by every jit/lru cache downstream."""
    return make_plan(words, d)


@plan_cache
def _tiled_for_words(words: tuple, d: int, max_rows: int) -> TiledPlan:
    """The interned TiledPlan — content-keyed for the same reason (TiledPlan
    hashes by identity, and ``sig_words`` jit-caches on the plan object)."""
    return make_tiled_plan(words, d, max_rows=max_rows)


@plan_cache
def _closure_tiled_plan(words: tuple, d: int, max_rows: int) -> TiledPlan:
    """Tiled plan whose *requested* words are the prefix closure of the word
    set — the kernel computes the closure rows anyway, so asking for them adds
    output gather only, and the terminal closure state is what the §4.2
    backward reconstructs from."""
    wplan = _plan_for_words(words, d)
    return make_tiled_plan(wplan.closure, d, max_rows=max_rows)


def _normalise_plans(plan, d: int) -> tuple[WordPlan, TiledPlan | None]:
    """-> (interned WordPlan, TiledPlan-or-None) from any accepted plan
    spelling.  The WordPlan is always the canonical content-interned object,
    so two structurally equal plans resolve to the same kernel caches."""
    if isinstance(plan, TiledPlan):
        return _plan_for_words(plan.words, plan.d), plan
    if isinstance(plan, WordPlan):
        return _plan_for_words(plan.words, plan.d), None
    return _plan_for_words(tuple(tuple(w) for w in plan), d), None


# ---------------------------------------------------------------------------
# projected signatures: Pallas closure forward, §4.2 custom VJP
# ---------------------------------------------------------------------------

@plan_cache
def _pallas_proj_inverse(words: tuple, d: int, batch_tile: int, max_rows: int,
                         interpret: bool, precision: str = "fp32"):
    """Word-kernel forward over the prefix closure + §4.2 backward.
    Content-keyed: (words, d) identify the plan, not object identity."""
    wplan = _plan_for_words(words, d)
    closure_tplan = _closure_tiled_plan(words, d, max_rows)
    out_rows = np.asarray(wplan.out_rows)

    def closure_state(increments):
        cw = sig_words(increments, closure_tplan, batch_tile=batch_tile,
                       interpret=interpret,
                       precision=precision)               # (B, W), closure order
        ones = jnp.ones((cw.shape[0], 1), cw.dtype)
        return jnp.concatenate([ones, cw], axis=1)        # (B, 1 + W)

    def proj(increments):
        return jnp.take(closure_state(increments), out_rows, axis=1)

    def fwd(increments):
        S_T = closure_state(increments)
        return jnp.take(S_T, out_rows, axis=1), (increments, S_T)

    def bwd(res, g_out):
        increments, S_T = res
        return (projected_inverse_bwd_scan(increments, S_T, g_out, wplan),)

    return scoped_vjp(proj, fwd, bwd)


@plan_cache
def _pallas_proj_stream(words: tuple, d: int, stride: int, batch_tile: int,
                        max_rows: int, interpret: bool,
                        precision: str = "fp32"):
    """Streamed word-kernel forward over the prefix closure + streamed §4.2
    backward (cotangents at every emitted step, one reverse scan)."""
    wplan = _plan_for_words(words, d)
    closure_tplan = _closure_tiled_plan(words, d, max_rows)
    out_rows = np.asarray(wplan.out_rows)

    def closure_stream(increments):
        cw = sig_words(increments, closure_tplan, batch_tile=batch_tile,
                       interpret=interpret, stream=True,
                       stream_stride=stride,
                       precision=precision)          # (B, M_out, W)
        ones = jnp.ones((*cw.shape[:2], 1), cw.dtype)
        return jnp.concatenate([ones, cw], axis=-1)  # (B, M_out, 1 + W)

    def proj(increments):
        return jnp.take(closure_stream(increments), out_rows, axis=-1)

    def fwd(increments):
        S = closure_stream(increments)
        # terminal closure state is the last emitted step — the only residual
        return jnp.take(S, out_rows, axis=-1), (increments, S[:, -1])

    def bwd(res, g_steps):
        increments, S_T = res
        return (projected_stream_inverse_bwd_scan(increments, S_T, g_steps,
                                                  wplan, stride),)

    return scoped_vjp(proj, fwd, bwd)


@plan_cache
def _pallas_proj_fused_inverse(words: tuple, d: int, batch_tile: int,
                               max_rows: int, interpret: bool, kspec,
                               precision: str):
    """Fused-transform word-kernel forward + §4.2 backward through the
    transform adjoint.  ``words``/``d`` describe the plan over the AUGMENTED
    alphabet (d == transform_dim(kspec, d_raw)); the backward materialises
    the augmented increments once, runs the standard projected sweep, then
    applies the transform's linear adjoint."""
    wplan = _plan_for_words(words, d)
    closure_tplan = _closure_tiled_plan(words, d, max_rows)
    out_rows = np.asarray(wplan.out_rows)

    def closure_state(increments, taux):
        cw = sig_words(increments, closure_tplan, batch_tile=batch_tile,
                       interpret=interpret, transform=kspec, taux=taux,
                       precision=precision)
        ones = jnp.ones((cw.shape[0], 1), cw.dtype)
        return jnp.concatenate([ones, cw], axis=1)

    def proj(increments, taux):
        return jnp.take(closure_state(increments, taux), out_rows, axis=1)

    def fwd(increments, taux):
        S_T = closure_state(increments, taux)
        return jnp.take(S_T, out_rows, axis=1), (increments, taux, S_T)

    def bwd(res, g_out):
        increments, taux, S_T = res
        e = fused_augment(increments, taux, kspec)
        g_e = projected_inverse_bwd_scan(e, S_T, g_out, wplan)
        return (fused_adjoint(g_e, kspec, increments.shape[-1]),
                jnp.zeros_like(taux))

    return scoped_vjp(proj, fwd, bwd)


@plan_cache
def _pallas_proj_fused_stream(words: tuple, d: int, stride: int,
                              batch_tile: int, max_rows: int, interpret: bool,
                              kspec, precision: str):
    """Fused-transform streamed word-kernel forward + streamed §4.2 backward
    through the transform adjoint (emissions stride the augmented axis)."""
    wplan = _plan_for_words(words, d)
    closure_tplan = _closure_tiled_plan(words, d, max_rows)
    out_rows = np.asarray(wplan.out_rows)

    def closure_stream(increments, taux):
        cw = sig_words(increments, closure_tplan, batch_tile=batch_tile,
                       interpret=interpret, stream=True, stream_stride=stride,
                       transform=kspec, taux=taux, precision=precision)
        ones = jnp.ones((*cw.shape[:2], 1), cw.dtype)
        return jnp.concatenate([ones, cw], axis=-1)

    def proj(increments, taux):
        return jnp.take(closure_stream(increments, taux), out_rows, axis=-1)

    def fwd(increments, taux):
        S = closure_stream(increments, taux)
        return jnp.take(S, out_rows, axis=-1), (increments, taux, S[:, -1])

    def bwd(res, g_steps):
        increments, taux, S_T = res
        e = fused_augment(increments, taux, kspec)
        g_e = projected_stream_inverse_bwd_scan(e, S_T, g_steps, wplan,
                                                stride)
        return (fused_adjoint(g_e, kspec, increments.shape[-1]),
                jnp.zeros_like(taux))

    return scoped_vjp(proj, fwd, bwd)


# ---------------------------------------------------------------------------
# dense route: a projected set's coordinates are coordinates of the truncated
# signature to the set's depth, so where that tensor is small next to the
# set's prefix closure the truncated kernels (cone forward, §4.2 reverse-scan
# kernel) compute it and the words are gathered from it.  A closure row's
# Horner chain reads only its own prefixes, and the rows outside the closure
# carry a zero cotangent, so both engines compute the same values and
# gradients up to float32 rounding.
# ---------------------------------------------------------------------------

# The rule's row ratio: the dense route takes a set whose truncated tensor,
# in the kernels' 8-row-aligned rows, has at most this many rows per row of
# the set's prefix closure.  On a TPU v5e at the §8 shape a training step's
# projected signature costs 105× as much per closure row on the word engine
# as per row on the truncated kernels (PERF.md §6); half of that is 52, and
# 32 leaves room for shapes whose per-row costs differ.
DENSE_ROWS_PER_CLOSURE_ROW = 32


def _dense_tiling(wplan: WordPlan, *, engine: str, interpret: bool,
                  backward: str, stream: bool, batch_tile: int | None,
                  B: int, M: int, precision: str):
    """-> the truncated kernels' ``(batch_tile, split)`` where the dense
    route may take a projected call, else None.  It may for a non-streamed
    ``inverse`` call of the Pallas row whose truncated tensor has at most
    :data:`DENSE_ROWS_PER_CLOSURE_ROW` rows per closure row.  The tiling is
    what :func:`signature` takes for the same shape (``M`` counts augmented
    steps); an explicit ``batch_tile`` wins."""
    if engine != "pallas" or stream or backward != "inverse":
        return None
    rows = int(level_offsets(wplan.d, wplan.depth)[-1])
    if rows > DENSE_ROWS_PER_CLOSURE_ROW * wplan.closure_size:
        return None
    if batch_tile is not None:
        return batch_tile, None
    hit = autotune.lookup("sig_trunc", engine=_tuned(engine, interpret),
                          d=wplan.d, depth=wplan.depth, M=M, B=B,
                          precision=precision)
    return hit.get("batch_tile", 128), hit.get("split")


def _trunc_kernels_fit(B: int, M: int, d_raw: int, depth: int,
                       batch_tile: int, split: int | None, precision: str,
                       kspec=None) -> bool:
    """Whether both truncated kernels take the signature of (B, M, d_raw)
    increments, ``kspec`` fused: the cone forward's split (``sig_trunc``'s
    own choice when ``split`` is None) within the VMEM limit, and the §4.2
    backward on its kernel rather than the XLA scan."""
    d = transform_dim(kspec, d_raw) if kspec else d_raw
    tile, B_pad = lane_tile(B, batch_tile)
    itemsize = 2 if precision == "bf16_fp32" else 4
    T, n_t = time_chunk(M, 2 * block_bytes((1, d_raw, tile), itemsize))

    def need(s):
        steps = B_pad // tile * d ** s * n_t
        return vmem_footprint(T, d_raw, d, depth, s, tile,
                              in_itemsize=itemsize,
                              buffers=1 if steps == 1 else 2)

    s = split
    if s is None:
        s = choose_split(d, depth, tile)
        while s < depth - 1 and need(s) > VMEM_LIMIT_BYTES:
            s += 1
    M_aug = M * kspec.sub_steps if kspec else M
    return (0 <= s < depth and need(s) <= VMEM_LIMIT_BYTES
            and sig_trunc_bwd.fits(B, M_aug, d, depth, batch_tile))


def _takes_dense(wplan: WordPlan, increments: jax.Array, dense,
                 precision: str, kspec=None) -> bool:
    """The engine of one traced projected inverse call of the Pallas row:
    the dense route where :func:`_dense_tiling` offered one and both
    truncated kernels take the shape, else the word engine.  Ticks
    ``pathsig_projected_engine_total`` by the engine picked."""
    B, M, d_raw = increments.shape
    ok = dense is not None and _trunc_kernels_fit(
        B, M, d_raw, wplan.depth, *dense, precision, kspec)
    obs.counter("pathsig_projected_engine_total",
                "traced projected inverse calls of the Pallas row, by "
                "engine (dense: the truncated kernels and a gather; words: "
                "the word kernel and the projected scan)", ("engine",)).inc(
        engine="dense" if ok else "words")
    return ok


@plan_cache
def _dense_index(words: tuple, d: int) -> np.ndarray:
    """Flat indices of a word set's coordinates in its truncated signature."""
    return np.asarray([flat_index(w, d) for w in words], np.int32)


def _dense_words(sig_flat: jax.Array, wplan: WordPlan) -> jax.Array:
    """(B, D_sig) -> (B, |I|): the set's coordinates, in its order."""
    idx = _dense_index(wplan.words, wplan.d)
    with jax.named_scope(PROJ_INDEX_SCOPE):
        return jnp.take(sig_flat, idx, axis=1,
                        unique_indices=len(set(idx.tolist())) == idx.size)


# ---------------------------------------------------------------------------
# hybrid engine: dense W_{<=N-1} + per-word top chains (repro.core.hybrid)
# ---------------------------------------------------------------------------

@plan_cache
def _hybrid_gather(words: tuple, d: int):
    """-> (top_words, out_idx): the level-N words the hybrid engine must chain
    explicitly, and the gather from its [dense W_{<=N-1} ++ top] buffer back
    to the requested word order."""
    wplan = _plan_for_words(words, d)
    depth = wplan.depth
    top: list = []
    seen: set = set()
    for w in wplan.words:
        if len(w) == depth and w not in seen:
            seen.add(w)
            top.append(w)
    top_pos = {w: i for i, w in enumerate(top)}
    lown = sig_dim(d, depth - 1)
    idx = [lown + top_pos[w] if len(w) == depth else flat_index(w, d)
           for w in wplan.words]
    return tuple(top), np.asarray(idx, dtype=np.int32)


def _hybrid_projected(increments: jax.Array, wplan: WordPlan,
                      backward: str) -> jax.Array:
    """Projected signature through the hybrid dense+word-table engine: the
    dense levelwise-Horner scan covers every level below the set's max level
    (gather/scatter-free), per-word chains cover only the top-level words,
    and the requested coordinates are gathered from the combined buffer.
    Worth it exactly when the set is dense at low levels (the §3.3 shape)."""
    if wplan.depth < 2:
        # no dense block below level 1: the word-table engine IS the limit
        return projected_signature_from_increments(
            increments, wplan, backward=backward, backend="jax")
    from repro.core.hybrid import hybrid_low_plus_top
    top, idx = _hybrid_gather(wplan.words, wplan.d)
    buf = hybrid_low_plus_top(increments, top, wplan.depth, backward=backward)
    return jnp.take(buf, jnp.asarray(idx), axis=1)


# ---------------------------------------------------------------------------
# mesh-aware SPMD path: an installed sharding_ctx(mesh) whose rules map the
# "batch" logical axis onto >= 2 devices turns EVERY dispatch cell into a
# shard_map over that axis — the same engines run per shard with per-shard
# custom-VJP closures (signatures are batch-elementwise, so gradients shard
# identically), the batch is zero-padded up to a multiple of the axis size
# (zero increments are identity updates; padded rows are sliced off, so their
# cotangents are exactly zero), and outside any context every entry point is
# bit-identical to the single-device path (the mesh branch is never taken).
# ---------------------------------------------------------------------------


def _mesh_batch():
    """-> (mesh, batch axis names, axis size) when the current sharding
    context shards the "batch" logical axis over >= 2 devices, else None."""
    mesh = current_mesh()
    if mesh is None:
        return None
    names = logical_axes("batch")
    size = 1
    for a in names:
        size *= mesh.shape[a]
    if size <= 1:
        return None
    return mesh, names, size


def _axis_arg(names: tuple):
    """Axis-name argument for PartitionSpec / collectives: a bare name for
    1 axis, the tuple for several (treated as one flattened axis)."""
    return names if len(names) > 1 else names[0]


def _pad_rows(x: jax.Array, size: int) -> jax.Array:
    """Zero-pad dim 0 up to a multiple of ``size``."""
    pad = -x.shape[0] % size
    if not pad:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


def _apply_sharded(fn, size: int, increments: jax.Array, lengths):
    """Pad the batch to a multiple of the axis size, run the shard_map'd
    ``fn``, slice the padding back off (its transpose zero-fills, so padded
    rows contribute exactly zero cotangent)."""
    B = increments.shape[0]
    incs = _pad_rows(increments, size)
    if lengths is None:
        out = fn(incs)
    else:
        out = fn(incs, _pad_rows(lengths, size))
    return out[:B] if incs.shape[0] != B else out


def _shard_wrap(mesh, names: tuple, with_lengths: bool, local_fn, *,
                site: str):
    """Wrap ``local_fn(increments, lengths_or_None)`` in shard_map with every
    argument batch-sharded on dim 0.  The body is the single-device dispatch,
    so the custom-VJP closure is rebuilt per shard and gradients shard
    identically to the primals.  ``check_vma=False``: pallas_call has no
    replication rule.

    The wrapper is jitted (with retrace accounting under ``site``): the
    plan cache pins one wrapper per (mesh, cell) and jit's cache pins one
    trace per argument shape, so repeated mesh calls with the same
    (op, cell, shape) re-dispatch a compiled executable instead of
    re-tracing the per-shard custom-VJP closures every call."""
    spec = PartitionSpec(_axis_arg(names))
    if with_lengths:
        def body(incs, lens):
            return local_fn(incs, lens)
        in_specs = (spec, spec)
    else:
        def body(incs):
            return local_fn(incs, None)
        in_specs = (spec,)
    return obs.instrument_jit(
        jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=spec,
                      check_vma=False), site=site)


@plan_cache
def _sharded_sig(mesh, names: tuple, with_lengths: bool, depth: int,
                 engine: str, interpret: bool, backward: str, batch_tile: int,
                 split: int | None, time_chunks: int, stream: bool,
                 stream_stride: int, precision: str = "fp32"):
    """shard_map wrapper around the truncated-signature cell.  Transforms
    are materialised BEFORE the mesh branch (support matrix), so the shard
    body only needs the precision knob."""
    return _shard_wrap(mesh, names, with_lengths, partial(
        _signature_local, depth=depth, engine=engine, interpret=interpret,
        backward=backward, batch_tile=batch_tile, split=split,
        time_chunks=time_chunks, stream=stream,
        stream_stride=stream_stride, precision=precision),
        site="sharded_sig")


@plan_cache
def _sharded_proj(mesh, names: tuple, with_lengths: bool, words: tuple,
                  d: int, engine: str, interpret: bool, backward: str,
                  batch_tile: int, max_rows: int, stream: bool,
                  stream_stride: int, precision: str = "fp32", dense=None):
    """shard_map wrapper around the projected-signature cell (incl. the
    hybrid engine).  Transforms are materialised before the mesh branch."""
    return _shard_wrap(mesh, names, with_lengths, partial(
        _projected_local, words=words, d=d, engine=engine,
        interpret=interpret, backward=backward, batch_tile=batch_tile,
        max_rows=max_rows, stream=stream, stream_stride=stream_stride,
        precision=precision, dense=dense), site="sharded_proj")


@plan_cache
def _sharded_proj_fwd(mesh, names: tuple, with_lengths: bool, words: tuple,
                      d: int, engine: str, interpret: bool, batch_tile: int,
                      max_rows: int, precision: str = "fp32"):
    """shard_map wrapper around :func:`projected_forward_only`'s body."""
    return _shard_wrap(mesh, names, with_lengths, partial(
        _projected_fwd_local, words=words, d=d, engine=engine,
        interpret=interpret, batch_tile=batch_tile, max_rows=max_rows,
        precision=precision), site="sharded_proj_fwd")


# ---------------------------------------------------------------------------
# weighted Gram product: word-blocked routes + closed-form product VJP
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("block",))
def _gram_blocked_jax(Sx: jax.Array, Sy: jax.Array, w: jax.Array,
                      block: int) -> jax.Array:
    """G = S_x diag(w) S_yᵀ via a fori-loop over word blocks: live state is
    the (B_x, B_y) accumulator plus one (B, block) slab per operand — the
    (B_x, B_y, D) elementwise intermediate is never formed."""
    Bx, D = Sx.shape
    By = Sy.shape[0]
    blk = min(block, D)
    n = -(-D // blk)
    dt = jnp.promote_types(Sx.dtype, jnp.float32)
    if n == 1:
        # single-block fast path: one dot, no fori_loop — keeps the lowered
        # HLO loop-free so the gram ring's tile dots stay visible to the
        # scheduler (and to ring_overlap's permute/compute analysis)
        return (Sx.astype(dt) * w.astype(dt)[None, :]) @ Sy.astype(dt).T
    pad = n * blk - D
    if pad:  # zero-padded weights make the padded columns exact no-ops
        Sx = jnp.pad(Sx, ((0, 0), (0, pad)))
        Sy = jnp.pad(Sy, ((0, 0), (0, pad)))
        w = jnp.pad(w, (0, pad))

    def body(i, acc):
        sx = jax.lax.dynamic_slice(Sx, (0, i * blk), (Bx, blk)).astype(dt)
        sy = jax.lax.dynamic_slice(Sy, (0, i * blk), (By, blk)).astype(dt)
        wb = jax.lax.dynamic_slice(w, (i * blk,), (blk,)).astype(dt)
        return acc + (sx * wb[None, :]) @ sy.T

    return jax.lax.fori_loop(0, n, body, jnp.zeros((Bx, By), dt))


@plan_cache
def _gram_vjp(engine: str, interpret: bool, block_words: int, bx_tile: int,
              by_tile: int):
    def forward(Sx, Sy, w):
        if engine == "pallas":
            return sig_gram_tiles(Sx, Sy, w, bx_tile=bx_tile, by_tile=by_tile,
                                  k_tile=block_words, interpret=interpret)
        return _gram_blocked_jax(Sx, Sy, w, block_words)

    def gram_fn(Sx, Sy, w):
        return forward(Sx, Sy, w)

    def fwd(Sx, Sy, w):
        return forward(Sx, Sy, w), (Sx, Sy, w)

    def bwd(res, g):
        # G_ij = Σ_k Sx_ik w_k Sy_jk: products of (B, D) mats only — the
        # backward obeys the same no-(B_x, B_y, D)-intermediate law.
        Sx, Sy, w = res
        g = g.astype(jnp.promote_types(Sx.dtype, jnp.float32))
        dSx = (g @ (Sy * w[None, :])).astype(Sx.dtype)
        dSy = (g.T @ (Sx * w[None, :])).astype(Sy.dtype)
        dw = ((g.T @ Sx) * Sy).sum(axis=0).astype(w.dtype)
        return dSx, dSy, dw

    return scoped_vjp(gram_fn, fwd, bwd)


@plan_cache
def _gram_ring(mesh, names: tuple, size: int, engine: str, interpret: bool,
               block_words: int, bx_tile: int, by_tile: int):
    """Cross-device Gram: X rows stay local, Y signature tiles rotate around
    the mesh axis in a ``jax.lax.ppermute`` ring.

    Both operands are batch-sharded; device p computes the (B_x/P, B_y/P)
    tile against whichever Y shard it currently holds, writes it into its
    output row block at the shard's *origin* columns, and passes the shard to
    its left neighbour — P steps visit every tile.  Per-device communication
    is (P-1)/P · B_y · D bytes (O(B·D_sig) in total), live memory is one Y
    shard + one in-flight shard + the local (B_x/P, B_y) row block, and no
    collective ever carries a replicated Gram-sized or (B_x, B_y, D_sig)
    intermediate — asserted via
    :func:`repro.distributed.hlo.collective_stats` in the shard tests.

    The ring is double-buffered: the loop is statically unrolled (size <= 8
    in practice) and each step issues the ppermute for the NEXT shard into
    a second buffer *before* consuming the current one, so the permute has
    no data dependence on the tile dot and the scheduler can hide step
    k+1's wire time under step k's matmul.  Only size-1 permutes are issued
    (the last held shard is consumed, not forwarded).  The carry buffers
    alias in place across steps (XLA reuses the consumed shard's buffer for
    the incoming one — the loop-free form is what makes that legal), and
    the overlap structure is asserted on the lowered HLO via
    :func:`repro.distributed.hlo.ring_overlap` in the shard tests.
    Differentiable: each tile rides the closed-form product VJP and the
    ring transposes to the reversed ring.
    """
    local = _gram_vjp(engine, interpret, block_words, bx_tile, by_tile)
    ax = _axis_arg(names)
    spec = PartitionSpec(ax)
    perm = [(i, (i - 1) % size) for i in range(size)]

    def body(sx, sy, w):
        p = jax.lax.axis_index(ax)
        by = sy.shape[0]
        dt = jnp.promote_types(sx.dtype, jnp.float32)
        G = jnp.zeros((sx.shape[0], by * size), dt)
        sy_cur = sy
        for s in range(size):
            sy_next = None
            if s + 1 < size:  # prefetch before the dot: no data dependence
                sy_next = jax.lax.ppermute(sy_cur, ax, perm)
            tile = local(sx, sy_cur, w).astype(dt)
            # (p + s) % size: origin device of the currently held shard
            G = jax.lax.dynamic_update_slice(G, tile,
                                             (0, ((p + s) % size) * by))
            if sy_next is not None:
                sy_cur = sy_next
        return G

    return obs.instrument_jit(
        jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, PartitionSpec()),
                      out_specs=spec, check_vma=False), site="gram_ring")


@_obs_entry
def gram(Sx: jax.Array, Sy: jax.Array, weights: jax.Array, *,
         backend: str = "auto", block_words: int | None = None,
         bx_tile: int | None = None, by_tile: int | None = None,
         precision: str = "fp32") -> jax.Array:
    """Weighted signature Gram product (B_x, D), (B_y, D), (D,) -> (B_x, B_y).

    The tiled route of the signature kernel k_ω(x, y) = S_x diag(ω) S_yᵀ:
    blocked over the word axis (``block_words`` coordinates at a time) so the
    (B_x, B_y, D) elementwise intermediate is never materialised, on every
    backend (see the support-matrix note in the module docstring).
    Differentiable in all three operands via the closed-form product VJP —
    gradients flow into learned signatures AND learned weights.

    Under an installed ``sharding_ctx(mesh)`` that shards the "batch"
    logical axis, the product runs as the cross-device ring of
    :func:`_gram_ring`: (B_x/P, B_y/P) tiles, O(B·D_sig) communication,
    never a replicated (B_x, B_y) or (B_x, B_y, D_sig) intermediate.  Both
    operands are padded up to a multiple of the axis size with zero rows
    (exact: zero rows / columns are sliced back off).
    """
    engine, interpret = resolve_backend(backend)
    precision = canon_precision(precision)
    if engine == "hybrid":  # the gram product has no dense/word split
        engine, interpret = "jax", False
    if Sx.ndim != 2 or Sy.ndim != 2 or Sy.shape[1] != Sx.shape[1] \
            or weights.shape != (Sx.shape[1],):
        raise ValueError(
            f"gram needs Sx (B_x, D), Sy (B_y, D), weights (D,); got "
            f"{Sx.shape}, {Sy.shape}, {weights.shape}")
    mb = _mesh_batch()
    if block_words is None or bx_tile is None or by_tile is None:
        if mb is not None:
            # under the mesh the tiles the local product actually sees are
            # the per-shard ones — key the autotune cell on those (and on P:
            # the ring's step count changes the profitable block size)
            P = mb[2]
            hit = autotune.lookup("gram_ring",
                                  engine=_tuned(engine, interpret),
                                  D=Sx.shape[1], Bx=-(-Sx.shape[0] // P),
                                  By=-(-Sy.shape[0] // P), P=P,
                                  precision=precision)
        else:
            hit = autotune.lookup("gram", engine=_tuned(engine, interpret),
                                  D=Sx.shape[1], Bx=Sx.shape[0],
                                  By=Sy.shape[0], precision=precision)
        block_words = hit.get("block_words", 512) if block_words is None \
            else block_words
        bx_tile = hit.get("bx_tile", 128) if bx_tile is None else bx_tile
        by_tile = hit.get("by_tile", 128) if by_tile is None else by_tile
    if block_words < 1:
        raise ValueError(f"block_words must be >= 1, got {block_words}")
    # precision: rounding the signature operands IS the semantics (both
    # engines then accumulate the same values in fp32); the STE quantiser
    # keeps exact fp32 cotangents for the rounded forward.
    Sx = quantise_increments(Sx, precision)
    Sy = quantise_increments(Sy, precision)
    if mb is not None:
        mesh, names, size = mb
        ring = _gram_ring(mesh, names, size, engine, interpret, block_words,
                          bx_tile, by_tile)
        Bx, By = Sx.shape[0], Sy.shape[0]
        if obs.REGISTRY._enabled:
            # analytic ring accounting: size-1 unrolled permute steps (the
            # last held shard is consumed, not forwarded), each one ppermute
            # of the local (By/size, D) Y shard — published at dispatch so
            # the ring-vs-oracle anomaly is a counter, not a benchmark-only
            # artefact (HLO-derived numbers ride obs.record_collectives
            # where a lowered module is at hand).
            By_pad = -(-By // size) * size
            shard_bytes = (By_pad // size) * Sy.shape[1] * Sy.dtype.itemsize
            obs.counter("pathsig_ring_ppermute_total",
                        "ppermute steps issued by the gram ring",
                        ("ctx",)).inc(
                size - 1, ctx="trace" if isinstance(Sx, jax.core.Tracer)
                else "eager")
            obs.counter("pathsig_ring_wire_bytes_total",
                        "analytic wire bytes moved by gram-ring ppermutes "
                        "(per device)", ("ctx",)).inc(
                (size - 1) * shard_bytes,
                ctx="trace" if isinstance(Sx, jax.core.Tracer) else "eager")
        with obs.span("kernels.gram_ring", devices=size,
                      shapes=obs.shape_key(Sx, Sy)):
            G = ring(_pad_rows(Sx, size), _pad_rows(Sy, size), weights)
        if G.shape != (Bx, By):
            G = G[:Bx, :By]
        return shard_constraint(G, "batch", None)
    return _gram_vjp(engine, interpret, block_words, bx_tile,
                     by_tile)(Sx, Sy, weights)


# ---------------------------------------------------------------------------
# public dispatch
# ---------------------------------------------------------------------------

def _mask_stream_out(out: jax.Array, M: int, stride: int,
                     lengths) -> jax.Array:
    """Zero a streamed output (B, M_out, D) after each example's true-
    terminal slot.  No-op without lengths (or with no emissions)."""
    if lengths is None or out.shape[1] == 0:
        return out
    return out * stream_emit_mask(M, stride, lengths)[..., None].astype(
        out.dtype)


def _signature_local(increments: jax.Array, lengths, *, depth: int,
                     engine: str, interpret: bool, backward: str,
                     batch_tile: int, split: int | None, time_chunks: int,
                     stream: bool, stream_stride: int, transform=None,
                     x0=None, precision: str = "fp32") -> jax.Array:
    """Single-device truncated-signature dispatch — the body of
    :func:`signature` after validation and mesh routing.  Under a mesh this
    runs per shard inside :func:`_sharded_sig` (never consults the context
    again, so shard_map bodies cannot recurse into the mesh branch).

    Precision discipline: the increments are quantised HERE, once, before any
    engine or custom VJP sees them — rounding is the semantics (every engine
    agrees bit-for-bit on what it accumulates), while the kernels' storage
    dtype handles the bandwidth side.  The STE-style quantiser keeps fp32
    cotangents, so gradients are exact for the rounded forward.
    """
    spec = transform
    if spec is None:
        if lengths is not None:
            lengths = as_lengths(lengths, increments.shape[0])
            increments = mask_increments(increments, lengths)
        increments = quantise_increments(increments, precision)
        if stream:
            if engine == "jax" or backward == "autodiff" \
                    or increments.shape[1] == 0:  # M=0: no emissions
                out = signature_from_increments(
                    increments, depth, stream=True,
                    stream_stride=stream_stride, backward=backward,
                    backend="jax")
            else:
                out = _pallas_sig_stream(depth, stream_stride, batch_tile,
                                         split, interpret,
                                         precision)(increments)
            # bf16_fp32 stores the streamed emission buffer in bf16 (the
            # pallas cells emit bf16 from fp32 accumulators); the STE round
            # here makes every engine agree on the emitted values and is
            # idempotent over the kernels' hard rounding
            out = quantise_increments(out, precision)
            return _mask_stream_out(out, increments.shape[1], stream_stride,
                                    lengths)
        if engine == "jax" or backward == "autodiff":
            # autodiff has no Pallas rule: route to the jax engine entirely so
            # the forward actually produces the residuals the scan AD consumes.
            return signature_from_increments(increments, depth,
                                             backward=backward, backend="jax")
        if time_chunks > 1:
            return _time_parallel_combine(
                lambda x: _signature_local(x, None, depth=depth, engine=engine,
                                           interpret=interpret,
                                           backward=backward,
                                           batch_tile=batch_tile, split=split,
                                           time_chunks=1, stream=False,
                                           stream_stride=1,
                                           precision=precision),
                increments, depth, time_chunks)
        if backward == "checkpoint":
            chunk = default_chunk(increments.shape[1])
            return _pallas_sig_checkpoint(depth, chunk, batch_tile, split,
                                          interpret, precision)(increments)
        return _pallas_sig_inverse(depth, batch_tile, split, interpret,
                                   precision)(increments)
    # ---- fused-transform cell -------------------------------------------
    if engine == "jax" or backward == "autodiff" or increments.shape[1] == 0:
        # the pure-JAX fused scan owns masking/basepoint/taux bookkeeping
        out = signature_from_increments(
            increments, depth, stream=stream, stream_stride=stream_stride,
            backward=backward, backend="jax", lengths=lengths, transform=spec,
            x0=x0, precision=precision)
        return quantise_increments(out, precision) if stream else out
    if lengths is not None:
        lengths = as_lengths(lengths, increments.shape[0])
        increments = mask_increments(increments, lengths)
    if spec.basepoint:
        if x0 is None:
            raise ValueError("transform with basepoint needs x0= (the path "
                             "start point, shape (B, d)); repro.core."
                             "signature.signature passes it automatically")
        x0 = jnp.asarray(x0).astype(increments.dtype)
        increments = jnp.concatenate([x0[:, None, :], increments], axis=1)
        lengths = None if lengths is None else lengths + 1
    increments = quantise_increments(increments, precision)
    kspec = dataclasses_replace_nobp(spec)
    if not kspec:
        # basepoint-only: just one prepended increment — plain engines
        # (lengths already shifted; re-masking the prepended batch is exact)
        return _signature_local(increments, lengths, depth=depth,
                                engine=engine, interpret=interpret,
                                backward=backward, batch_tile=batch_tile,
                                split=split, time_chunks=time_chunks,
                                stream=stream, stream_stride=stream_stride,
                                precision=precision)
    B, M_bp, _ = increments.shape
    taux = transform_time_aux(kspec, B, M_bp, lengths)
    M_aug = M_bp * kspec.sub_steps
    aug_lengths = None if lengths is None else lengths * kspec.sub_steps
    if stream:
        out = _pallas_sig_fused_stream(depth, stream_stride, batch_tile,
                                       split, interpret, kspec,
                                       precision)(increments, taux)
        out = quantise_increments(out, precision)
        return _mask_stream_out(out, M_aug, stream_stride, aug_lengths)
    if time_chunks > 1 or backward == "checkpoint":
        # materialise-then-sweep fallback (support matrix): the augment is
        # linear jnp, so autodiff through it IS the transform adjoint and the
        # chunked cells run unchanged over the augmented increments.
        e = fused_augment(increments, taux, kspec)
        return _signature_local(e, None, depth=depth, engine=engine,
                                interpret=interpret, backward=backward,
                                batch_tile=batch_tile, split=split,
                                time_chunks=time_chunks, stream=False,
                                stream_stride=1, precision=precision)
    return _pallas_sig_fused_inverse(depth, batch_tile, split, interpret,
                                     kspec, precision)(increments, taux)


@_obs_entry
def signature(increments: jax.Array, depth: int, *, backend: str = "auto",
              backward: str = "inverse", batch_tile: int | None = None,
              split: int | None = None, time_chunks: int = 1,
              stream: bool = False, stream_stride: int = 1,
              lengths=None, transform=None, x0=None,
              precision: str = "fp32") -> jax.Array:
    """Truncated signature (B, M, d) -> (B, D_sig), differentiable on every
    backend (see the support matrix in the module docstring).

    ``stream=True`` -> (B, M_out, D_sig) prefix signatures at every
    ``stream_stride``-th step (terminal always included).

    ``lengths`` (B,) makes the batch ragged (every backend × backward ×
    stream cell): padded-tail increments are zero-masked BEFORE the engine
    runs (a zero increment is the identity update, so terminal outputs are
    exactly the per-example unpadded signatures, and the outermost mask
    multiply zeroes cotangents past each true end); streamed outputs are
    additionally masked after each example's true-terminal slot
    (:func:`repro.core.signature.stream_emit_slots` gathers it).

    ``transform`` (``"time_augment"``, ``"lead_lag"``, ``"basepoint"``, or a
    ``+``-joined composition — :func:`repro.core.transforms.as_transform`)
    applies the path transform FUSED into the engine sweep: the augmented
    increment is built in VMEM/registers per Horner sub-step and the
    (B, M_aug, d_aug) intermediate never materialises (forward, streamed,
    and both custom-VJP backwards; streamed strides/lengths count AUGMENTED
    steps).  ``x0`` (B, d) is the path start, required iff the transform
    includes basepoint.  ``precision="bf16_fp32"`` rounds increments to bf16
    (storage + traffic) while accumulating in fp32 — see the module
    docstring for the error model.

    ``batch_tile=None`` (the default) consults the persistent autotuner
    (:mod:`repro.kernels.autotune`) for this dispatch cell and falls back to
    128; an explicit value always wins.  A cached autotune entry may also
    supply ``split`` when it is not passed.

    Under an installed ``sharding_ctx(mesh)`` whose rules shard the "batch"
    logical axis, the call is SPMD: the batch is split over the mesh with
    ``shard_map`` and each shard runs this same cell (see the mesh note in
    the module docstring).  Outside any context the result is bit-identical
    to the single-device path.
    """
    engine, interpret = resolve_backend(backend)
    _check_backward(backward)
    precision = canon_precision(precision)
    spec = as_transform(transform)
    if engine == "hybrid":
        raise ValueError(
            "backend='hybrid' only applies to projected word sets (the "
            "truncated signature IS the dense engine); use backend='jax'")
    if stream:
        if stream_stride < 1:
            raise ValueError(
                f"stream_stride must be >= 1, got {stream_stride}")
        if backward == "checkpoint":
            raise unsupported_stream_backward(backward)
        if time_chunks > 1:
            raise NotImplementedError(
                "stream=True is incompatible with time_chunks > 1: chunked "
                "signatures only reconstruct the terminal state")
    d_eff = transform_dim(spec, increments.shape[-1]) if spec \
        else increments.shape[-1]
    M_eff = transform_steps(spec, increments.shape[1]) if spec \
        else increments.shape[1]
    if batch_tile is None:
        hit = autotune.lookup("sig_trunc", engine=_tuned(engine, interpret),
                              d=d_eff, depth=depth, M=M_eff,
                              B=increments.shape[0], precision=precision)
        batch_tile = hit.get("batch_tile", 128)
        if split is None:
            split = hit.get("split")
    if obs.REGISTRY._enabled and engine == "pallas" and depth >= 1:
        from .sig_trunc import state_footprint
        itemsize = 2 if precision == "bf16_fp32" else 4
        s = split if split is not None else choose_split(
            d_eff, depth, batch_tile, itemsize=itemsize)
        obs.gauge(
            "pathsig_vmem_state_bytes",
            "per-cell VMEM footprint of the cone-kernel state at the "
            "resolved (batch_tile, split)", ("op",)).set(
            state_footprint(d_eff, depth, s, batch_tile, itemsize),
            op="signature")
    kw = dict(depth=depth, engine=engine, interpret=interpret,
              backward=backward, batch_tile=batch_tile, split=split,
              time_chunks=time_chunks, stream=stream,
              stream_stride=stream_stride)
    mb = _mesh_batch()
    if mb is None:
        return _signature_local(increments, lengths, **kw, transform=spec,
                                x0=x0, precision=precision)
    mesh, names, size = mb
    if lengths is not None:
        lengths = as_lengths(lengths, increments.shape[0])
    if spec:
        # mesh × transform: increment-level materialise (support matrix) —
        # the augment is linear jnp, so its adjoint rides ordinary AD and the
        # per-shard custom VJPs run unchanged over augmented increments.
        if lengths is not None:
            increments, lengths = augment_increments(increments, spec, x0=x0,
                                                     lengths=lengths)
        else:
            increments = augment_increments(increments, spec, x0=x0)
    fn = _sharded_sig(mesh, names, lengths is not None, depth, engine,
                      interpret, backward, batch_tile, split, time_chunks,
                      stream, stream_stride, precision)
    out = _apply_sharded(fn, size, increments, lengths)
    if stream:
        return shard_constraint(out, "batch", "path_time", "sig_words")
    return shard_constraint(out, "batch", "sig_words")


def _projected_local(increments: jax.Array, lengths, *, words: tuple, d: int,
                     engine: str, interpret: bool, backward: str,
                     batch_tile: int, max_rows: int, stream: bool,
                     stream_stride: int, transform=None, x0=None,
                     precision: str = "fp32", dense=None) -> jax.Array:
    """Single-device projected-signature dispatch — the body of
    :func:`projected` after validation and mesh routing (``max_rows`` is
    already resolved from any caller-supplied TiledPlan; ``dense`` is
    :func:`_dense_tiling`'s).  Same precision discipline as
    :func:`_signature_local`: quantise once at dispatch, every engine sees
    the rounded values."""
    wplan = _plan_for_words(words, d)
    spec = transform
    if spec is not None:
        # fused-transform cell: the word kernel fuses lead_lag/time; every
        # other engine × backward cell runs the documented materialise-then-
        # sweep fallback over augmented increments.
        if lengths is not None:
            lengths = as_lengths(lengths, increments.shape[0])
            increments = mask_increments(increments, lengths)
        if spec.basepoint:
            if x0 is None:
                raise ValueError("transform with basepoint needs x0= (the "
                                 "path start point, shape (B, d))")
            x0 = jnp.asarray(x0).astype(increments.dtype)
            increments = jnp.concatenate([x0[:, None, :], increments], axis=1)
            lengths = None if lengths is None else lengths + 1
        increments = quantise_increments(increments, precision)
        kspec = dataclasses_replace_nobp(spec)
        kw = dict(words=words, d=d, engine=engine, interpret=interpret,
                  backward=backward, batch_tile=batch_tile, max_rows=max_rows,
                  stream=stream, stream_stride=stream_stride,
                  precision=precision, dense=dense)
        if not kspec:  # basepoint-only: one prepended increment
            return _projected_local(increments, lengths, **kw)
        B, M_bp, _ = increments.shape
        taux = transform_time_aux(kspec, B, M_bp, lengths)
        M_aug = M_bp * kspec.sub_steps
        aug_lengths = None if lengths is None else lengths * kspec.sub_steps
        if engine in ("jax", "hybrid") or backward == "autodiff" \
                or M_aug == 0 or (not stream and backward != "inverse"):
            e = fused_augment(increments, taux, kspec)
            return _projected_local(e, aug_lengths, **kw)
        if stream:
            out = _pallas_proj_fused_stream(
                wplan.words, wplan.d, stream_stride, batch_tile, max_rows,
                interpret, kspec, precision)(increments, taux)
            out = quantise_increments(out, precision)
            return _mask_stream_out(out, M_aug, stream_stride, aug_lengths)
        if _takes_dense(wplan, increments, dense, precision, kspec):
            return _dense_words(_pallas_sig_fused_inverse(
                wplan.depth, *dense, interpret, kspec,
                precision)(increments, taux), wplan)
        return _pallas_proj_fused_inverse(
            wplan.words, wplan.d, batch_tile, max_rows, interpret, kspec,
            precision)(increments, taux)
    if lengths is not None:
        lengths = as_lengths(lengths, increments.shape[0])
        increments = mask_increments(increments, lengths)
    increments = quantise_increments(increments, precision)
    if engine == "hybrid":
        if backward == "checkpoint":
            # no chunk-boundary buffer in the hybrid engine: run on jax
            return projected_signature_from_increments(
                increments, wplan, backward=backward, backend="jax")
        return _hybrid_projected(increments, wplan, backward)
    if stream:
        if engine == "jax" or backward == "autodiff" \
                or increments.shape[1] == 0:  # M=0: no emissions, any engine
            out = projected_signature_from_increments(
                increments, wplan, stream=True, stream_stride=stream_stride,
                backward=backward, backend="jax")
        else:
            out = _pallas_proj_stream(wplan.words, wplan.d, stream_stride,
                                      batch_tile, max_rows, interpret,
                                      precision)(increments)
        # same streamed-emission rounding discipline as _signature_local
        out = quantise_increments(out, precision)
        return _mask_stream_out(out, increments.shape[1], stream_stride,
                                lengths)
    if engine == "jax" or backward != "inverse":
        # checkpoint needs chunk-boundary closure states the word kernel
        # cannot emit; autodiff needs scan residuals — both run on jax.
        return projected_signature_from_increments(
            increments, wplan, backward=backward, backend="jax")
    if _takes_dense(wplan, increments, dense, precision):
        return _dense_words(_pallas_sig_inverse(
            wplan.depth, *dense, interpret, precision)(increments), wplan)
    return _pallas_proj_inverse(wplan.words, wplan.d, batch_tile, max_rows,
                                interpret, precision)(increments)


@_obs_entry
def projected(increments: jax.Array, plan, *, backend: str = "auto",
              backward: str = "inverse", batch_tile: int | None = None,
              max_rows: int = 256, stream: bool = False,
              stream_stride: int = 1, lengths=None, transform=None,
              x0=None, precision: str = "fp32") -> jax.Array:
    """Projected signature over a word set / plan (B, M, d) -> (B, |I|),
    differentiable on every backend.  ``plan`` may be a WordPlan, a
    TiledPlan, or an iterable of letter tuples.

    ``stream=True`` -> (B, M_out, |I|) per-step projections.  ``lengths``
    (B,) makes the batch ragged, with the same zero-masked-increment
    exactness guarantees as :func:`signature`.  An installed
    ``sharding_ctx(mesh)`` sharding the "batch" logical axis makes the call
    SPMD exactly like :func:`signature`.

    ``transform`` / ``x0`` / ``precision`` mirror :func:`signature`: the
    word set is over the AUGMENTED alphabet (letters index the transformed
    channels, ``wplan.d == transform_dim(transform, d_raw)``), the pallas
    inverse/stream cells fuse the transform into the kernel time loop, and
    every other cell materialises augmented increments once then sweeps
    (support matrix).  ``batch_tile=None`` consults the autotuner, under
    ``sig_trunc`` for the dense route and ``sig_words`` for the word
    engine.
    """
    engine, interpret = resolve_backend(backend)
    _check_backward(backward)
    precision = canon_precision(precision)
    spec = as_transform(transform)
    d_in = increments.shape[-1]
    d_eff = transform_dim(spec, d_in) if spec else d_in
    wplan, tplan = _normalise_plans(plan, d_eff)
    if spec and wplan.d != d_eff:
        raise ValueError(
            f"projected word plan is over d={wplan.d} letters, but transform "
            f"{spec} maps d={d_in} input channels to {d_eff} augmented "
            f"channels — build the plan over the augmented alphabet")
    if engine == "hybrid" and stream:
        raise NotImplementedError(
            "backend='hybrid' has no streamed forward; use "
            "backend='jax' or a pallas backend for stream=True")
    if stream:
        if stream_stride < 1:
            raise ValueError(
                f"stream_stride must be >= 1, got {stream_stride}")
        if backward == "checkpoint":
            raise unsupported_stream_backward(backward)
    if tplan is not None:  # keep the caller's tile granularity
        max_rows = max(p.closure_size for p in tplan.tiles)
    M_eff = transform_steps(spec, increments.shape[1]) if spec \
        else increments.shape[1]
    dense = _dense_tiling(wplan, engine=engine, interpret=interpret,
                          backward=backward, stream=stream,
                          batch_tile=batch_tile, B=increments.shape[0],
                          M=M_eff, precision=precision)
    if batch_tile is None:
        hit = autotune.lookup(
            "sig_words", engine=_tuned(engine, interpret), d=wplan.d,
            depth=wplan.depth, M=M_eff, B=increments.shape[0],
            precision=precision)
        batch_tile = hit.get("batch_tile", 128)
    kw = dict(words=wplan.words, d=wplan.d, engine=engine,
              interpret=interpret, backward=backward, batch_tile=batch_tile,
              max_rows=max_rows, stream=stream, stream_stride=stream_stride,
              dense=dense)
    mb = _mesh_batch()
    if mb is None:
        return _projected_local(increments, lengths, **kw, transform=spec,
                                x0=x0, precision=precision)
    mesh, names, size = mb
    if lengths is not None:
        lengths = as_lengths(lengths, increments.shape[0])
    if spec:
        # mesh × transform: increment-level materialise (support matrix)
        if lengths is not None:
            increments, lengths = augment_increments(increments, spec, x0=x0,
                                                     lengths=lengths)
        else:
            increments = augment_increments(increments, spec, x0=x0)
    fn = _sharded_proj(mesh, names, lengths is not None, wplan.words,
                       wplan.d, engine, interpret, backward, batch_tile,
                       max_rows, stream, stream_stride, precision, dense)
    out = _apply_sharded(fn, size, increments, lengths)
    if stream:
        return shard_constraint(out, "batch", "path_time", "sig_words")
    return shard_constraint(out, "batch", "sig_words")


def _projected_fwd_local(increments: jax.Array, lengths, *, words: tuple,
                         d: int, engine: str, interpret: bool,
                         batch_tile: int, max_rows: int, transform=None,
                         x0=None, precision: str = "fp32") -> jax.Array:
    """Single-device body of :func:`projected_forward_only`."""
    wplan = _plan_for_words(words, d)
    if lengths is not None:
        lengths = as_lengths(lengths, increments.shape[0])
        increments = mask_increments(increments, lengths)
    spec = transform
    taux = None
    kspec = None
    if spec is not None:
        if spec.basepoint:
            if x0 is None:
                raise ValueError("transform with basepoint needs x0= (the "
                                 "path start point, shape (B, d))")
            increments = jnp.concatenate(
                [jnp.asarray(x0).astype(increments.dtype)[:, None, :],
                 increments], axis=1)
            lengths = None if lengths is None else lengths + 1
        kspec = dataclasses_replace_nobp(spec) or None
    increments = quantise_increments(increments, precision)
    if kspec is not None:
        taux = transform_time_aux(kspec, increments.shape[0],
                                  increments.shape[1], lengths)
        if engine in ("jax", "hybrid"):
            # materialise-then-sweep fallback (support matrix)
            increments = fused_augment(increments, taux, kspec)
            kspec = taux = None
    if engine == "hybrid":
        return _hybrid_projected(increments, wplan, "inverse")
    if engine == "jax":
        return projected_signature_from_increments(increments, wplan,
                                                   backend="jax")
    tplan = _tiled_for_words(wplan.words, wplan.d, max_rows)
    return sig_words(increments, tplan, batch_tile=batch_tile,
                     interpret=interpret, transform=kspec, taux=taux,
                     precision=precision)


@_obs_entry
def projected_forward_only(increments: jax.Array, plan, *,
                           backend: str = "auto", batch_tile: int | None = None,
                           max_rows: int = 256, lengths=None, transform=None,
                           x0=None, precision: str = "fp32") -> jax.Array:
    """Inference-only projected signature: skips the closure readout (the
    kernel gathers just the requested rows).  Not differentiable on the
    pallas engines — use :func:`projected` for training.  Mesh-aware like
    :func:`projected` (per-shard kernels under a batch-sharding context);
    ``transform`` / ``x0`` / ``precision`` / autotuned ``batch_tile`` mirror
    :func:`projected`."""
    engine, interpret = resolve_backend(backend)
    precision = canon_precision(precision)
    spec = as_transform(transform)
    d_in = increments.shape[-1]
    d_eff = transform_dim(spec, d_in) if spec else d_in
    wplan, tplan = _normalise_plans(plan, d_eff)
    if spec and wplan.d != d_eff:
        raise ValueError(
            f"projected word plan is over d={wplan.d} letters, but transform "
            f"{spec} maps d={d_in} input channels to {d_eff} augmented "
            f"channels — build the plan over the augmented alphabet")
    if tplan is not None:  # keep the caller's tile granularity
        max_rows = max(p.closure_size for p in tplan.tiles)
    if batch_tile is None:
        hit = autotune.lookup(
            "sig_words", engine=_tuned(engine, interpret), d=wplan.d,
            depth=wplan.depth,
            M=transform_steps(spec, increments.shape[1]) if spec
            else increments.shape[1],
            B=increments.shape[0], precision=precision)
        batch_tile = hit.get("batch_tile", 128)
    kw = dict(words=wplan.words, d=wplan.d, engine=engine,
              interpret=interpret, batch_tile=batch_tile, max_rows=max_rows)
    mb = _mesh_batch()
    if mb is None:
        return _projected_fwd_local(increments, lengths, **kw, transform=spec,
                                    x0=x0, precision=precision)
    mesh, names, size = mb
    if lengths is not None:
        lengths = as_lengths(lengths, increments.shape[0])
    if spec:
        # mesh × transform: increment-level materialise (support matrix)
        if lengths is not None:
            increments, lengths = augment_increments(increments, spec, x0=x0,
                                                     lengths=lengths)
        else:
            increments = augment_increments(increments, spec, x0=x0)
    fn = _sharded_proj_fwd(mesh, names, lengths is not None, wplan.words,
                           wplan.d, engine, interpret, batch_tile, max_rows,
                           precision)
    out = _apply_sharded(fn, size, increments, lengths)
    return shard_constraint(out, "batch", "sig_words")


def _time_parallel_combine(sig_flat_fn, increments: jax.Array, depth: int,
                           time_chunks: int) -> jax.Array:
    """Fold time chunks into the batch axis, compute chunk signatures with
    ``sig_flat_fn`` ((B·C, Mc, d) -> (B·C, D_sig)), Chen-combine in a
    log-depth tree.  Shared by :func:`signature_time_parallel` (public,
    routed through the dispatch) and the mesh path's per-shard body."""
    B, M, d = increments.shape
    C = max(1, min(time_chunks, M))
    Mc = -(-M // C)
    pad = C * Mc - M
    x = jnp.pad(increments, ((0, 0), (0, pad), (0, 0)))  # zero incs = identity
    x = x.reshape(B, C, Mc, d).reshape(B * C, Mc, d)
    flat = sig_flat_fn(x)
    parts = flat.reshape(B, C, -1)
    # log-depth Chen combination tree
    while parts.shape[1] > 1:
        n = parts.shape[1]
        even, odd = parts[:, 0:n - n % 2:2], parts[:, 1:n:2]
        a = tops.flat_to_levels(even.reshape(-1, even.shape[-1]), d, depth)
        b = tops.flat_to_levels(odd.reshape(-1, odd.shape[-1]), d, depth)
        merged = tops.levels_to_flat(tops.chen_mul(a, b))
        merged = merged.reshape(even.shape)
        if n % 2:
            merged = jnp.concatenate([merged, parts[:, -1:]], axis=1)
        parts = merged
    return parts[:, 0]


def signature_time_parallel(increments: jax.Array, depth: int,
                            time_chunks: int, *, backend: str = "auto",
                            backward: str = "inverse",
                            batch_tile: int | None = None,
                            split: int | None = None,
                            precision: str = "fp32") -> jax.Array:
    """Chunked-time signature: fold chunks into batch, tree-Chen-combine.

    Differentiable end to end: the per-chunk signatures carry the dispatch
    layer's custom VJPs and the combination tree is plain jnp algebra.
    """
    return _time_parallel_combine(
        lambda x: signature(x, depth, backend=backend, backward=backward,
                            batch_tile=batch_tile, split=split,
                            time_chunks=1, precision=precision),
        increments, depth, time_chunks)
