"""Dynamic batching for ragged signature serving.

Per-request serving of variable-length paths is the worst case for a
compiled runtime: every distinct length is a fresh executable, and batch=1
leaves the hardware idle.  ``DynamicBatcher`` turns that traffic into
micro-batched serving with a *bounded* set of compiled shapes:

1. requests are queued (:meth:`submit`) as (M_i+1, d) paths;
2. :meth:`flush` packs them into length buckets on the
   :func:`repro.ragged.bucket_ladder` (lengths rounded up a geometric
   ladder) and pads each micro-batch's row count up a power-of-two ladder;
3. each bucket runs ONE engine call over its padded
   :class:`repro.ragged.RaggedPaths` — exact per-request answers, because
   zero-masked padding is the identity (see :mod:`repro.ragged`);
4. results are scattered back to the submitting tickets.

Shape accounting is explicit: ``shapes_seen`` is the set of (padded_len,
padded_batch) pairs fed to the engine — at most ``len(ladder) ×
len(batch-rungs)`` entries no matter how many distinct request lengths
arrive — and ``stats()`` reports padding waste next to it.

The two factories bind the batcher to the serving engines of
:mod:`repro.serve.engine`: :meth:`signature_service` computes the terminal
window features a :class:`SigStreamEngine` tracks online, and
:meth:`scoring_service` rides a :class:`SigScoreEngine`'s cached reference
signatures/Gram for retrieval scores or KRR predictions per request.

Multi-device: give the batcher a mesh (``mesh=`` or build it inside an
installed ``sharding_ctx``) and every flushed rung is placed across it —
the batch rung rounds up to a multiple of the mesh's batch-shard count so
each device owns the same number of rows, values/lengths are device_put
batch-sharded, and the per-shape jitted computes trace under the mesh
context (so the engine calls inside take the SPMD path of
:mod:`repro.kernels.ops`).  :meth:`stats` then reports per-device occupancy
(``devices`` / ``rows_per_device`` / ``occupancy``) next to the shape
accounting.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.distributed.ctx import (current_mesh, logical_axis_size,
                                   named_sharding, sharding_ctx)
from repro.kernels.ops import BoundedCache
from repro.ragged import (RaggedPaths, assign_buckets, batch_rung,
                          bucket_ladder, pad_batch)


@dataclasses.dataclass
class _Request:
    ticket: int
    path: np.ndarray      # (M_i+1, d)
    length: int           # increments


@dataclasses.dataclass
class DynamicBatcher:
    """Queue → length-bucket → micro-batch executor (see module docstring).

    ``compute(batch: RaggedPaths) -> (B, ...) array`` is the per-bucket
    engine call; row b of its output is the answer for example b.  Build
    one with :meth:`signature_service` / :meth:`scoring_service`, or pass
    any custom callable (it sees zero-masked-exact padded batches).
    """
    compute: Callable[[RaggedPaths], jax.Array]
    d: int
    max_len: int                      # longest accepted request (increments)
    min_bucket: int = 16              # bottom rung of the length ladder
    growth: float = 2.0               # ladder growth factor
    max_batch: int = 64               # top rung of the batch ladder
    ladder: Optional[np.ndarray] = None   # explicit rungs override
    jit_compute: bool = True          # one executable per (rung, batch) shape
    mesh: Optional[object] = None     # jax Mesh: place rungs across devices
    mesh_rules: Optional[dict] = None     # logical-axis rule overrides
    slos: Optional[tuple] = None      # health() objectives (None -> defaults)
    latency_window: int = 1024        # recent flush latencies kept for health
    async_dispatch: bool = True       # prefetch next rung while current runs
    max_in_flight: int = 2            # bound on dispatched-not-retired rungs

    def __post_init__(self):
        if self.ladder is None:
            self.ladder = bucket_ladder(self.max_len,
                                        min_len=self.min_bucket,
                                        growth=self.growth)
        self.ladder = np.asarray(self.ladder, np.int64)
        self.max_len = int(self.ladder[-1])
        if self.mesh is None:  # adopt an installed context at build time
            self.mesh = current_mesh()
        # per-(rung, batch) jitted computes, bounded under the shared
        # plan-cache policy: evicting a shape frees its executable; traffic
        # returning to it just re-jits (bit-identical results)
        self._compute_cache = BoundedCache("dynamic_batcher_compute")
        if self.slos is None:
            self.slos = obs.batcher_slos()
        # host-side latency record so health() works with metrics disabled
        self._flush_latencies = collections.deque(
            maxlen=max(1, self.latency_window))
        if self.max_in_flight < 1:
            raise ValueError(f"max_in_flight must be >= 1, got "
                             f"{self.max_in_flight}")
        self._queue: list[_Request] = []
        self._next_ticket = 0
        self._in_flight_peak = 0      # most dispatched-not-retired rungs seen
        self._prefetched_rungs = 0    # Σ rungs device_put ahead of compute
        self.shapes_seen: set[tuple[int, int]] = set()
        self.padded_steps = 0         # Σ padded increments fed to the engine
        self.true_steps = 0           # Σ true increments served
        self.padded_rows = 0          # Σ batch rows fed to the engine
        self.true_rows = 0            # Σ real requests served

    # -- mesh placement ----------------------------------------------------

    def _mesh_scope(self):
        """Context manager installing this batcher's mesh (a no-op stack
        entry when the batcher is single-device)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return sharding_ctx(self.mesh, self.mesh_rules)

    def _batch_shards(self) -> int:
        """Shards of the "batch" logical axis under this batcher's OWN mesh
        (fixed at construction — never the ambient context, so rung rounding
        and stats() accounting cannot drift with the call site)."""
        if self.mesh is None:
            return 1
        with self._mesh_scope():
            return logical_axis_size("batch")

    def _place(self, rp: RaggedPaths) -> RaggedPaths:
        """device_put a flushed rung across the mesh: values and lengths
        batch-sharded, so each device owns B_pad / P requests."""
        if self.mesh is None:
            return rp
        with self._mesh_scope():
            shardings = RaggedPaths(
                values=named_sharding("batch", "path_time", None),
                lengths=named_sharding("batch"))
        return jax.device_put(rp, shardings)

    # -- request side ------------------------------------------------------

    def submit(self, path) -> int:
        """Queue one (M_i+1, d) path; returns the ticket :meth:`flush`
        resolves."""
        path = np.asarray(path, np.float32)
        if path.ndim != 2 or path.shape[-1] != self.d:
            raise ValueError(f"request must be (M+1, {self.d}), got "
                             f"{path.shape}")
        length = path.shape[0] - 1
        if not 0 <= length <= self.max_len:
            raise ValueError(f"request length {length} outside [0, "
                             f"{self.max_len}] (the ladder's top rung)")
        t = self._next_ticket
        self._next_ticket += 1
        self._queue.append(_Request(t, path, length))
        if obs.enabled():
            obs.gauge("pathsig_batcher_queue_depth",
                      "requests waiting in the DynamicBatcher queue",
                      ).set(len(self._queue))
        return t

    @property
    def pending(self) -> int:
        return len(self._queue)

    # -- execution side ----------------------------------------------------

    def _compute_fn(self, rung: int, B_pad: int):
        return (self._compute_cache.get(
            (rung, B_pad),
            lambda: obs.instrument_jit(
                self.compute, site="batcher_compute"))
            if self.jit_compute else self.compute)

    def _pack_groups(self, queue) -> list:
        """Bucket + split the queue into host-side micro-batches:
        [(rung, B_pad, part, host RaggedPaths)], with shape/padding
        accounting applied."""
        shards = self._batch_shards()
        lengths = np.asarray([r.length for r in queue], np.int64)
        which = assign_buckets(lengths, self.ladder)
        groups = []
        for k in np.unique(which):
            rung = int(self.ladder[k])
            group = [queue[i] for i in np.nonzero(which == k)[0]]
            # split oversized groups so the batch rung never exceeds
            # max_batch
            for off in range(0, len(group), self.max_batch):
                part = group[off:off + self.max_batch]
                rp = RaggedPaths.from_list([r.path for r in part],
                                           pad_to=rung)
                B_pad = batch_rung(len(part), self.max_batch)
                # round the rung up to a multiple of the mesh's batch
                # shards so every device owns the same number of rows
                B_pad = -(-B_pad // shards) * shards
                self.shapes_seen.add((rung, B_pad))
                self.padded_steps += rung * B_pad
                self.true_steps += int(sum(r.length for r in part))
                self.padded_rows += B_pad
                self.true_rows += len(part)
                groups.append((rung, B_pad, part, pad_batch(rp, B_pad)))
        return groups

    def _run_groups(self, groups) -> list:
        """Async-dispatch executor: device_put the next rungs' host buffers
        while the current rung computes (each rung's transfer is issued up
        to ``max_in_flight`` groups ahead), dispatch every compute without
        blocking on its result (jax dispatch is async), and retire the
        oldest outstanding rung whenever more than ``max_in_flight`` are in
        flight.  Returns [(part, result_array)]; with ``async_dispatch=
        False`` this degrades to strict place→compute→next serial order."""
        window = self.max_in_flight if self.async_dispatch else 0
        placed = collections.deque()
        next_put = 0

        def top_up(limit):
            nonlocal next_put
            while next_put < len(groups) and next_put < limit:
                rung, B_pad, part, rp = groups[next_put]
                with obs.span("serve.batcher.place", rung=rung, B_pad=B_pad):
                    placed.append((rung, B_pad, part, self._place(rp)))
                next_put += 1

        results: list = []
        in_flight: collections.deque = collections.deque()
        for i in range(len(groups)):
            top_up(i + 1 + window)
            self._prefetched_rungs += len(placed) - 1
            rung, B_pad, part, rp = placed.popleft()
            fn = self._compute_fn(rung, B_pad)
            with self._mesh_scope(), \
                    obs.span("serve.batcher.rung", rung=rung, B_pad=B_pad,
                             rows=len(part), prefetched=len(placed)):
                res = fn(rp)
            results.append((part, res))
            in_flight.append(res)
            self._in_flight_peak = max(self._in_flight_peak, len(in_flight))
            while len(in_flight) > max(1, window):
                with obs.span("serve.batcher.retire"):
                    jax.block_until_ready(in_flight.popleft())
        return results

    @obs.dump_on_error("batcher.flush")
    def flush(self) -> dict[int, jax.Array]:
        """Run every queued request through bucketed micro-batches; returns
        {ticket: result_row}."""
        queue, self._queue = self._queue, []
        out: dict[int, jax.Array] = {}
        if not queue:
            return out
        t_flush = time.perf_counter()
        with obs.span("serve.batcher.flush", requests=len(queue)):
            with obs.span("serve.batcher.pack", requests=len(queue)):
                groups = self._pack_groups(queue)
            results = self._run_groups(groups)
            with obs.span("serve.batcher.rows", rows=len(queue)):
                for part, res in results:
                    for row, req in enumerate(part):
                        out[req.ticket] = res[row]
        self._flush_latencies.append(time.perf_counter() - t_flush)
        if obs.enabled():
            obs.histogram(
                "pathsig_batcher_flush_seconds",
                "wall-clock of one DynamicBatcher.flush (dispatch side)",
            ).observe(time.perf_counter() - t_flush)
            obs.counter("pathsig_batcher_requests_total",
                        "requests served through DynamicBatcher.flush",
                        ).inc(len(queue))
            obs.gauge("pathsig_batcher_padding_overhead",
                      "cumulative padded/true step ratio fed to the engine",
                      ).set(self.padded_steps / self.true_steps
                            if self.true_steps else 0.0)
            obs.gauge("pathsig_batcher_occupancy",
                      "cumulative true/padded batch-row occupancy",
                      ).set(self.true_rows / self.padded_rows
                            if self.padded_rows else 0.0)
            obs.gauge("pathsig_batcher_compiled_shapes",
                      "distinct (rung, B_pad) shapes fed to the engine",
                      ).set(len(self.shapes_seen))
            obs.gauge("pathsig_batcher_queue_depth",
                      "requests waiting in the DynamicBatcher queue",
                      ).set(len(self._queue))
        return out

    def _flush_pctl(self, q: float) -> float:
        lat = sorted(self._flush_latencies)
        if not lat:
            return 0.0
        i = max(0, min(len(lat) - 1,
                       int(np.ceil(q / 100.0 * len(lat))) - 1))
        return lat[i]

    def stats(self) -> dict:
        """Shape-count + padding-waste accounting for the traffic so far,
        plus per-device occupancy when the batcher places across a mesh."""
        shards = self._batch_shards()
        return {
            "flush_p50_s": self._flush_pctl(50),
            "flush_p99_s": self._flush_pctl(99),
            "flushes_recorded": len(self._flush_latencies),
            "compiled_shapes": len(self.shapes_seen),
            "shapes": sorted(self.shapes_seen),
            "ladder": self.ladder.tolist(),
            "padded_steps": self.padded_steps,
            "true_steps": self.true_steps,
            "padding_overhead": (self.padded_steps / self.true_steps
                                 if self.true_steps else 0.0),
            "devices": shards,
            "rows_per_device": self.padded_rows // shards,
            "occupancy": (self.true_rows / self.padded_rows
                          if self.padded_rows else 0.0),
            "async_dispatch": self.async_dispatch,
            "max_in_flight": self.max_in_flight,
            "in_flight_peak": self._in_flight_peak,
            "prefetched_rungs": self._prefetched_rungs,
            "compute_cache": dict(self._compute_cache.info()._asdict()),
        }

    def health(self, slos: Optional[tuple] = None) -> dict:
        """Machine-readable SLO health evaluated over :meth:`stats` —
        ``{"status": "ok"|"breach", "breaches": [...], "results": [...]}``.
        Host-side only (the recent-flush latency window feeds the p99), so
        it works with the metrics registry disabled."""
        use = self.slos if slos is None else tuple(slos)
        return obs.slo.report(obs.evaluate_values(use, self.stats()))

    # -- engine factories --------------------------------------------------

    @classmethod
    def signature_service(cls, d: int, depth: int, *, max_len: int,
                          backend: str = "auto", transform=None,
                          precision: str = "fp32", **kw) -> "DynamicBatcher":
        """Batcher computing each request's terminal signature features —
        the batched analogue of draining a :class:`SigStreamEngine` slot
        (same (D_sig,) feature vector its ``features`` property holds).
        ``transform`` fuses path transforms into the sweep (no augmented
        intermediate per batch); ``precision="bf16_fp32"`` serves the
        mixed-precision sweep."""
        from repro.kernels import ops
        from repro.core import tensor_ops as tops
        from repro.core.transforms import as_transform
        spec = as_transform(transform)

        def compute(rp: RaggedPaths) -> jax.Array:
            incs = tops.path_increments(rp.values)
            x0 = (rp.values[:, 0] if spec is not None and spec.basepoint
                  else None)
            return ops.signature(incs, depth, backend=backend,
                                 lengths=rp.lengths, transform=spec, x0=x0,
                                 precision=precision)

        return cls(compute, d, max_len, **kw)

    @classmethod
    def scoring_service(cls, engine, *, max_len: int, mode: str = "scores",
                        **kw) -> "DynamicBatcher":
        """Batcher scoring requests against a :class:`SigScoreEngine`'s
        cached reference signatures: ``mode="scores"`` returns (R,) kernel
        scores per request (RKHS cosine if the engine normalises),
        ``"nearest"`` the argmax reference index, ``"predict"`` the KRR
        prediction from the engine's cached duals."""
        if mode not in ("scores", "nearest", "predict"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode == "predict" and engine.alpha is None:
            raise ValueError("scoring_service(mode='predict') needs a "
                             "SigScoreEngine constructed with targets=")
        from repro.kernels import ops
        from repro.core import tensor_ops as tops
        from repro.sigkernel import gram_diag, krr_predict

        def compute(rp: RaggedPaths) -> jax.Array:
            incs = tops.path_increments(rp.values)
            S = ops.signature(incs, engine.depth, backend=engine.backend,
                              lengths=rp.lengths,
                              precision=getattr(engine, "precision", "fp32"))
            K = ops.gram(S, engine.ref_sigs, engine.weights,
                         backend=engine.backend,
                         block_words=engine.block_words,
                         precision=getattr(engine, "precision", "fp32"))
            if mode == "predict":
                return krr_predict(K, engine.alpha)
            if engine.normalize:
                qn = jnp.sqrt(jnp.maximum(gram_diag(S, engine.weights),
                                          1e-12))
                rn = jnp.sqrt(jnp.maximum(jnp.diag(engine.ref_gram), 1e-12))
                K = K / (qn[:, None] * rn[None, :])
            if mode == "nearest":
                return jnp.argmax(K, axis=-1)
            return K

        return cls(compute, engine.d, max_len, **kw)
