"""Open-loop ragged requests through ``DynamicBatcher.signature_service``.

Requests arrive on a schedule fixed before the window (Poisson at the
traffic's ``rate_per_s``; see ``generators.open_loop_schedule``), whatever
the server is doing.  One serving loop submits every request that is due,
calls ``flush()`` whenever the queue is not empty, and fetches each
request's feature row to the host.  A request's latency runs from the time
it was due to the time its row is on the host, so a stall counts against
every request that waited behind it.  Set-up warms every (length rung,
batch rung) shape of the batcher's ladder.

Traffic keys: ``rate_per_s``, ``min_len``, ``max_len``, ``mean_frac``
(request lengths), ``check_rows`` (answers compared with the reference),
``trace_seconds`` (length of the traced slice of a traced run).
"""
from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

from bench import check, reference
from bench.generators import open_loop_schedule, request_paths

LATE_S = 60.0          # an answer later than this past the close never came
REF_BLOCK = 512


def build(run, precision: str = "fp32"):
    from repro.serve import DynamicBatcher
    cfg, traffic = run.cfg, run.traffic
    svc = DynamicBatcher.signature_service(
        cfg["channels"], cfg["depth"], max_len=traffic["max_len"],
        backend="auto", precision=precision)
    # every shape the mix uses: each (length rung, batch rung) program, and
    # the eager padding of a group of n requests up to its batch rung
    for rung in svc.ladder:
        paths = request_paths(0, np.full(svc.max_batch, int(rung)),
                              cfg["channels"])
        for n in range(1, svc.max_batch + 1):
            tickets = [svc.submit(p) for p in paths[:n]]
            out = svc.flush()
            for t in tickets:
                np.asarray(out[t])
    due, lengths = open_loop_schedule(
        run.seed, traffic["rate_per_s"], run.seconds, traffic["max_len"],
        traffic["min_len"], traffic["mean_frac"])
    paths = request_paths(run.seed, lengths, cfg["channels"])
    return svc, due, paths


class Loop:
    """The serving loop's state: what is due, what was sent, what came
    back, and the bench's own spans around each flush."""

    def __init__(self, svc, due, paths):
        self.svc, self.due, self.paths = svc, due, paths
        self.sent = np.full(len(due), np.nan)
        self.done = np.full(len(due), np.nan)
        self.rows: dict[int, np.ndarray] = {}
        self.flush_s: list[float] = []
        self.next = 0
        self.ticket_of: dict[int, int] = {}

    def serve_until(self, t0: float, t_stop: float, prof=None) -> None:
        """Serve until ``t_stop`` (perf_counter), or until every request
        has been answered."""
        note = prof.annotate if prof is not None else _no_annotation
        n = len(self.due)
        while True:
            now = time.perf_counter()
            if now >= t_stop or (self.next >= n and not self.svc.pending):
                return
            with note("bench.submit"):
                while self.next < n and t0 + self.due[self.next] <= now:
                    i = self.next
                    self.ticket_of[self.svc.submit(self.paths[i])] = i
                    self.sent[i] = time.perf_counter()
                    self.next += 1
            if self.svc.pending:
                t = time.perf_counter()
                with note("bench.flush"):
                    out = self.svc.flush()
                with note("bench.fetch"):
                    for ticket, row in out.items():
                        i = self.ticket_of.pop(ticket)
                        self.rows[i] = np.asarray(row)
                        self.done[i] = time.perf_counter()
                self.flush_s.append(time.perf_counter() - t)
            elif self.next < n:
                with note("bench.wait"):
                    wait = t0 + self.due[self.next] - time.perf_counter()
                    if wait > 0:
                        time.sleep(min(wait, 0.002))


def _no_annotation(name):
    return contextlib.nullcontext()


def reference_rows(cfg, paths, idx, device) -> np.ndarray:
    """The reference signature of each sampled request's unpadded path."""
    d, M = cfg["channels"], max(len(paths[i]) - 1 for i in idx)
    sig = jax.jit(lambda x: reference.signature(x, cfg["depth"]))
    out = []
    for lo in range(0, len(idx), REF_BLOCK):
        part = idx[lo:lo + REF_BLOCK]
        x = np.zeros((len(part), M, d), np.float32)
        for r, i in enumerate(part):
            inc = np.diff(paths[i], axis=0)
            x[r, :len(inc)] = inc
        out.append(np.asarray(sig(jax.device_put(x, device))))
    return np.concatenate(out)


def sample(seed: int, lengths: np.ndarray, answered: np.ndarray,
           n: int) -> np.ndarray:
    """Up to ``n`` answered requests drawn from the seed, the longest
    among them."""
    idx = np.nonzero(answered)[0]
    if len(idx) <= n:
        return idx
    rng = np.random.default_rng((7, int(seed)))
    longest = idx[np.argmax(lengths[idx])]
    pick = rng.choice(idx[idx != longest], size=n - 1, replace=False)
    return np.sort(np.append(pick, longest))


def run(run):
    svc, due, paths = build(run)
    loop = Loop(svc, due, paths)
    before = svc.stats()
    setup_s = time.time() - run.t0
    out = {"setup_s": setup_s}
    run.compiles.mark()
    t0 = time.perf_counter()
    close = t0 + run.seconds
    if run.trace:
        t_trace = t0 + run.seconds / 3
        loop.serve_until(t0, t_trace)
        with run.profiler() as prof:
            loop.serve_until(t0, time.perf_counter()
                             + run.traffic["trace_seconds"], prof)
    loop.serve_until(t0, close + LATE_S)
    run.compiles.close()
    after = svc.stats()
    answered = ~np.isnan(loop.done)
    lat = (loop.done - (t0 + due))[answered]
    out["attempted"] = len(due)
    out["failed"] = int((~answered).sum())
    late = (loop.sent - (t0 + due))[~np.isnan(loop.sent)]
    out["notes"] = {"requests": len(due),
                    "generator_late_p95_ms": float(np.percentile(late, 95)
                                                   * 1e3),
                    "flushes": len(loop.flush_s)}
    if run.trace:
        out["layer"] = {
            "spans": {"flush": loop.flush_s},
            "counters": {
                "padded_steps": after["padded_steps"] - before["padded_steps"],
                "true_steps": after["true_steps"] - before["true_steps"]}}
    elif len(lat):
        out["e2e"] = {"req_p95_ms": float(np.percentile(lat, 95) * 1e3)}
    out["memory_peak_bytes"] = run.memory_peak()
    lengths = np.asarray([len(p) - 1 for p in paths])
    idx = sample(run.seed, lengths, answered, run.traffic["check_rows"])
    got = np.stack([loop.rows[i] for i in idx])
    del svc, loop
    want = reference_rows(run.cfg, paths, idx, run.devices[0])
    out["numbers"] = {"row_err": check.row_error(got, want)}
    return out
