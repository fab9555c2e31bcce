"""The program's own names in a profiler trace: the device scopes it sets
and its host spans, which ``bench/trace_reduce.py`` does not read.

    python3 -m bench.program_trace --workload <train cell> --seed <n>

runs a training cell's traced stretch, as ``bench/run.py --trace 1`` does,
and prints one JSON line: the device time a step under each ``pathsig.*``
scope, in ms, beside the run's per-layer metrics.  Needs a TPU, as the
harness does.  From a trace:

- program scopes: each device operation's self time inside the traced
  window, counted as ``trace_reduce`` counts it and averaged over the
  devices, goes to every ``pathsig.*`` name found anywhere in its compiled
  ``op_name``, so ``transpose(jvp(pathsig.proj.index))`` counts.  It is
  inclusive: ``pathsig.bwd.reconstruct`` is part of ``pathsig.bwd``.  A
  program that sets no scope reads ``{}``.
- program gaps: the window's idle gaps on the first device, named by the
  innermost host event open at their midpoint among the ``bench.*``
  annotations and the program's spans (names under ``SPAN_PREFIXES``, a
  copy of ``repro.obs.trace.SPAN_PREFIXES``).
"""
from __future__ import annotations

import collections
import contextlib
import json
import os
import re
import shutil
import sys
import tempfile
import time

from bench import harness, trace_reduce

SPAN_PREFIXES = ("kernels.", "serve.", "train.")
PROGRAM_SCOPE = re.compile(r"pathsig(?:\.\w+)+")


def _host_events(pd, prefixes):
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return out


def reduce(pd, hlo_texts=None, top: int = 10) -> dict:
    """-> ``program_scopes`` {scope: seconds} and ``program_gaps``."""
    notes = _host_events(pd, ("bench.",) + SPAN_PREFIXES)
    windows = [n for n in notes if n[2] == trace_reduce.WINDOW]
    if not windows:
        raise ValueError(f"no {trace_reduce.WINDOW!r} annotation in the trace")
    w0, w1, _ = max(windows, key=lambda n: n[1] - n[0])
    inner = [n for n in notes if n[2] != trace_reduce.WINDOW]
    devices = trace_reduce._device_ops(
        pd, trace_reduce.hlo_op_names(hlo_texts))
    if not devices:
        raise ValueError("no device operations in the trace")
    scopes = collections.Counter()
    gaps = collections.Counter()
    for k, evs in enumerate(devices.values()):
        evs = sorted(((max(s, w0), min(e, w1), n, p) for s, e, n, p in evs
                      if e > w0 and s < w1), key=lambda v: (v[0], -v[1]))
        for (_, _, _, path), dt in zip(evs, trace_reduce._self_times(evs)):
            for scope in set(PROGRAM_SCOPE.findall(path)):
                scopes[scope] += dt
        if k == 0:
            busy = trace_reduce._union([(s, e) for s, e, _, _ in evs])
            edges = [w0] + [x for iv in busy for x in iv] + [w1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b <= a:
                    continue
                mid = (a + b) / 2
                live = [n for n in inner if n[0] <= mid <= n[1]]
                who = min(live, key=lambda n: n[1] - n[0])[2] if live \
                    else trace_reduce.WINDOW
                gaps[who] += b - a
    n = len(devices)
    return {"program_scopes": {k: v / n * 1e-9
                               for k, v in sorted(scopes.items())},
            "program_gaps": [[name, v * 1e-9]
                             for name, v in gaps.most_common(top)]}


class Profiler(harness.Profiler):
    """The harness's profiler, which also reduces the program's names from
    the trace before the trace is removed."""

    def __init__(self):
        super().__init__()
        self.program = None

    @contextlib.contextmanager
    def __call__(self):
        import jax
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(tmp)
            try:
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                    yield self
            finally:
                jax.profiler.stop_trace()
            pd = trace_reduce._load(trace_reduce.find_xplane(tmp))
            self.reduced = trace_reduce.reduce(pd, hlo_texts=self.hlo_texts)
            self.program = reduce(pd, hlo_texts=self.hlo_texts)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    import argparse
    t0 = time.time()
    ap = argparse.ArgumentParser(
        description="Device time a step under each of the program's scopes, "
                    "from one traced run of a training cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    steps = cell.traffic["trace_steps"]
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    prof = Profiler()
    run = harness.Run(cell, args.seed, args.seconds, True,
                      devices[:cell.chips], t0, harness.Compiles(),
                      profiler=prof)
    out = harness.execute(run)
    red, scopes = prof.reduced, prof.program["program_scopes"]
    phases = scopes.get("pathsig.fwd", 0.0) + scopes.get("pathsig.bwd", 0.0)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "correct": out["correct"], "steps": steps,
        "scope_ms": {k: v * 1e3 / steps for k, v in scopes.items()},
        "bench_sig_ms": red["scope_s"] * 1e3 / steps,
        "phases_of_bench_sig_pct": (100.0 * phases / red["scope_s"]
                                    if red["scope_s"] > 0 else None),
        "program_gaps": prof.program["program_gaps"],
        "metrics": out["metrics"], "device": out["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    # libtpu logs under this run's own TMPDIR, not a path shared by runs
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.exit(main())
