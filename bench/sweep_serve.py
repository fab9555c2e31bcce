"""Find the highest request rate a serve cell sustains without a growing
backlog: one process, one warmed batcher, the cell's open-loop mix at each
rate in turn.

    python3 bench/sweep_serve.py --workload t1_d4n5.serve --seconds 10 \\
        --rates 200 400 600 800

For each rate it prints one JSON line: requests, the share answered by the
close of the window, latency quantiles, and the 50th percentile of the
last third of requests over that of the first third (a backlog that grows
through the window shows as a ratio well above 1).  Needs a TPU, as
``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np


def main(argv=None) -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import harness
    from bench.drivers import serve_open
    from bench.generators import open_loop_schedule, request_paths
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"sweep: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    harness.use_compile_cache()
    run = harness.Run(cell, args.seed, args.seconds, False, devices[:1],
                      time.time())
    svc, _, _ = serve_open.build(run)
    tr = cell.traffic
    for rate in args.rates:
        due, lengths = open_loop_schedule(args.seed, rate, args.seconds,
                                          tr["max_len"], tr["min_len"],
                                          tr["mean_frac"])
        paths = request_paths(args.seed, lengths, cell.cfg["channels"])
        loop = serve_open.Loop(svc, due, paths)
        t0 = time.perf_counter()
        loop.serve_until(t0, t0 + args.seconds)
        on_time = float((~np.isnan(loop.done)).mean())
        loop.serve_until(t0, t0 + args.seconds + serve_open.LATE_S)
        lat = (loop.done - (t0 + due)) * 1e3
        third = len(lat) // 3
        print(json.dumps({
            "rate_per_s": rate, "requests": len(due),
            "answered_by_close": on_time,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "growth": float(np.median(lat[-third:]) / np.median(lat[:third])),
            "flushes": len(loop.flush_s),
            "flush_ms_mean": 1e3 * float(np.mean(loop.flush_s)),
            "device": harness.device_info(devices[:1])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
