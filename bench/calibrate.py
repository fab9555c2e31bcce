"""The readings each limit in ``bench/limits/`` is set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 ... 12 \\
        --control-seeds 1 2 3 [--fault-seeds 1 2 3] [--seconds 5]

One process, on the chip, at the cell's own size.  For each seed it prints
one JSON line of the numbers ``correct`` compares:

- ``program``: the timed path as a run drives it;
- ``control``: the same with the program's own lower-precision path
  switched on (``precision="bf16_fp32"``: increments rounded to bfloat16);
- training cells, ``control:high``: the reference in the program's place
  with its matmuls at ``high`` (three bfloat16 passes) for ``highest``;
- training cells, ``fault:half`` and (on four chips) ``fault:local``: the
  reference put in the program's place with the mean taken over the first
  half of each batch, or over one chip's rows alone, the exchange between
  chips left out.  A step that returns its state unchanged reads 1 on
  ``update_gap`` by construction and needs no run.

A serve cell drives its open-loop mix at the cell's rate for ``--seconds``
per seed.  Needs a TPU, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def train_readings(run, kind: str) -> dict:
    from bench import check
    from bench.drivers import train_closed as tc
    precision = "bf16_fp32" if kind == "control" else "fp32"
    built = tc.build(run, precision=precision)
    batches = tc.first_batches(built)
    model, opt, p0, B = built["model"], built["opt"], built["p0"], built["B"]
    prog = built["readings"]
    del built
    block = run.cfg["ref_block_rows"]
    ref = tc.reference_readings(model, run.cfg, opt, p0, batches, block,
                                run.devices[0])
    if kind == "control:high":
        prog = tc.reference_readings(model, run.cfg, opt, p0, batches, block,
                                     run.devices[0], precision="high")
    if kind.startswith("fault:"):
        rows = B // 2 if kind == "fault:half" else B // len(run.devices)
        prog = tc.reference_readings(model, run.cfg, opt, p0, batches, block,
                                     run.devices[0], rows=rows)
    numbers = check.train_numbers(prog, ref, run.cfg.get("loss_steps", 3))
    numbers["step_loss_gaps"] = [check.loss_gap([a], [b]) for a, b in
                                 zip(prog["losses"], ref["losses"])]
    return numbers


def serve_readings(run, kind: str, services: dict) -> dict:
    import numpy as np
    from bench import check
    from bench.drivers import serve_open as so
    from bench.generators import open_loop_schedule, request_paths
    tr = run.traffic
    if kind not in services:
        precision = "bf16_fp32" if kind == "control" else "fp32"
        services[kind] = so.build(run, precision=precision)[0]
    svc = services[kind]
    due, lengths = open_loop_schedule(run.seed, tr["rate_per_s"], run.seconds,
                                      tr["max_len"], tr["min_len"],
                                      tr["mean_frac"])
    paths = request_paths(run.seed, lengths, run.cfg["channels"])
    loop = so.Loop(svc, due, paths)
    t0 = time.perf_counter()
    loop.serve_until(t0, t0 + run.seconds + so.LATE_S)
    answered = ~np.isnan(loop.done)
    idx = so.sample(run.seed, lengths, answered, tr["check_rows"])
    got = np.stack([loop.rows[i] for i in idx])
    want = so.reference_rows(run.cfg, paths, idx, run.devices[0])
    return {"row_err": check.row_error(got, want),
            "unanswered": int((~answered).sum())}


def main(argv=None) -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs a TPU with the cell's chips", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    serve = cell.traffic["driver"] == "serve_open"
    faults = [] if serve else ["fault:half"] + (
        ["fault:local"] if cell.chips > 1 else [])
    plan = [("program", s) for s in args.seeds]
    plan += [("control", s) for s in args.control_seeds]
    if not serve:
        plan += [("control:high", s) for s in args.control_seeds]
    plan += [(f, s) for f in faults for s in args.fault_seeds]
    services: dict = {}
    for kind, seed in plan:
        run = harness.Run(cell, seed, args.seconds, False,
                          devices[:cell.chips], time.time())
        t = time.time()
        numbers = (serve_readings(run, kind, services) if serve
                   else train_readings(run, kind))
        print(json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                          **numbers, "seconds": time.time() - t}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
