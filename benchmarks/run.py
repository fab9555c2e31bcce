"""Benchmark suite entry point: one module per paper table/figure, plus
the statistical regression gate.

Usage:
    PYTHONPATH=src python -m benchmarks.run            # quick (CI) sizes
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sweeps
    PYTHONPATH=src python -m benchmarks.run --only table1,table2
    PYTHONPATH=src python -m benchmarks.run --emit-metrics
    PYTHONPATH=src python -m benchmarks.run --reruns 3 --compare
    PYTHONPATH=src python -m benchmarks.run --reruns 3 --update-baselines

``--emit-metrics`` enables the :mod:`repro.obs` registry for the run and
writes one metrics snapshot per suite (``BENCH_<suite>_obs.json``, next to
that suite's ``BENCH_*.json``) — so perf numbers always land with their
compile/retrace, plan-cache, and autotune counters attached.

The regression gate (:mod:`repro.obs.baseline`) flattens each suite's
``BENCH_*.json`` into the canonical record schema after every rerun,
aggregates reruns (median value, MAD-widened noise floor), and either
refreshes the committed baselines (``--update-baselines``) or compares
against them (``--compare``), printing a verdict table.  Every suite runs
inside a crash guard, so one broken suite neither hides the others nor
masks a regression verdict.

Exit status is a bitmask CI can split: bit 1 (=1) at least one suite
crashed, bit 2 (=2) at least one metric regressed.

The roofline harness (EXPERIMENTS.md §Roofline, needs 512 placeholder
devices) is separate: ``python -m benchmarks.roofline``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

SUITES = ("table1", "table2", "table3", "fig3", "proj", "gram", "ragged",
          "sessions", "shard")

EXIT_CRASH = 1
EXIT_REGRESSED = 2

_BASELINE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baselines")


def _load_suite_json(mod, t_before: float):
    """The suite's freshly (re)written BENCH json, or None when the suite
    emits no JSON (table2/proj) or didn't write this rerun."""
    path = getattr(mod, "JSON_PATH", None)
    if not path or not os.path.exists(path):
        return None
    if os.path.getmtime(path) < t_before:
        return None             # stale file from an earlier run
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        print(f"# warning: cannot read {path}: {e}", flush=True)
        return None


def _run_once(name, mod, quick: bool, gating: bool) -> None:
    mod.run(quick=quick)
    if gating and name == "table1":
        # the perf-trajectory metrics for table1 are the lever
        # before/afters, written by run_levers, not the engine sweep
        mod.run_levers(quick)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (slow on CPU)")
    ap.add_argument("--only", default="",
                    help=f"comma list from {SUITES} (+ 'fixture')")
    ap.add_argument("--emit-metrics", action="store_true",
                    help="enable repro.obs and write BENCH_<suite>_obs.json "
                         "snapshots (per-suite deltas: the registry resets "
                         "between suites)")
    ap.add_argument("--reruns", type=int, default=1, metavar="K",
                    help="run each suite K times; the gate takes the "
                         "median and derives per-metric noise floors from "
                         "the MAD across reruns")
    ap.add_argument("--compare", action="store_true",
                    help="compare against committed baselines; print a "
                         "verdict table; exit nonzero on any regression")
    ap.add_argument("--update-baselines", action="store_true",
                    help="rewrite benchmarks/baselines/<suite>.json from "
                         "this run")
    ap.add_argument("--baseline-dir", default=_BASELINE_DIR,
                    help="baseline directory (default: "
                         "benchmarks/baselines)")
    ap.add_argument("--verdicts-out", default="",
                    help="also write the verdict rows as JSON (CI "
                         "artifact)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    only = [s.strip() for s in args.only.split(",") if s.strip()] or SUITES
    gating = args.compare or args.update_baselines
    reruns = max(1, args.reruns)

    from repro.obs import baseline

    from . import fig3_windows, fixture_suite, gram_scaling, proj_sparse, \
        ragged_throughput, session_throughput, shard_scaling, \
        table1_runtime, table2_memory, table3_logsig
    mods = {"table1": table1_runtime, "table2": table2_memory,
            "table3": table3_logsig, "fig3": fig3_windows,
            "proj": proj_sparse, "gram": gram_scaling,
            "ragged": ragged_throughput, "sessions": session_throughput,
            "shard": shard_scaling, "fixture": fixture_suite}
    unknown = [s for s in only if s not in mods]
    if unknown:
        ap.error(f"unknown suites {unknown}; choose from {sorted(mods)}")
    if args.emit_metrics:
        from repro import obs
        obs.enable()

    t0 = time.time()
    status: dict[str, str | None] = {}          # suite -> error or None
    collected: dict[str, list] = {}             # suite -> [records per rerun]
    for name in only:
        err = None
        runs = []
        for k in range(reruns):
            if args.emit_metrics:
                obs.reset()   # per-suite deltas, not run-cumulative soup
            t_before = time.time()
            try:
                _run_once(name, mods[name], quick=not args.full,
                          gating=gating)
            except KeyboardInterrupt:
                raise
            except Exception as e:
                err = f"{type(e).__name__}: {e}"
                print(f"# suite {name} rerun {k + 1}/{reruns} CRASHED:",
                      flush=True)
                traceback.print_exc()
                break
            if gating:
                doc = _load_suite_json(mods[name], t_before)
                if doc is not None:
                    recs = baseline.extract_records(name, doc)
                    if recs:
                        runs.append(recs)
        if args.emit_metrics and err is None:
            path = obs.write_snapshot(f"BENCH_{name}_obs.json")
            print(f"# {name}: metrics snapshot -> {path}", flush=True)
        status[name] = err
        if runs:
            collected[name] = runs

    exit_code = 0
    crashed = [n for n, e in status.items() if e]
    if crashed:
        exit_code |= EXIT_CRASH

    current = {name: baseline.aggregate(runs)
               for name, runs in collected.items()}
    if args.update_baselines:
        for name, recs in current.items():
            path = baseline.write_baseline(args.baseline_dir, name, recs,
                                           reruns=reruns)
            print(f"# baseline updated: {path} ({len(recs)} metrics)",
                  flush=True)
    if args.compare:
        baselines = baseline.load_baseline_dir(args.baseline_dir)
        verdicts = baseline.compare(current, baselines)
        print("\n# regression gate "
              f"(reruns={reruns}, baselines: {args.baseline_dir})")
        print(baseline.verdict_table(verdicts))
        if args.verdicts_out:
            with open(args.verdicts_out, "w") as f:
                json.dump({"reruns": reruns, "crashed": crashed,
                           "verdicts": [vars(v) for v in verdicts]},
                          f, indent=1, sort_keys=True)
            print(f"# verdicts -> {args.verdicts_out}", flush=True)
        if baseline.regressions(verdicts):
            exit_code |= EXIT_REGRESSED

    print("\n# suites: " + ", ".join(
        f"{n} {'FAIL' if status[n] else 'ok'}" for n in only), flush=True)
    for n in crashed:
        print(f"#   {n}: {status[n]}", flush=True)
    print(f"# benchmarks done in {time.time() - t0:.0f}s "
          f"(exit {exit_code})", flush=True)
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
