"""One run of one benchmark cell, found by name.

A cell ``<config>.<traffic>`` of ``BENCHMARK.json`` resolves to
``bench/configs/<config>.json`` (sizes, and the model under
``bench/models/``), ``bench/traffic/<traffic>.json`` (the parameters of one
of the general drivers under ``bench/drivers/``), ``bench/limits/<cell>.json``
(the limit of each number compared with the reference) and, for each
per-layer metric, ``bench/metrics/<metric>.py``.  A new cell, mix or metric
is new files and a new entry; no file here names one.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    cell = cells[name]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    bench = root / "bench"
    return SimpleNamespace(
        name=name, chips=cell["chips"], e2e=e2e, per_layer=layer,
        cfg=load_json(bench / "configs" / f"{cell['config']}.json"),
        traffic=load_json(bench / "traffic" / f"{cell['traffic']}.json"),
        limits=load_json(bench / "limits" / f"{name}.json"))


def load_reader(metric: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def use_compile_cache() -> str:
    """JAX's persistent cache at the checkout's fixed ``.jax_cache``, or
    where ``JAX_COMPILATION_CACHE_DIR`` already points JAX."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class Compiles:
    """Counts jaxpr traces and backend compiles (cache hits included)
    between :meth:`mark` and :meth:`close`, from JAX's monitoring events
    and the program's ``pathsig_jit_traces_total`` sites."""
    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        from jax._src import monitoring
        from repro.obs import compile as obs_compile
        self.counts = {"traces": 0, "compiles": 0, "program_traces": 0}
        self.window = None
        self.on = False
        monitoring.register_event_duration_secs_listener(self._event)
        previous = obs_compile._RETRACE_SINK

        def sink(site, key):
            if self.on:
                self.counts["program_traces"] += 1
            if previous is not None:
                previous(site, key)
        obs_compile.set_retrace_sink(sink)

    def _event(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def mark(self):
        self.on = True

    def close(self):
        self.on = False
        self.window = dict(self.counts)


class Profiler:
    """A profiler trace of a short stretch, written under TMPDIR and
    reduced when it stops (``bench/trace_reduce.py``)."""

    def __init__(self):
        self.reduced = None
        self.hlo_texts = []        # compiled programs whose ops it names

    @contextlib.contextmanager
    def __call__(self):
        import jax
        from bench import trace_reduce
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(tmp)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    yield self
            finally:
                jax.profiler.stop_trace()
            self.reduced = trace_reduce.reduce_dir(
                tmp, hlo_texts=self.hlo_texts)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    @staticmethod
    def annotate(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)


@dataclass
class Run:
    cell: SimpleNamespace
    seed: int
    seconds: float
    trace: bool
    devices: list
    t0: float
    compiles: Compiles = None
    profiler: Profiler = field(default_factory=Profiler)
    peaks: dict | None = None      # default: bench/peaks.json's entry

    @property
    def cfg(self):
        return self.cell.cfg

    @property
    def traffic(self):
        return self.cell.traffic

    def memory_peak(self) -> int | None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def execute(run: Run) -> dict:
    """Drive the cell's traffic once; -> the result line's fields."""
    from bench import check
    cell = run.cell
    driver = importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")
    if run.compiles is None:
        run.compiles = Compiles()
    res = driver.run(run)
    device = device_info(run.devices)
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    metrics, breakdown, notes = {}, None, dict(res.get("notes", {}))
    if run.trace:
        red = run.profiler.reduced
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        layer = SimpleNamespace(trace=red, cfg=run.cfg, traffic=run.traffic,
                                peaks=run.peaks or peaks_for(device["kind"]),
                                notes=notes, **res.get("layer", {}))
        for m in cell.per_layer:
            value = load_reader(m["name"]).read(layer)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
    else:
        values = dict(res.get("e2e", {}), setup_s=res["setup_s"])
        for m in cell.e2e:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    checks = {k: {"value": v, "limit": cell.limits[k]}
              for k, v in res["numbers"].items()}
    correct = res["failed"] == 0 and check.verdict(res["numbers"],
                                                   cell.limits)
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["notes"] = dict(notes, setup_s=res["setup_s"],
                        window_compiles=run.compiles.window)
    out["checks"] = checks
    return out


def main(argv=None, t0: float | None = None) -> int:
    import argparse
    t0 = time.time() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
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
    use_compile_cache()
    run = Run(cell, args.seed, args.seconds, bool(args.trace),
              devices[:cell.chips], t0, Compiles())
    out = execute(run)
    print(f"bench: compiles in the window: {out['notes']['window_compiles']}",
          file=sys.stderr)
    print(f"bench: notes {json.dumps(out['notes'])}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"check failed = {out['failed']} (limit 0)", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
