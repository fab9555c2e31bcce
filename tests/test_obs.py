"""repro.obs: metrics registry, span tracer, compile accounting, and the
instrumented stack.

Contracts under test:

1. *Registry semantics*: counters/gauges/histograms with label sets;
   disabled instruments are no-ops; histograms never return NaN
   percentiles; snapshots / Prometheus text / JSONL export round-trip.
2. *Tracer*: spans nest correctly (depth metadata + containment), the
   Chrome-trace file round-trips through ``json.load`` with the expected
   event schema, and disabled mode adds no measurable overhead
   (guard-banded timing).
3. *Compile accounting*: ``instrument_jit`` ticks exactly one counter per
   compiled variant, labelled with the offending shape key; cached calls
   add nothing.
4. *Instrumented stack*: plan-cache eviction ticks the new ``evictions``
   counter without changing results; autotune lookups record outcomes;
   dispatch entries count calls.
5. *SLOs* (``repro.obs.slo``): all three evaluation surfaces (value
   dicts, registry snapshots with group_by, JSONL logs with burn-rate),
   plus the wired health endpoints — ``SessionStore.health()`` flags a
   seeded staleness breach and ``train_loop`` warns/aborts on its
   trailing-window bundle.
6. *Flight recorder* (``repro.obs.flight``): bounded ring, Chrome-trace
   dump contents (spans + retrace keys + exception), single-dump marker
   across nested boundaries, and a crashing ``train_loop`` leaving a
   dump behind.  Plus the bounded trace ring / metric-cardinality guard
   satellites.
"""
import contextlib
import dataclasses
import glob
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import ops


@pytest.fixture(autouse=True)
def clean_registry():
    """Each test starts disabled with zeroed instruments and no active
    trace (module import may have inherited env state)."""
    obs.disable()
    obs.reset()
    if obs.trace_active():
        obs.TRACER._active = False
    obs.TRACER.clear()
    yield
    obs.disable()
    obs.reset()
    obs.TRACER._active = False
    obs.TRACER.clear()


# ---------------------------------------------------------------------------
# 1. registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_label_sets():
    with obs.enabled_scope():
        c = obs.counter("t_requests_total", "x", ("op",))
        c.inc(op="a")
        c.inc(2, op="a")
        c.inc(op="b")
        assert c.value(op="a") == 3.0
        assert c.value(op="b") == 1.0
        assert c.total() == 4.0

        g = obs.gauge("t_depth", "x", ("pool",))
        g.set(7, pool="p")
        g.add(-2, pool="p")
        assert g.value(pool="p") == 5.0

        h = obs.histogram("t_lat_seconds", "x", ("site",))
        for v in (1e-4, 2e-4, 5e-2):
            h.observe(v, site="s")
        assert h.count(site="s") == 3
        assert 0 < h.percentile(50, site="s") < 5e-2


def test_disabled_instruments_are_noops():
    c = obs.counter("t_off_total", "x", ("op",))
    h = obs.histogram("t_off_seconds", "x")
    g = obs.gauge("t_off_gauge", "x")
    c.inc(op="a")
    h.observe(1.0)
    g.set(3.0)
    assert c.total() == 0.0
    assert h.count() == 0
    assert g.value() == 0.0


def test_histogram_empty_percentile_is_zero_not_nan():
    with obs.enabled_scope():
        h = obs.histogram("t_empty_seconds", "x", ("site",))
        p = h.percentile(50, site="never_observed")
        assert p == 0.0 and not np.isnan(p)


def test_metric_type_conflict_raises():
    with obs.enabled_scope():
        obs.counter("t_conflict", "x", ("a",))
        with pytest.raises(ValueError, match="already registered"):
            obs.gauge("t_conflict", "x", ("a",))
        with pytest.raises(ValueError, match="already registered"):
            obs.counter("t_conflict", "x", ("b",))


def test_missing_label_raises():
    with obs.enabled_scope():
        c = obs.counter("t_labels_total", "x", ("op", "backend"))
        with pytest.raises(ValueError, match="missing"):
            c.inc(op="a")


def test_snapshot_prometheus_and_jsonl_roundtrip(tmp_path):
    with obs.enabled_scope():
        obs.counter("t_snap_total", "help text", ("op",)).inc(3, op="sig")
        obs.histogram("t_snap_seconds", "h", ()).observe(0.01)
        snap = obs.snapshot()
        assert snap["metrics"]["t_snap_total"]["type"] == "counter"
        assert snap["metrics"]["t_snap_total"]["values"][0] == {
            "labels": {"op": "sig"}, "value": 3.0}

        p = obs.write_snapshot(str(tmp_path / "snap.json"))
        assert json.load(open(p))["metrics"]["t_snap_total"]["values"]

        jl = str(tmp_path / "snap.jsonl")
        obs.append_jsonl(jl, extra={"suite": "x"})
        obs.append_jsonl(jl)
        lines = [json.loads(ln) for ln in open(jl)]
        assert len(lines) == 2 and lines[0]["suite"] == "x"

        text = obs.to_prometheus()
        assert "# TYPE t_snap_total counter" in text
        assert 't_snap_total{op="sig"} 3.0' in text
        assert "t_snap_seconds_bucket" in text
        assert "t_snap_seconds_count 1" in text


def test_collector_runs_at_snapshot_time():
    calls = []
    reg = obs.Registry(enabled=True)
    reg.register_collector(lambda r: calls.append(1) or r.gauge(
        "t_pulled", "x").set(42.0))
    snap = reg.snapshot()
    assert calls == [1]
    assert snap["metrics"]["t_pulled"]["values"][0]["value"] == 42.0


def test_jsonl_sink_appends_per_call(tmp_path):
    sink = obs.jsonl_sink(str(tmp_path / "run.jsonl"))
    sink(0, {"loss": 1.5})
    sink(10, {"loss": 0.5, "straggler": True})
    lines = [json.loads(ln) for ln in open(sink.path)]
    assert [ln["step"] for ln in lines] == [0, 10]
    assert lines[1]["straggler"] is True


# ---------------------------------------------------------------------------
# 2. tracer
# ---------------------------------------------------------------------------

def test_spans_nest_and_chrome_trace_roundtrips(tmp_path):
    path = str(tmp_path / "trace.json")
    with obs.trace_scope(path):
        with obs.span("outer", layer="serve"):
            time.sleep(0.002)
            with obs.span("inner"):
                time.sleep(0.001)
        obs.instant("marker", n=1)
    doc = json.load(open(path))
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert {"outer", "inner", "marker"} <= set(evs)
    # schema: complete events carry ph/ts/dur/pid/tid/args
    for name in ("outer", "inner"):
        e = evs[name]
        assert e["ph"] == "X"
        assert {"ts", "dur", "pid", "tid", "args"} <= set(e)
    assert evs["marker"]["ph"] == "i"
    # nesting: depth metadata + interval containment on one track
    assert evs["outer"]["args"]["depth"] == 0
    assert evs["inner"]["args"]["depth"] == 1
    assert evs["outer"]["ts"] <= evs["inner"]["ts"]
    assert (evs["inner"]["ts"] + evs["inner"]["dur"]
            <= evs["outer"]["ts"] + evs["outer"]["dur"] + 1.0)
    assert evs["outer"]["args"]["layer"] == "serve"


def test_spans_nest_under_jit_boundaries(tmp_path):
    """Spans opened around and inside (at trace time) a jit call keep
    consistent nesting — the inner span is emitted at trace time only."""
    path = str(tmp_path / "trace.json")

    def f(x):
        with obs.span("jit.body"):
            return x * 2

    jf = jax.jit(f)
    x = jnp.ones(4)
    with obs.trace_scope(path):
        with obs.span("call.outer"):
            jf(x).block_until_ready()      # compiles: body span emitted
        with obs.span("call.cached"):
            jf(x).block_until_ready()      # cached: no new body span
    evs = json.load(open(path))["traceEvents"]
    body = [e for e in evs if e["name"] == "jit.body"]
    outer = [e for e in evs if e["name"] == "call.outer"]
    assert len(body) == 1 and len(outer) == 1
    assert body[0]["args"]["depth"] == outer[0]["args"]["depth"] + 1


def test_disabled_tracing_adds_no_measurable_overhead():
    """Guard-banded absolute bound: a disabled span costs well under 10us
    per entry (typical ~0.5us — one flag check + the null-span singleton).
    The generous band absorbs CI scheduling noise."""
    N = 20_000

    def instrumented():
        t0 = time.perf_counter()
        acc = 0
        for i in range(N):
            with obs.span("hot"):
                acc += i
        return time.perf_counter() - t0

    assert not obs.trace_active() and not obs.enabled()
    instrumented()   # warm
    per_span = min(instrumented() for _ in range(5)) / N
    assert per_span < 10e-6, f"{per_span * 1e6:.2f}us per disabled span"


def test_null_span_supports_set():
    s = obs.span("inactive", a=1)
    assert s.set(b=2) is s
    with s:
        pass


def _profile(tmp_path, fn):
    """Run ``fn`` under a ``jax.profiler`` session -> its ProfileData."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    return ProfileData.from_file(path)


@contextlib.contextmanager
def _nothing_records():
    """Neither the trace file nor the flight ring records spans."""
    was = obs.flight_active()
    obs.disable_flight()
    try:
        assert not obs.trace_active() and not obs.TRACER._record
        yield
    finally:
        if was:
            obs.enable_flight()


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    """With nothing in the process recording spans, a span is still a host
    event of any profiler session, on the device ops' clock: its interval
    holds the ops of the jitted call it waits for."""
    def wrapped(x):
        return jnp.sin(x) @ x

    f = jax.jit(wrapped)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()

    def work():
        with obs.span("serve.test.call", rows=3) as s:
            f(x).block_until_ready()
            assert s.set(done=1) is s

    with _nothing_records():
        pd = _profile(tmp_path, work)
    spans, ops = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                st = dict(ev.stats)
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == "serve.test.call":
                    spans.append((plane.name, iv, st))
                elif st.get("hlo_module") == "jit_wrapped":
                    ops.append(iv)
    assert len(spans) == 1
    plane, (t0, t1), st = spans[0]
    assert plane.startswith("/host:")
    assert st["rows"] == 3 and st["done"] == 1
    assert ops and all(t0 <= a and b <= t1 for a, b in ops)


def test_batcher_flush_idle_gaps_are_named_by_its_spans(tmp_path):
    """The device's idle time inside a ``DynamicBatcher.flush()`` is named
    by the batcher's own phase spans (``bench/program_trace.py``)."""
    from bench import program_trace
    from repro.serve import DynamicBatcher
    db = DynamicBatcher.signature_service(2, 2, max_len=16, backend="jax",
                                          min_bucket=4, max_batch=8)
    rng = np.random.default_rng(0)

    def submit():
        for L in (3, 7, 5, 12, 2, 16):
            db.submit(np.cumsum(rng.normal(size=(L + 1, 2)).astype(
                np.float32), axis=0))
    submit()
    db.flush()                                  # compiles every rung
    submit()

    def work():
        with jax.profiler.TraceAnnotation("bench.window"):
            db.flush()

    with _nothing_records():
        red = program_trace.reduce(_profile(tmp_path, work))
    gaps = dict(red["program_gaps"])
    named = {n: v for n, v in gaps.items()
             if n.startswith("serve.batcher.")}
    assert {"serve.batcher.pack", "serve.batcher.rows"} <= set(named), gaps
    assert sum(named.values()) >= 0.75 * sum(gaps.values()), gaps


def test_span_prefixes_cover_the_program_spans():
    """Every span the program opens is one that ``bench/program_trace.py``
    names idle gaps by, and its copy of the prefixes is the same."""
    from pathlib import Path

    from bench import program_trace
    from repro.obs.trace import SPAN_PREFIXES
    assert program_trace.SPAN_PREFIXES == SPAN_PREFIXES
    src = Path(obs.__file__).resolve().parents[1]
    literal = re.compile(r"""\bspan\(\s*f?(["'])(.*?)\1""")
    names = [m[1] for path in sorted(src.rglob("*.py"))
             for m in literal.findall(path.read_text())]
    assert len(names) >= 8, names
    assert all(n.startswith(SPAN_PREFIXES) for n in names), names


# ---------------------------------------------------------------------------
# 3. compile accounting
# ---------------------------------------------------------------------------

def test_shape_key_describes_arrays_and_pytrees():
    x = jnp.zeros((4, 10, 3), jnp.float32)
    key = obs.shape_key(x, depth=3, split=None)
    assert "f32[4,10,3]" in key and "depth=3" in key

    key2 = obs.shape_key({"a": x, "b": [x, x]})
    assert "a:f32[4,10,3]" in key2


def test_instrument_jit_counts_one_trace_per_variant():
    with obs.enabled_scope():
        calls = []

        def f(x):
            calls.append(1)
            return x + 1

        jf = obs.instrument_jit(f, site="t_site")
        x4 = jnp.zeros(4)
        x8 = jnp.zeros(8)
        jf(x4); jf(x4); jf(x4)          # one compile, three calls
        jf(x8)                           # second variant
        c = obs.REGISTRY.get(obs.TRACE_COUNTER_NAME)
        by_shape = {row["labels"]["shapes"]: row["value"]
                    for row in c._values_list()
                    if row["labels"]["site"] == "t_site"}
        assert by_shape == {"f32[4]": 1.0, "f32[8]": 1.0}
        assert len(calls) == 2           # python body ran once per variant


def test_count_trace_is_noop_when_disabled():
    obs.count_trace("t_disabled", jnp.zeros(3))
    assert obs.REGISTRY.get(obs.TRACE_COUNTER_NAME) is None or not [
        r for r in obs.REGISTRY.get(
            obs.TRACE_COUNTER_NAME)._values_list()
        if r["labels"]["site"] == "t_disabled"]


# ---------------------------------------------------------------------------
# 4. instrumented stack
# ---------------------------------------------------------------------------

def test_plan_cache_eviction_ticks_counter_without_changing_results(rng=None):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(3, 8, 2)).astype(np.float32))
    # two distinct word sets, alternated: under maxsize=1 each revisit evicts
    sets = [((0,),), ((0,), (1,)), ((0,),), ((0,), (1,))]
    try:
        ref = [np.asarray(ops.projected(x, w, backend="jax")) for w in sets]
        ops.set_plan_cache_maxsize(1)      # zeroes counters, bound=1
        got = [np.asarray(ops.projected(x, w, backend="jax")) for w in sets]
        info = ops.plan_cache_info()["_plan_for_words"]
        assert info.evictions >= 1, info   # alternating keys under maxsize=1
        assert info.misses >= 3, info
        assert info.maxsize == 1 and info.currsize <= 1
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
    finally:
        ops.set_plan_cache_maxsize(256)


def test_bounded_cache_eviction_counter():
    cache = ops.BoundedCache("t_obs_cache")
    try:
        ops.set_plan_cache_maxsize(2)
        for k in range(4):
            cache.get(k, lambda: k)
        info = cache.info()
        assert info.evictions == 2 and info.currsize == 2, info
        assert ops.plan_cache_info()["t_obs_cache"].evictions == 2
    finally:
        ops.set_plan_cache_maxsize(256)


def test_plan_cache_collector_publishes_gauges():
    with obs.enabled_scope():
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 6, 2)).astype(np.float32))
        ops.projected(x, ((0,), (0, 1)), backend="jax")
        snap = obs.snapshot()
        rows = snap["metrics"]["pathsig_plan_cache"]["values"]
        stats = {(r["labels"]["cache"], r["labels"]["stat"]): r["value"]
                 for r in rows}
        assert any(k[1] == "misses" and v > 0 for k, v in stats.items())
        assert ("_pallas_sig_inverse", "evictions") in stats


def test_dispatch_call_counter_and_autotune_outcomes():
    with obs.enabled_scope():
        x = jnp.asarray(np.random.default_rng(1).normal(
            size=(2, 6, 2)).astype(np.float32))
        ops.signature(x, 2, backend="pallas_interpret")
        ops.signature(x, 2, backend="pallas_interpret")
        calls = obs.REGISTRY.get("pathsig_dispatch_calls_total")
        assert calls.value(op="signature", backend="pallas_interpret",
                           ctx="eager") == 2.0
        lookups = obs.REGISTRY.get("pathsig_autotune_lookups_total")
        assert lookups is not None and lookups.total() >= 2


def test_kernel_retrace_counter_under_repeat_calls():
    with obs.enabled_scope():
        x = jnp.asarray(np.random.default_rng(2).normal(
            size=(2, 5, 2)).astype(np.float32))
        for _ in range(3):
            ops.signature(x, 2, backend="pallas_interpret")
        c = obs.REGISTRY.get(obs.TRACE_COUNTER_NAME)
        sig_rows = [r for r in c._values_list()
                    if r["labels"]["site"] == "sig_trunc"
                    and "f32[2,5,2]" in r["labels"]["shapes"]]
        # three identical calls -> at most one fresh compile of this cell
        assert sum(r["value"] for r in sig_rows) <= 1.0, sig_rows


def test_dispatch_disabled_is_bitwise_transparent():
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(2, 7, 3)).astype(np.float32))
    a = np.asarray(ops.signature(x, 3, backend="jax"))
    with obs.enabled_scope():
        obs.start_trace(None)
        b = np.asarray(ops.signature(x, 3, backend="jax"))
        obs.TRACER._active = False
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# 5. SLOs
# ---------------------------------------------------------------------------

def test_slo_evaluate_values_surfaces():
    slos = (obs.Slo("lat", "p99_s", 0.5),
            obs.Slo("thr", "rate", 10.0, op=">="),
            obs.Slo("ghost", "absent", 1.0))
    res = obs.evaluate_values(slos, {"p99_s": 0.7, "rate": 50.0})
    by = {r.slo.name: r for r in res}
    assert by["lat"].breached and by["lat"].observed == 0.7
    assert by["thr"].status == "ok"
    assert by["ghost"].status == "no_data" and not by["ghost"].breached
    # non-finite observations always breach
    (nan_r,) = obs.evaluate_values((obs.Slo("l", "v", 1.0),),
                                   {"v": float("nan")})
    assert nan_r.breached and nan_r.detail == "non-finite"
    rep = obs.slo.report(res)
    assert rep["status"] == "breach" and rep["breaches"] == ["lat"]
    assert json.loads(json.dumps(rep))["results"][0]["name"] == "lat"


def test_slo_bad_spec_raises():
    with pytest.raises(ValueError, match="op"):
        obs.Slo("x", "m", 1.0, op="!=")
    with pytest.raises(ValueError, match="reducer"):
        obs.Slo("x", "m", 1.0, reducer="p75")


def test_slo_evaluate_snapshot_group_by_worst():
    with obs.enabled_scope():
        c = obs.counter("t_retrace_total", "x", ("site",))
        c.inc(2, site="quiet")
        c.inc(40, site="noisy")
        h = obs.histogram("t_lat_s", "x")
        for v in [0.01] * 90 + [2.0] * 10:      # 10% tail -> p99 in the tail
            h.observe(v)
        snap = obs.snapshot()
    slos = (obs.Slo("budget", "t_retrace_total", 32, reducer="sum",
                    group_by="site"),
            obs.Slo("p99", "t_lat_s", 0.5, reducer="p99"),
            obs.Slo("absent", "t_nope", 1.0))
    by = {r.slo.name: r for r in obs.evaluate_snapshot(slos, snap)}
    assert by["budget"].breached and by["budget"].detail == "site=noisy"
    assert by["budget"].observed == 40.0        # the worst group, not the sum
    assert by["p99"].breached                   # p99 caught the tail
    assert by["absent"].status == "no_data"


def test_slo_evaluate_log_burn_rate(tmp_path):
    rows = [{"sec": 0.01} for _ in range(90)] + \
           [{"sec": 5.0} for _ in range(10)]
    budget = obs.Slo("steps", "sec", 1.0, budget=0.05)   # 5% allowed
    (r,) = obs.evaluate_log((budget,), rows, window=100)
    assert r.breached and r.burn_rate == pytest.approx(2.0)  # 10% / 5%
    (ok,) = obs.evaluate_log((obs.Slo("steps", "sec", 1.0, budget=0.2),),
                             rows, window=100)
    assert ok.status == "ok" and ok.burn_rate == pytest.approx(0.5)
    # trailing window drops old violations
    (w,) = obs.evaluate_log((budget,), rows[:95], window=5)
    assert w.breached                      # window is all-violating tail
    path = tmp_path / "log.jsonl"
    path.write_text("\n".join(json.dumps(x) for x in rows) + "\nnot json\n")
    (f,) = obs.evaluate_log((budget,), str(path), window=100)
    assert f.breached and "violating" in f.detail


def test_session_store_health_flags_staleness_breach():
    from repro.serve.sessions import SessionStore
    store = SessionStore(d=2, depth=2, initial_sessions=4)
    h = store.health()
    assert h["status"] == "ok"
    # seed the staleness window with a breach of the 0.25 s default
    store._staleness.extend([1.0] * 100)
    h = store.health()
    assert h["status"] == "breach"
    assert "sessions_p99_staleness" in h["breaches"]
    # custom bundle overrides the default
    h2 = store.health(slos=(obs.Slo("lax", "p99_staleness_s", 10.0),))
    assert h2["status"] == "ok"


def test_batcher_health_and_flush_latency_stats():
    from repro.serve import DynamicBatcher
    db = DynamicBatcher.signature_service(2, 2, max_len=16, backend="jax",
                                          min_bucket=4, max_batch=8)
    rng = np.random.default_rng(0)
    for L in (3, 7, 5):
        db.submit(np.cumsum(rng.normal(size=(L + 1, 2)).astype(np.float32),
                            axis=0))
    db.flush()
    st = db.stats()
    assert st["flushes_recorded"] == 1 and st["flush_p99_s"] > 0
    assert db.health()["status"] == "ok"
    h = db.health(slos=(obs.Slo("tight", "flush_p99_s", 1e-12),))
    assert h["status"] == "breach" and h["breaches"] == ["tight"]


def _tiny_train_cfg():
    from repro.configs import get_config, reduce_config
    return dataclasses.replace(reduce_config(get_config("qwen3-4b")),
                               n_layers=1, d_model=32, n_heads=2,
                               n_kv_heads=2, head_dim=16, d_ff=64,
                               vocab_size=64)


def _tiny_train(loop, steps_seen=None):
    import repro.models as M
    from repro.data.pipeline import TokenStream
    from repro.optim import adamw
    from repro.train import train_loop
    cfg = _tiny_train_cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    it = iter(TokenStream(64, 2, 8, seed=0))
    if steps_seen is not None:
        base = it

        def counting():
            for b in base:
                steps_seen.append(1)
                yield b
        it = counting()
    return train_loop(cfg, params, adamw(lr=1e-3), it, loop)


@pytest.mark.slow
def test_train_loop_slo_warn_and_abort(tmp_path, monkeypatch):
    from repro.train import TrainLoopConfig
    monkeypatch.setenv("PATHSIG_FLIGHT_DIR", str(tmp_path))
    # impossible p99 budget in warn mode: completes, but warns
    loop = TrainLoopConfig(steps=3, log_every=1, run_dir="",
                           slos=obs.train_slos(step_p99_s=1e-9))
    with pytest.warns(UserWarning, match="SLO breach"):
        _, _, hist = _tiny_train(loop)
    assert len(hist) == 3                       # run was not aborted
    # abort mode raises SloBreach and leaves a flight dump behind
    calls = []
    loop = TrainLoopConfig(steps=3, log_every=1, run_dir="",
                           slos=obs.train_slos(step_p99_s=1e-9),
                           slo_action="abort",
                           slo_callback=lambda s, rep: calls.append(rep))
    with pytest.raises(obs.SloBreach, match="train_step_p99"):
        _tiny_train(loop)
    assert calls and calls[0]["status"] == "breach"
    dumps = list(tmp_path.glob("flight_*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert doc["otherData"]["exception"]["type"] == "SloBreach"
    # healthy budgets: silent, full history
    loop = TrainLoopConfig(steps=3, log_every=1, run_dir="",
                           slos=obs.train_slos())
    _, _, hist = _tiny_train(loop)
    assert len(hist) == 3


# ---------------------------------------------------------------------------
# 6. flight recorder + bounded-ring satellites
# ---------------------------------------------------------------------------

def test_flight_ring_is_bounded():
    from repro.obs.flight import FlightRecorder
    fl = FlightRecorder(capacity=8, retrace_keys=2)
    for i in range(20):
        fl.record_span(f"s{i}", 0.0, 1.0, 0, None)
        fl.record_retrace("site", f"k{i}")
    assert len(fl) == 8
    doc = fl.to_chrome()
    assert [e["name"] for e in doc["traceEvents"]] == \
        [f"s{i}" for i in range(12, 20)]        # most-recent survive
    assert [r["shapes"] for r in doc["otherData"]["retrace_keys"]] == \
        ["k18", "k19"]


def test_flight_dump_contents_and_metric_series(tmp_path):
    from repro.obs.flight import FlightRecorder
    fl = FlightRecorder(capacity=32)
    fl.record_span("serve.flush", 1.0, 1.5, 0, {"rungs": 2})
    fl.record_instant("evict", {"sid": "a"})
    fl.record_metric("counter", "t_total", {"op": "x"}, 3.0)
    fl.record_retrace("sig_trunc", "f32[2,5,2]")
    try:
        raise ValueError("boom")
    except ValueError as e:
        p = fl.dump(str(tmp_path / "f.json"), exc=e, note="unit")
    doc = json.load(open(p))
    evs = {e["ph"]: e for e in doc["traceEvents"]}
    assert evs["X"]["name"] == "serve.flush" and evs["X"]["dur"] == 5e5
    assert evs["X"]["args"]["rungs"] == 2
    assert evs["i"]["args"] == {"sid": "a"}
    assert evs["C"]["name"] == "t_total{op=x}"      # labelled series
    other = doc["otherData"]
    assert other["note"] == "unit"
    assert other["retrace_keys"][0]["site"] == "sig_trunc"
    assert other["exception"]["type"] == "ValueError"
    assert "boom" in other["exception"]["traceback"]
    assert fl.dumps == 1


def test_spans_feed_flight_even_without_active_trace(tmp_path):
    """The always-on path: no start_trace, yet obs.span lands in the
    flight ring (this is what makes post-mortem dumps non-empty)."""
    from repro.obs.flight import FlightRecorder, disable_flight, \
        enable_flight
    fl = FlightRecorder(capacity=16)
    enable_flight(fl)
    try:
        assert not obs.trace_active()
        with obs.span("quiet.work", k=1):
            pass
        obs.instant("quiet.mark")
        # metric deltas mirror only when the registry is enabled
        with obs.enabled_scope():
            obs.counter("t_flight_total", "x").inc(2)
        names = [e[1] for e in fl._ring]
        assert "quiet.work" in names and "quiet.mark" in names
        assert "t_flight_total" in names
        # ...and the trace-file buffer stayed empty
        assert obs.TRACER.events == []
    finally:
        disable_flight()
        obs.enable_flight()                     # restore module default


def test_dump_on_error_dumps_once_across_nested_boundaries(tmp_path,
                                                           monkeypatch):
    monkeypatch.setenv("PATHSIG_FLIGHT_DIR", str(tmp_path))
    with pytest.raises(RuntimeError, match="inner"):
        with obs.dump_on_error("outer.site"):
            with obs.dump_on_error("inner.site"):
                raise RuntimeError("inner boom")
    dumps = list(tmp_path.glob("flight_*.json"))
    assert len(dumps) == 1                      # marker stopped the second
    doc = json.load(open(dumps[0]))
    assert doc["otherData"]["note"] == "inner.site"
    assert doc["otherData"]["exception"]["message"] == "inner boom"


@pytest.mark.slow
def test_train_loop_crash_leaves_flight_dump(tmp_path, monkeypatch):
    """Acceptance: an induced train_loop exception produces a flight dump
    holding the last-N spans and the exception."""
    from repro.train import TrainLoopConfig
    monkeypatch.setenv("PATHSIG_FLIGHT_DIR", str(tmp_path))
    obs.FLIGHT.clear()

    def dying_iter():
        from repro.data.pipeline import TokenStream
        it = iter(TokenStream(64, 2, 8, seed=0))
        yield next(it)
        yield next(it)
        raise RuntimeError("data pipeline died")

    import repro.models as M
    from repro.optim import adamw
    from repro.train import train_loop
    cfg = _tiny_train_cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    loop = TrainLoopConfig(steps=5, log_every=1, run_dir="")
    with pytest.raises(RuntimeError, match="data pipeline died"):
        train_loop(cfg, params, adamw(lr=1e-3), dying_iter(), loop)
    dumps = list(tmp_path.glob("flight_*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    other = doc["otherData"]
    assert other["exception"]["type"] == "RuntimeError"
    assert "data pipeline died" in other["exception"]["message"]
    spans = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and e["name"] == "train.step"]
    assert len(spans) >= 2                      # the completed steps


def test_sigusr2_dumps_live_ring(tmp_path, monkeypatch):
    import signal
    from repro.obs import flight
    if not flight._SIG_INSTALLED:
        pytest.skip("SIGUSR2 hook not installed in this process")
    monkeypatch.setenv("PATHSIG_FLIGHT_DIR", str(tmp_path))
    with obs.span("pre.signal"):
        pass
    os.kill(os.getpid(), signal.SIGUSR2)
    dumps = list(tmp_path.glob("flight_*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert doc["otherData"]["note"] == "SIGUSR2"
    assert any(e["name"] == "pre.signal" for e in doc["traceEvents"])


def test_trace_ring_bounds_events_and_counts_drops():
    from repro.obs.trace import DROP_COUNTER_NAME, Tracer
    t = Tracer(max_events=3)
    t.start()
    with obs.enabled_scope():
        for i in range(8):
            with t.span(f"s{i}"):
                pass
        assert len(t.events) == 3
        assert [e["name"] for e in t.events] == ["s5", "s6", "s7"]
        assert t.dropped == 5
        assert obs.counter(DROP_COUNTER_NAME, "x").value() == 5.0
    t.stop()
    t.clear()
    assert t.dropped == 0
    # env sizing is honoured (and clamped to >= 1)
    os.environ["PATHSIG_TRACE_MAX_EVENTS"] = "2"
    try:
        assert Tracer()._max_events == 2
    finally:
        del os.environ["PATHSIG_TRACE_MAX_EVENTS"]


def test_metric_label_cardinality_guard():
    from repro.obs.metrics import CARDINALITY_DROP_COUNTER, Registry
    reg = Registry(enabled=True, max_label_sets=3)
    c = reg.counter("t_wild_total", "x", ("rid",))
    with pytest.warns(UserWarning, match="cardinality"):
        for i in range(10):
            c.inc(rid=f"r{i}")
    assert len(c._values) == 3                  # capped
    assert c.value(rid="r0") == 1.0 and c.value(rid="r9") == 0.0
    drops = reg.counter(CARDINALITY_DROP_COUNTER, "x", ("metric",))
    assert drops.value(metric="t_wild_total") == 7.0
    # existing label sets keep updating after the cap
    c.inc(rid="r1")
    assert c.value(rid="r1") == 2.0
    # histograms share the guard
    h = reg.histogram("t_wild_seconds", "x", ("rid",))
    with pytest.warns(UserWarning, match="cardinality"):
        for i in range(6):
            h.observe(0.1, rid=f"r{i}")
    assert h.count(rid="r5") == 0 and h.count(rid="r0") == 1
    # reset clears values and re-arms the warn-once
    reg.reset()
    assert not c._values and not c._card_warned
