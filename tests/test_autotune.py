"""Persistent per-cell autotuner (repro.kernels.autotune) safety rails.

The cache is an optimisation, never a correctness dependency: corrupt files
degrade to defaults with one warning, invalid modes degrade to ``off``,
traced shapes and jax-engine cells skip the lookup, and the hysteresis rule
guarantees an autotuned cell can never lose to the library default by more
than timing noise.
"""
from __future__ import annotations

import json
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import autotune, ops


@pytest.fixture()
def cache(tmp_path, monkeypatch):
    p = tmp_path / "tune.json"
    monkeypatch.setenv("PATHSIG_AUTOTUNE_CACHE", str(p))
    monkeypatch.setenv("PATHSIG_AUTOTUNE", "load")
    autotune.clear()
    yield p
    autotune.clear()


def _write(p, payload):
    p.write_text(payload if isinstance(payload, str)
                 else json.dumps(payload))
    autotune.clear()


CELL = dict(engine="pallas_interpret", d=3, depth=3, M=100, B=32,
            precision="fp32")


def test_load_mode_returns_cached_record(cache):
    key = autotune.cell_key("sig_trunc", **CELL)
    _write(cache, {"version": 1,
                   "cells": {key: {"batch_tile": 32, "split": 1}}})
    hit = autotune.lookup("sig_trunc", **CELL)
    assert hit["batch_tile"] == 32 and hit["split"] == 1


def test_corrupt_cache_falls_back_to_defaults(cache):
    """Satellite guard: garbage cache -> defaults + ONE warning, no raise."""
    _write(cache, "{not json at all")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert autotune.lookup("sig_trunc", **CELL) == {}
        assert autotune.lookup("sig_trunc", **CELL) == {}  # warned once
    assert sum("corrupt" in str(x.message) for x in w) == 1
    # and the dispatch keeps working end to end on the defaults
    incs = jnp.asarray(np.random.default_rng(0)
                       .standard_normal((4, 9, 2)).astype(np.float32))
    out = ops.signature(incs, 3, backend="pallas_interpret")
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("payload", [
    {"version": 999, "cells": {}},          # wrong version
    {"version": 1, "cells": "nope"},        # wrong cells type
    [1, 2, 3],                              # wrong top-level type
], ids=["version", "cells-type", "top-type"])
def test_wrong_schema_falls_back(cache, payload):
    _write(cache, payload)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert autotune.lookup("sig_trunc", **CELL) == {}


def test_off_mode_never_reads(cache, monkeypatch):
    key = autotune.cell_key("sig_trunc", **CELL)
    _write(cache, {"version": 1, "cells": {key: {"batch_tile": 8}}})
    monkeypatch.setenv("PATHSIG_AUTOTUNE", "off")
    assert autotune.lookup("sig_trunc", **CELL) == {}


def test_invalid_mode_degrades_to_off(cache, monkeypatch):
    monkeypatch.setenv("PATHSIG_AUTOTUNE", "turbo")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert autotune.mode() == "off"


def test_jax_engine_skips_lookup(cache):
    key = autotune.cell_key("sig_trunc", **dict(CELL, engine="jax"))
    _write(cache, {"version": 1, "cells": {key: {"batch_tile": 8}}})
    assert autotune.lookup("sig_trunc", **dict(CELL, engine="jax")) == {}


def test_cell_key_buckets_sizes():
    a = autotune.cell_key("sig_trunc", **dict(CELL, M=100, B=32))
    b = autotune.cell_key("sig_trunc", **dict(CELL, M=128, B=20))
    c = autotune.cell_key("sig_trunc", **dict(CELL, M=129, B=32))
    assert a == b            # 100 and 128 share the pow2 bucket; 20|32 too
    assert a != c            # 129 -> 256
    d_ = autotune.cell_key("sig_trunc", **dict(CELL, d=4))
    assert d_ != a           # structural axes are exact


def test_cell_key_separates_devices(cache, monkeypatch):
    """A record swept on one device is never applied on another: the key
    carries the platform and device kind of the device it was timed on."""
    cell = dict(CELL, engine="pallas")
    cpu = autotune.cell_key("sig_trunc", platform="cpu", device_kind="cpu",
                            **cell)
    tpu = autotune.cell_key("sig_trunc", platform="tpu",
                            device_kind="TPU v5 lite", **cell)
    assert cpu != tpu
    _write(cache, {"version": 1, "cells": {cpu: {"batch_tile": 256}}})
    monkeypatch.setattr(autotune, "_device", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite"})
    assert autotune.lookup("sig_trunc", **cell) == {}
    monkeypatch.setattr(autotune, "_device", lambda: {
        "platform": "cpu", "device_kind": "cpu"})
    assert autotune.lookup("sig_trunc", **cell)["batch_tile"] == 256


def test_hysteresis_keeps_default_within_noise():
    """A non-default candidate must beat the default by >= 10%, so the tuned
    configuration can never lose to the default by more than that margin."""
    default = {"batch_tile": 128, "split": None}
    other = {"batch_tile": 8, "split": None}
    # 5% faster: not enough evidence, default retained
    pick = autotune._pick([(1.00, default), (0.95, other)], default)
    assert pick == default
    # 20% faster: tuned wins
    pick = autotune._pick([(1.00, default), (0.80, other)], default)
    assert pick == other


def test_lookup_through_ops_dispatch(cache):
    """ops.signature with batch_tile=None consults the cache; a cached tile
    must give the same numbers as passing it explicitly."""
    incs = jnp.asarray(np.random.default_rng(0)
                       .standard_normal((4, 9, 3)).astype(np.float32))
    key = autotune.cell_key("sig_trunc", engine="pallas_interpret", d=3,
                            depth=3, M=9, B=4, precision="fp32")
    _write(cache, {"version": 1,
                   "cells": {key: {"batch_tile": 8, "split": 1}}})
    tuned = ops.signature(incs, 3, backend="pallas_interpret")
    explicit = ops.signature(incs, 3, backend="pallas_interpret",
                             batch_tile=8, split=1)
    np.testing.assert_array_equal(np.asarray(tuned), np.asarray(explicit))


def test_sweep_mode_persists_winner(cache, monkeypatch):
    monkeypatch.setenv("PATHSIG_AUTOTUNE", "sweep")
    cell = dict(engine="pallas_interpret", d=2, depth=2, M=6, B=4,
                precision="fp32")
    rec = autotune.lookup("sig_trunc", **cell)
    assert "batch_tile" in rec
    assert rec["platform"] == autotune._device()["platform"]
    saved = json.loads(cache.read_text())
    assert autotune.cell_key("sig_trunc", **cell) in saved["cells"]
    # second lookup is a pure cache hit (load mode suffices)
    monkeypatch.setenv("PATHSIG_AUTOTUNE", "load")
    autotune.clear()
    assert autotune.lookup("sig_trunc", **cell)["batch_tile"] == \
        rec["batch_tile"]


def test_tuned_never_loses_to_default_by_more_than_5pct():
    """Acceptance rail measured, not assumed: time the recorded winner vs
    the default on a sweep cell; the hysteresis rule plus shared timing
    noise keeps any regression under 5%... with CPU-timer slack."""
    import time

    import jax

    cell = dict(engine="pallas_interpret", d=2, depth=3, M=20, B=4,
                precision="fp32")
    rec = autotune.sweep_cell("sig_trunc", cell, repeats=3)
    if not rec:
        pytest.skip("sweep found nothing to tune")
    incs = jnp.asarray(np.random.default_rng(0).standard_normal(
        (cell["B"], cell["M"], cell["d"])).astype(np.float32))

    def med(bt, sp):
        fn = jax.jit(lambda x: ops.signature(
            x, cell["depth"], backend="pallas_interpret", batch_tile=bt,
            split=sp))
        jax.block_until_ready(fn(incs))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(incs))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[2]

    t_tuned = med(rec["batch_tile"], rec.get("split"))
    t_default = med(128, None)
    assert t_tuned <= 1.30 * t_default  # 5% rule + generous CPU-timer noise
