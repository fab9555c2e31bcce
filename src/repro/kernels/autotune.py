"""Persistent per-cell autotuner for the Pallas dispatch layer.

The kernels' tile shapes (``batch_tile``, the level ``split`` of the cone
kernel, the Gram block shapes) are workload-dependent.  This module keeps a
small JSON cache of measured winners keyed by dispatch *cell* — (kind, d,
depth, pow2-bucketed M and B, engine, precision) and by the device the
winner was timed on (``platform``, ``device_kind``), so a record swept on
one device is never applied on another — which :mod:`repro.kernels.ops`
consults whenever the caller does not pass an explicit tile.  A sweep times
the engine the cell names: ``pallas`` compiled on the TPU,
``pallas_interpret`` in interpret mode.

Environment control (read per call, so tests can monkeypatch):

``PATHSIG_AUTOTUNE``
    ``off``   — never consult or write the cache: library defaults.
    ``load``  — (default) consult the cache, never measure.
    ``sweep`` — consult the cache; on a miss, measure the candidate grid for
    that cell once, persist the winner, and use it from then on.

``PATHSIG_AUTOTUNE_CACHE``
    Cache file path (default ``.pathsig_autotune.json`` in the CWD).

Safety rails:

* the library default configuration is ALWAYS a sweep candidate, and a
  non-default winner is only recorded when it beats the default by >= 10%
  (hysteresis) — so an autotuned cell can never lose to the default by more
  than timing noise;
* a corrupt / unreadable / wrong-version cache file degrades to the empty
  cache with a one-time warning — never an exception on the hot path;
* lookups with non-concrete (traced) cell values return the defaults.

CLI: ``python -m repro.kernels.autotune --quick`` sweeps a small paper-grid
set of cells and writes the cache (used by the CI benchmark job).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time
import warnings
from pathlib import Path

from repro import obs

__all__ = ["lookup", "cell_key", "load_cache", "save_cache", "sweep_cell",
           "clear", "cache_path", "mode", "main"]

_VERSION = 1
_DEFAULT_CACHE = ".pathsig_autotune.json"

# in-memory cache: {path: cells-dict}; invalidated via clear()
_caches: dict[str, dict] = {}
_warned: set[str] = set()
_sweeping = False  # reentrancy guard: sweeps call back into the dispatch


def mode() -> str:
    m = os.environ.get("PATHSIG_AUTOTUNE", "load").strip().lower()
    if m not in ("off", "load", "sweep"):
        _warn_once(f"PATHSIG_AUTOTUNE={m!r} is not off|load|sweep; "
                   "treating as 'off'")
        return "off"
    return m


def cache_path() -> Path:
    return Path(os.environ.get("PATHSIG_AUTOTUNE_CACHE", _DEFAULT_CACHE))


def _warn_once(msg: str) -> None:
    if msg not in _warned:
        _warned.add(msg)
        warnings.warn(msg, stacklevel=3)


def clear() -> None:
    """Drop the in-memory cache + warning dedup (tests / env changes)."""
    _caches.clear()
    _warned.clear()


def _bucket(n: int) -> int:
    """Pow2 ceiling — cells generalise across nearby sizes."""
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


_BUCKETED = ("M", "B", "Bx", "By", "D")


def _device() -> dict:
    """The device a record is timed on and looked up for."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind}


def cell_key(kind: str, **cell) -> str:
    """Canonical cache key.  Size-like axes (M, B, Bx, By, D) are bucketed
    to the next power of two; structural axes (d, depth, engine, precision)
    and the device (``platform`` and ``device_kind``, the current device's
    unless given) are exact."""
    cell = {**_device(), **cell}
    parts = [kind]
    for k in sorted(cell):
        v = cell[k]
        if k in _BUCKETED:
            v = _bucket(v)
        parts.append(f"{k}={v}")
    return "|".join(parts)


def load_cache(path: Path | None = None) -> dict:
    """-> the cells dict for ``path`` (never raises; corrupt -> {})."""
    path = cache_path() if path is None else Path(path)
    key = str(path)
    if key in _caches:
        return _caches[key]
    cells: dict = {}
    if path.exists():
        try:
            raw = json.loads(path.read_text())
            if not isinstance(raw, dict) or raw.get("version") != _VERSION \
                    or not isinstance(raw.get("cells"), dict):
                raise ValueError(f"bad schema (want version={_VERSION} with "
                                 "a 'cells' dict)")
            cells = {k: v for k, v in raw["cells"].items()
                     if isinstance(v, dict)}
        except Exception as e:  # corrupt cache must never break dispatch
            _warn_once(f"ignoring corrupt autotune cache {path}: {e}")
            cells = {}
    _caches[key] = cells
    return cells


def save_cache(cells: dict, path: Path | None = None) -> None:
    path = cache_path() if path is None else Path(path)
    try:
        path.write_text(json.dumps({"version": _VERSION, "cells": cells},
                                   indent=1, sort_keys=True) + "\n")
        _caches[str(path)] = cells
    except OSError as e:
        _warn_once(f"cannot write autotune cache {path}: {e}")


def _concrete(cell: dict) -> dict | None:
    """Cell with int-able sizes, or None if anything is traced/abstract."""
    out = {}
    for k, v in cell.items():
        if isinstance(v, str):
            out[k] = v
            continue
        try:
            out[k] = int(v)
        except TypeError:
            return None
    return out


def _count_lookup(kind: str, outcome: str) -> None:
    if not obs.enabled():
        return
    obs.counter("pathsig_autotune_lookups_total",
                "autotune cache consultations by outcome "
                "(hit/miss/sweep/off/traced/jax_engine)",
                ("kind", "outcome")).inc(kind=kind, outcome=outcome)


def lookup(kind: str, **cell) -> dict:
    """The cached record for a dispatch cell ({} on miss / off / traced).

    In ``sweep`` mode a miss triggers a one-off candidate sweep for the cell
    (measured with synthetic data of the cell's shape), whose winner is
    persisted and returned.  Every consultation ticks
    ``pathsig_autotune_lookups_total{kind=,outcome=}`` when metrics are on."""
    m = mode()
    if m == "off" or _sweeping:
        _count_lookup(kind, "off")
        return {}
    cell = _concrete(cell)
    if cell is None:
        _count_lookup(kind, "traced")
        return {}
    if cell.get("engine") == "jax":
        _count_lookup(kind, "jax_engine")
        return {}  # tile shapes are a Pallas concern
    key = cell_key(kind, **cell)
    cells = load_cache()
    hit = cells.get(key)
    if hit is not None:
        _count_lookup(kind, "hit")
        return hit
    if m != "sweep":
        _count_lookup(kind, "miss")
        return {}
    _count_lookup(kind, "sweep")
    rec = sweep_cell(kind, cell)
    if rec:
        cells[key] = rec
        save_cache(cells)
    return rec


# ---------------------------------------------------------------------------
# sweeps
# ---------------------------------------------------------------------------

def _median_times(fns: list, repeats: int = 3) -> list[float]:
    """Median seconds of each already-warm ``fn``, timed round-robin so
    that no candidate alone pays for the process warming up."""
    ts: list[list[float]] = [[] for _ in fns]
    for _ in range(repeats):
        for t, fn in zip(ts, fns):
            t0 = time.perf_counter()
            fn()
            t.append(time.perf_counter() - t0)
    return [sorted(t)[len(t) // 2] for t in ts]


def _pick(timed: list[tuple[float, dict]], default: dict,
          hysteresis: float = 0.9) -> dict:
    """Winner with default-bias: the default config is always present, and a
    non-default candidate must beat it by >= (1 - hysteresis) to be chosen."""
    t_default = next(t for t, rec in timed if rec == default)
    t_best, best = min(timed, key=lambda p: p[0])
    if best != default and t_best < hysteresis * t_default:
        return best
    return default


def _lane_tiles(n: int) -> list[int]:
    """Lane-tile candidates for an axis of ``n`` rows: multiples of 128
    (the kernels shrink a tile wider than the axis to the whole axis, see
    :func:`repro.kernels.sig_trunc.lane_tile`)."""
    return [128, 256] if n > 128 else [128]


def _sig_candidates(depth: int, B: int) -> list[dict]:
    tiles = _lane_tiles(B)
    splits = sorted({s for s in (None, 2, depth - 1) if s is None
                     or 1 <= s < depth}, key=lambda s: -1 if s is None else s)
    return [{"batch_tile": bt, "split": sp} for bt in tiles for sp in splits]


def sweep_cell(kind: str, cell: dict, repeats: int = 3) -> dict:
    """Measure the candidate grid for one dispatch cell on synthetic data of
    the cell's shape; -> the winning record ({} when the cell has nothing to
    tune).  Never raises: a failing candidate is skipped, a failing sweep
    returns {}."""
    global _sweeping
    import jax
    import numpy as np
    from repro.kernels import ops

    backend = cell.get("engine", "pallas")
    if backend == "jax":
        return {}
    precision = cell.get("precision", "fp32")
    rng = np.random.default_rng(0)
    _sweeping = True
    try:
        if kind in ("sig_trunc", "sig_words"):
            B, M, d, depth = (cell["B"], cell["M"], cell["d"], cell["depth"])
            args = (jax.numpy.asarray(
                rng.standard_normal((B, M, d), np.float32) * 0.1),)
            if kind == "sig_trunc":
                cands = _sig_candidates(depth, B)
                default = {"batch_tile": 128, "split": None}

                def run(rec, x):
                    return ops.signature(
                        x, depth, backend=backend, precision=precision,
                        batch_tile=rec["batch_tile"], split=rec["split"])
            else:
                from repro.core.words import all_words
                words = tuple(all_words(d, depth))
                cands = [{"batch_tile": bt} for bt in _lane_tiles(B)]
                default = {"batch_tile": 128}

                def run(rec, x):
                    return ops.projected(
                        x, words, backend=backend, precision=precision,
                        batch_tile=rec["batch_tile"])
        elif kind == "gram":
            D, Bx, By = cell["D"], cell["Bx"], cell["By"]
            args = (jax.numpy.asarray(
                rng.standard_normal((Bx, D), np.float32) * 0.1),
                jax.numpy.asarray(
                    rng.standard_normal((By, D), np.float32) * 0.1),
                jax.numpy.asarray(rng.random(D, dtype=np.float32)))
            cands = [{"block_words": bw, "bx_tile": bx, "by_tile": by}
                     for bw in (128, 512)
                     for bx in _lane_tiles(Bx) for by in _lane_tiles(By)]
            default = {"block_words": 512, "bx_tile": 128, "by_tile": 128}

            def run(rec, Sx, Sy, w):
                return ops.gram(Sx, Sy, w, backend=backend,
                                precision=precision, **rec)
        elif kind == "gram_ring":
            # per-step tiles of the cross-device ppermute ring: only
            # sweepable under a live mesh whose "batch" axis matches the
            # cell's P (the lookup happens inside gram() under the caller's
            # sharding context, so that context is ambient here)
            from repro.distributed.ctx import current_mesh, logical_axis_size
            P = int(cell.get("P", 0))
            if current_mesh() is None or P < 2 \
                    or logical_axis_size("batch") != P:
                return {}
            D = cell["D"]
            Bx, By = cell["Bx"] * P, cell["By"] * P  # cell keys = per-shard
            args = (jax.numpy.asarray(
                rng.standard_normal((Bx, D), np.float32) * 0.1),
                jax.numpy.asarray(
                    rng.standard_normal((By, D), np.float32) * 0.1),
                jax.numpy.asarray(rng.random(D, dtype=np.float32)))
            cands = [{"block_words": bw, "bx_tile": bx, "by_tile": by}
                     for bw in (128, 512)
                     for bx in _lane_tiles(cell["Bx"])
                     for by in _lane_tiles(cell["By"])]
            default = {"block_words": 512, "bx_tile": 128, "by_tile": 128}

            def run(rec, Sx, Sy, w):
                return ops.gram(Sx, Sy, w, backend=backend,
                                precision=precision, **rec)
        else:
            return {}
        if default not in cands:
            cands.append(default)
        feasible = []
        for rec in cands:
            # timed jitted, as a training or serving step calls the kernels
            step = jax.jit(functools.partial(run, rec))
            fn = functools.partial(
                lambda f: jax.block_until_ready(f(*args)), step)
            try:
                fn()  # compile + warm
            except Exception:
                continue  # infeasible candidate (e.g. invalid split)
            feasible.append((rec, fn))
        timed = list(zip(_median_times([fn for _, fn in feasible], repeats),
                         [rec for rec, _ in feasible]))
        if not any(rec == default for _, rec in timed):
            return {}  # even the default failed: leave the cell untuned
        best = _pick(timed, default)
        return dict(
            best, **_device(),
            ms=round(min(t for t, r in timed if r == best) * 1e3, 4),
            default_ms=round(min(t for t, r in timed if r == default) * 1e3,
                             4))
    except Exception as e:
        _warn_once(f"autotune sweep failed for {kind} cell {cell}: {e}")
        return {}
    finally:
        _sweeping = False


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

_QUICK_GRID = [
    # (kind, cell) — small cells of the paper's grid
    ("sig_trunc", dict(d=6, depth=2, M=100, B=32, precision="fp32")),
    ("sig_trunc", dict(d=3, depth=3, M=200, B=32, precision="fp32")),
    ("sig_trunc", dict(d=6, depth=2, M=100, B=32, precision="bf16_fp32")),
    ("sig_words", dict(d=2, depth=3, M=100, B=32, precision="fp32")),
    ("gram", dict(D=364, Bx=64, By=64, precision="fp32")),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="sweep the small built-in paper-grid cell set")
    ap.add_argument("--out", default=None,
                    help="cache file (default: PATHSIG_AUTOTUNE_CACHE or "
                         f"{_DEFAULT_CACHE})")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    if args.out:
        os.environ["PATHSIG_AUTOTUNE_CACHE"] = args.out
        clear()
    grid = _QUICK_GRID  # --quick is the only shipped grid so far
    if not args.quick:
        print("note: only the --quick grid is defined; sweeping it")
    engine = "pallas" if _device()["platform"] == "tpu" else \
        "pallas_interpret"
    cells = load_cache()
    for kind, cell in grid:
        cell = dict(cell, engine=engine)
        rec = sweep_cell(kind, cell, repeats=args.repeats)
        key = cell_key(kind, **cell)
        if rec:
            cells[key] = rec
            print(f"{key:70s} -> {rec}")
        else:
            print(f"{key:70s} -> (no winner; defaults)")
    save_cache(cells)
    print(f"wrote {cache_path()} ({len(cells)} cells)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
