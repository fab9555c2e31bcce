"""``correct`` at sizes a test run holds: the harness's look for a chip is
skipped and the rest of a run is driven on the CPU, with the cells' own
limits.  Sound runs pass; the control (the program's bfloat16-increment
path) and each fault a cell can have come out not correct."""
import functools
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness
from bench.drivers import serve_open, train_closed

SMALL = {"trunc_linear": dict(batch_per_chip=32, steps=24, ref_block_rows=16),
         "hurst_mlp": dict(batch_per_chip=32, steps=16, mlp=[16, 8],
                           ref_block_rows=16)}
SEED = 2**33 + 99


def small_cell(name):
    cell = harness.load_cell(name)
    cell.cfg.update(SMALL[cell.cfg["model"]])
    return cell


def file_cell(config, traffic, chips=1):
    """A cell built from its files alone, for the mixes held out of
    BENCHMARK.json until they are proven on the chip (PERF.md)."""
    bench = harness.BENCH
    cell = SimpleNamespace(
        name=f"{config}.{traffic}", chips=chips, per_layer=[], e2e=[],
        cfg=harness.load_json(bench / "configs" / f"{config}.json"),
        traffic=harness.load_json(bench / "traffic" / f"{traffic}.json"),
        limits=harness.load_json(bench / "limits"
                                 / f"{config}.{traffic}.json"))
    cell.cfg.update(SMALL[cell.cfg["model"]])
    return cell


def small_serve_cell():
    cell = file_cell("t1_d4n5", "serve")
    cell.traffic.update(rate_per_s=60, max_len=16, check_rows=64)
    return cell


def execute(cell, devices=None):
    run = harness.Run(cell, SEED, 0.5, False,
                      devices or jax.devices()[:1], time.time())
    return harness.execute(run)


@pytest.mark.parametrize("name", ["t1_d4n5.train", "hurst_leadlag.train"])
@pytest.mark.parametrize("plant", [None, "control", "frozen", "half"])
def test_train_cell(monkeypatch, name, plant):
    if plant is not None:
        kw = ({"precision": "bf16_fp32"} if plant == "control"
              else {"fault": plant})
        monkeypatch.setattr(train_closed, "build",
                            functools.partial(train_closed.build, **kw))
    out = execute(small_cell(name))
    assert out["correct"] is (plant is None), out["checks"]
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] == 1


def _altered_service(orig):
    """signature_service whose answers are altered where they are made."""
    def make(*args, **kw):
        svc = orig(*args, **kw)
        compute = svc.compute
        svc.compute = lambda rp: compute(rp).at[0, 0].add(1e-2)
        return svc
    return make


@pytest.mark.parametrize("plant", [None, "control", "altered"])
def test_serve_cell(monkeypatch, plant):
    from repro.serve import DynamicBatcher
    if plant == "control":
        monkeypatch.setattr(serve_open, "build", functools.partial(
            serve_open.build, precision="bf16_fp32"))
    if plant == "altered":
        monkeypatch.setattr(DynamicBatcher, "signature_service",
                            _altered_service(DynamicBatcher.signature_service))
    out = execute(small_serve_cell())
    assert out["correct"] is (plant is None), out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 30


DP4 = """
import functools, json, sys, time
import jax
from bench.tests.test_bench_correct import execute, file_cell
from bench.drivers import train_closed
if sys.argv[1] == "local":
    train_closed.build = functools.partial(train_closed.build, fault="local")
out = execute(file_cell("t1_d4n5", "train_dp4", 4), jax.devices()[:4])
print(json.dumps({"correct": out["correct"], "count": out["device"]["count"],
                  "checks": out["checks"]}))
"""


@pytest.mark.parametrize("plant", ["none", "local"])
def test_train_dp4_cell(plant):
    """Four virtual CPU devices: the sharded step is correct; with the
    gradients of one chip's rows alone (no exchange) it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(harness.ROOT),
                                           str(harness.ROOT / "src")]))
    res = subprocess.run([sys.executable, "-c", DP4, plant], env=env,
                         cwd=harness.ROOT, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["count"] == 4
    assert out["correct"] is (plant == "none"), out["checks"]


def test_row_error_scale():
    from bench.check import row_error
    want = np.array([[1.0, -2.0], [0.5, 0.25]])
    got = want + np.array([[0.0, 2e-3], [0.0, 0.0]])
    assert row_error(got, want) == pytest.approx(1e-3)
