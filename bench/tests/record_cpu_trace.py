"""Record the small CPU trace that ``test_bench_trace.py`` reduces.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m bench.tests.record_cpu_trace

Three training steps of the ``trunc_linear`` model at a tiny size, with
the signature under the benchmark's scope, inside a ``bench.window``
annotation.  Writes ``data/cpu_step.xplane.pb`` and the step's compiled
HLO, ``data/cpu_step.hlo.txt``, beside this file.
"""
import glob
import os
import shutil
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

from bench.drivers.train_closed import SGD, make_step
from bench.generators import seed_key
from bench.models import trunc_linear

DATA = Path(__file__).resolve().parent / "data"
CFG = {"channels": 3, "depth": 3, "steps": 16}


def main():
    opt = SGD(0.01, 0.9)
    params = trunc_linear.init(CFG, seed_key(1))
    pool = trunc_linear.pool(CFG, seed_key(2), 2, 8)
    state = {"params": params, "opt": opt.init(params),
             "i": jnp.zeros((), jnp.int32), "aux": None}
    step = make_step(trunc_linear, CFG, opt, trunc_linear.program_sig(CFG), 2)
    compiled = jax.jit(step).lower(state, pool).compile()
    state, loss = compiled(state, pool)
    loss.block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                state, loss = compiled(state, pool)
        with jax.profiler.TraceAnnotation("bench.block"):
            loss.block_until_ready()
    jax.profiler.stop_trace()
    DATA.mkdir(exist_ok=True)
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, DATA / "cpu_step.xplane.pb")
    (DATA / "cpu_step.hlo.txt").write_text(compiled.as_text())
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
