"""Record the small CPU traces that ``test_program_trace.py`` reduces.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m bench.tests.record_cpu_scoped_trace

Three training steps of a model at a tiny size, with the signature under
the benchmark's scope and the program's own device scopes, inside a
``bench.window`` annotation.  Writes ``data/<name>.xplane.pb`` and the
step's compiled HLO, ``data/<name>.hlo.txt``, beside this file, for each
recording below: ``cpu_t1_step`` (``trunc_linear``) and ``cpu_hurst_step``
(``hurst_mlp``).  ``record_cpu_trace.py``'s ``cpu_step`` was made before
the program set any scope.
"""
import glob
import os
import re
import shutil
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

from bench.drivers.train_closed import make_step, optimizer
from bench.generators import seed_key
from bench.models import hurst_mlp, trunc_linear

DATA = Path(__file__).resolve().parent / "data"
ROOT = DATA.parents[2]
RECORDINGS = {
    "cpu_t1_step": (trunc_linear, {"channels": 3, "depth": 3, "steps": 16},
                    {"name": "sgd", "lr": 0.01, "momentum": 0.9}),
    "cpu_hurst_step": (hurst_mlp, {"channels": 2, "depth": 3, "steps": 8,
                                   "hurst_range": [0.25, 0.75],
                                   "mlp": [8]},
                       {"name": "adam", "lr": 0.01}),
}


def record(name: str) -> None:
    model, cfg, opt_spec = RECORDINGS[name]
    opt = optimizer(opt_spec)
    params = model.init(cfg, seed_key(1))
    pool = model.pool(cfg, seed_key(2), 2, 8)
    sig = model.program_sig(cfg)
    aux = model.aux(cfg, params, jax.tree.map(lambda a: a[0], pool), sig)
    state = {"params": params, "opt": opt.init(params),
             "i": jnp.zeros((), jnp.int32), "aux": aux}
    step = make_step(model, cfg, opt, sig, 2)
    compiled = jax.jit(step).lower(state, pool).compile()
    state, loss = compiled(state, pool)
    loss.block_until_ready()
    tmp = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # host annotations, no Python frames
    jax.profiler.start_trace(tmp, profiler_options=options)
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
    shutil.copy(path, DATA / f"{name}.xplane.pb")
    (DATA / f"{name}.hlo.txt").write_text(compiled.as_text())
    shutil.rmtree(tmp)


if __name__ == "__main__":
    # source files in the recorded metadata relative to the checkout
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(ROOT) + os.sep))
    for name in RECORDINGS:
        record(name)
