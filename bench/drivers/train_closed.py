"""Closed loop of training steps over an on-device pool of batches.

Set-up builds one object, the compiled step with its state, and drives it
through its first three steps (the readings the reference is compared
with), each on its own batch of the pool.  The window then calls that same
object on the batches that follow, cycling through the pool, in chunks of
about a quarter of a second that each end in ``block_until_ready``:
``step_ms`` is the window's wall time over the steps completed in it.

Traffic keys: ``pool_batches`` (batches drawn at set-up), ``trace_steps``
(steps under the profiler in a traced run).  Configuration keys used here:
``model``, ``batch_per_chip``, ``optimizer``, ``ref_block_rows`` and
``loss_steps`` (how many of the three losses ``correct`` compares).
"""
from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check
from bench.generators import seed_key

CHUNK_S = 0.25


# -- optimizers (the program's and the reference's alike) -------------------

class SGD:
    """Heavy-ball SGD; after one step the buffer is the first gradient."""

    def __init__(self, lr: float, momentum: float):
        self.lr, self.beta = lr, momentum

    def init(self, params):
        return {"m": jax.tree.map(jnp.zeros_like, params)}

    def update(self, params, state, g):
        m = jax.tree.map(lambda a, b: self.beta * a + b, state["m"], g)
        return jax.tree.map(lambda p, a: p - self.lr * a, params, m), {"m": m}

    def first_grad(self, state):
        return state["m"]


class Adam:
    def __init__(self, lr: float, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8):
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps

    def init(self, params):
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
        return {"m": zeros(), "v": zeros(), "t": jnp.zeros((), jnp.float32)}

    def update(self, params, state, g):
        t = state["t"] + 1
        m = jax.tree.map(lambda a, b: self.b1 * a + (1 - self.b1) * b,
                         state["m"], g)
        v = jax.tree.map(lambda a, b: self.b2 * a + (1 - self.b2) * b * b,
                         state["v"], g)
        c1, c2 = 1 - self.b1 ** t, 1 - self.b2 ** t
        params = jax.tree.map(
            lambda p, a, b: p - self.lr * (a / c1) / (jnp.sqrt(b / c2)
                                                     + self.eps),
            params, m, v)
        return params, {"m": m, "v": v, "t": t}

    def first_grad(self, state):
        return jax.tree.map(lambda a: a / (1 - self.b1), state["m"])


def optimizer(spec: dict):
    kw = {k: v for k, v in spec.items() if k != "name"}
    return {"sgd": SGD, "adam": Adam}[spec["name"]](**kw)


# -- the program's step -------------------------------------------------------

def make_step(model, cfg, opt, sig, n_pool: int, fault: str | None = None,
              shards: int = 1):
    """(state, pool) -> (state, loss).  ``fault`` plants a broken step for
    the tests: ``"frozen"`` returns the state unchanged, ``"half"`` takes
    the mean over the first half of the batch alone, ``"local"`` over the
    first of ``shards`` shards' rows alone, as a step whose gradients are
    never exchanged between chips reads on the first chip."""
    def loss_fn(params, aux, batch):
        return jnp.mean(model.row_losses(cfg, params, aux, batch, sig))

    def step(state, pool):
        i = state["i"]
        batch = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i % n_pool, 0, False),
            pool)
        if fault in ("half", "local"):
            keep = 2 if fault == "half" else shards
            batch = jax.tree.map(lambda a: a[:a.shape[0] // keep], batch)
        loss, g = jax.value_and_grad(loss_fn)(state["params"], state["aux"],
                                              batch)
        if fault == "frozen":
            return state, loss
        params, ostate = opt.update(state["params"], state["opt"], g)
        return dict(state, params=params, opt=ostate, i=i + 1), loss
    return step


def _mesh(chips: int):
    if chips == 1:
        return None
    from repro.launch.mesh import make_sig_mesh
    return make_sig_mesh(chips)


def _scope(mesh):
    import contextlib
    if mesh is None:
        return contextlib.nullcontext()
    from repro.distributed import sharding_ctx
    return sharding_ctx(mesh)


def build(run, precision: str = "fp32", fault: str | None = None):
    """Set-up: weights, pool and optimizer state on the device from the
    seed, the compiled step, and its first three steps.  -> dict."""
    cfg, traffic = run.cfg, run.traffic
    model = importlib.import_module(f"bench.models.{cfg['model']}")
    chips = len(run.devices)
    B, P = cfg["batch_per_chip"] * chips, traffic["pool_batches"]
    mesh = _mesh(chips)
    opt = optimizer(cfg["optimizer"])
    k_params, k_pool = jax.random.split(seed_key(run.seed))
    dev0 = jax.sharding.SingleDeviceSharding(run.devices[0])
    if mesh is None:
        rows = rep = dev0
    else:
        spec = jax.sharding.PartitionSpec
        rows = jax.sharding.NamedSharding(mesh, spec(None, "data"))
        rep = jax.sharding.NamedSharding(mesh, spec())
    params = jax.jit(lambda k: model.init(cfg, k),
                     out_shardings=rep)(k_params)
    pool = jax.jit(lambda k: model.pool(cfg, k, P, B),
                   out_shardings=rows)(k_pool)
    sig = model.program_sig(cfg, precision)
    with _scope(mesh):
        aux = jax.jit(lambda p, b: model.aux(cfg, p, b, sig))(
            params, jax.tree.map(lambda a: a[0], pool))
        state = {"params": params, "opt": opt.init(params),
                 "i": jnp.zeros((), jnp.int32), "aux": aux}
        step = make_step(model, cfg, opt, sig, P, fault, chips)
        compiled = jax.jit(step, donate_argnums=0).lower(state, pool).compile()
    p0 = jax.device_get(params)
    losses, grad, t_step = [], None, 0.0
    for k in range(3):
        t = time.perf_counter()
        state, loss = compiled(state, pool)
        losses.append(float(loss))
        t_step = time.perf_counter() - t
        if k == 0:
            grad = jax.device_get(opt.first_grad(state["opt"]))
    delta = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                         jax.device_get(state["params"]), p0)
    return {"model": model, "opt": opt, "pool": pool, "state": state,
            "compiled": compiled, "p0": p0, "B": B, "t_step": t_step,
            "readings": {"losses": losses, "grad": grad, "delta": delta}}


def reference_readings(model, cfg, opt, p0, batches, block: int,
                       device, rows: int | None = None,
                       precision: str = "highest") -> dict:
    """The reference's three steps from ``p0`` over ``batches`` (a list of
    host batches), in blocks of ``block`` rows.  ``rows`` keeps only the
    first rows of each batch and ``precision`` lowers its matmuls: a fault
    or a control read with the reference in the program's place."""
    sig = model.reference_sig(cfg)
    put = lambda t: jax.device_put(t, device)  # noqa: E731
    mm = jax.lax.Precision[precision.upper()]
    with jax.default_matmul_precision(precision):
        aux = jax.jit(lambda p, b: model.aux(cfg, p, b, sig))(
            put(p0), put(batches[0]))
        if rows is not None:
            batches = [jax.tree.map(lambda a: a[:rows], b) for b in batches]
        B = jax.tree.leaves(batches[0])[0].shape[0]

        @jax.jit
        def block_grad(params, aux, batch):
            def f(p):
                return jnp.sum(model.row_losses(cfg, p, aux, batch, sig,
                                                 mm)) / B
            return jax.value_and_grad(f)(params)

        params, ostate = put(p0), opt.init(put(p0))
        losses, grad = [], None
        for batch in batches:
            total, g = 0.0, None
            for lo in range(0, B, block):
                part = put(jax.tree.map(lambda a: a[lo:lo + block], batch))
                v, gb = block_grad(params, aux, part)
                total += float(v)
                g = gb if g is None else jax.tree.map(jnp.add, g, gb)
            losses.append(total)
            params, ostate = jax.jit(opt.update)(params, ostate, g)
            if grad is None:
                grad = jax.device_get(opt.first_grad(ostate))
        delta = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                             jax.device_get(params), p0)
    return {"losses": losses, "grad": grad, "delta": delta}


def first_batches(built) -> list:
    return [jax.device_get(jax.tree.map(lambda a: a[k], built["pool"]))
            for k in range(3)]


def run(run):
    built = build(run)
    compiled, pool = built["compiled"], built["pool"]
    state = built["state"]
    setup_s = time.time() - run.t0
    chunk = max(1, int(round(CHUNK_S / max(built["t_step"], 1e-4))))
    out = {"setup_s": setup_s}
    run.compiles.mark()
    if run.trace:
        t_end = time.perf_counter() + 1.0           # reach a steady state
        while time.perf_counter() < t_end:
            for _ in range(chunk):
                state, loss = compiled(state, pool)
            loss.block_until_ready()
        n = run.traffic["trace_steps"]
        run.profiler.hlo_texts = [compiled.as_text()]
        with run.profiler() as prof:
            for _ in range(n):
                with prof.annotate("bench.dispatch"):
                    state, loss = compiled(state, pool)
            with prof.annotate("bench.block"):
                loss.block_until_ready()
        steps = n
        out["layer"] = {"counts": built["model"].sweep_counts(run.cfg,
                                                              built["B"]),
                        "steps": n}
    else:
        steps, t0 = 0, time.perf_counter()
        while True:
            for _ in range(chunk):
                state, loss = compiled(state, pool)
            steps += chunk
            loss.block_until_ready()
            elapsed = time.perf_counter() - t0
            if elapsed >= run.seconds:
                break
        out["e2e"] = {"step_ms": 1e3 * elapsed / steps}
    run.compiles.close()
    final_ok = bool(np.isfinite(float(loss)))
    out["attempted"], out["failed"] = steps, 0 if final_ok else steps
    out["memory_peak_bytes"] = run.memory_peak()
    batches = first_batches(built)
    model, opt, p0 = built["model"], built["opt"], built["p0"]
    prog = built["readings"]
    del built, state, compiled, pool
    ref = reference_readings(model, run.cfg, opt, p0, batches,
                             run.cfg["ref_block_rows"], run.devices[0])
    out["numbers"] = check.train_numbers(prog, ref,
                                         run.cfg.get("loss_steps", 3))
    return out
