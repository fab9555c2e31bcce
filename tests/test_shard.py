"""Mesh-sharded signature stack: sharded vs unsharded equivalence.

The SPMD contract of ``repro.kernels.ops`` (see the mesh note in its
docstring) has three testable halves:

1. *No context -> bit-identical*: without ``sharding_ctx`` the mesh branch
   is never taken, so outputs and grads match the seed to the bit (also true
   under a context whose batch axis has one shard).
2. *Context -> same answers*: under an 8-host-device mesh every dispatch
   cell (backend × backward × stream × lengths), the Gram ring route, the
   sig-MMD trainer loss and the DynamicBatcher placement all agree with
   their unsharded oracles.
3. *The communication law*: the Gram ring moves O(B·D_sig) bytes over
   collective-permutes — no all-gather of the (B_x, B_y, D_sig) elementwise
   intermediate (asserted on lowered HLO via
   ``repro.distributed.hlo.collective_stats``).

Multi-device execution happens in subprocesses (the main test process must
keep seeing 1 device — XLA locks the count at first init), matching
``test_distributed.py``.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.distributed import collective_stats, sharding_ctx
    from repro.kernels import ops
    from repro.launch.mesh import make_sig_mesh

    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_sig_mesh()
    B, M, d, depth = 6, 8, 2, 3      # B=6: exercises padding up to 8
    x = jax.random.normal(jax.random.PRNGKey(0), (B, M, d)) * 0.2
    lens = jnp.asarray([8, 3, 0, 5, 1, 7], jnp.int32)
    words = ((0,), (1, 0), (0, 1, 1))

    def check(f, tag, rtol=1e-6, atol=1e-6):
        ref = f(x)
        gref = jax.grad(lambda a: (f(a) ** 2).sum())(x)
        with sharding_ctx(mesh):
            got = f(x)
            ggot = jax.grad(lambda a: (f(a) ** 2).sum())(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=rtol, atol=atol, err_msg=tag)
        np.testing.assert_allclose(np.asarray(ggot), np.asarray(gref),
                                   rtol=10 * rtol, atol=atol, err_msg=tag)
        print("ok", tag, flush=True)
""")

_TRUNCATED = _PRELUDE + textwrap.dedent("""
    for backend in ("jax", "pallas_interpret"):
        for backward in ("inverse", "checkpoint", "autodiff"):
            for stream in (False, True):
                if stream and backward == "checkpoint":
                    continue
                for lengths in (None, lens):
                    def f(a, be=backend, bw=backward, st=stream, ln=lengths):
                        return ops.signature(a, depth, backend=be,
                                             backward=bw, stream=st,
                                             stream_stride=3, lengths=ln)
                    check(f, f"sig/{backend}/{backward}/{stream}/"
                             f"{lengths is not None}")
    print("SHARDOK truncated")
""")

_PROJECTED = _PRELUDE + textwrap.dedent("""
    for backend in ("jax", "pallas_interpret"):
        for backward in ("inverse", "checkpoint", "autodiff"):
            for stream in (False, True):
                if stream and backward == "checkpoint":
                    continue
                for lengths in (None, lens):
                    def f(a, be=backend, bw=backward, st=stream, ln=lengths):
                        return ops.projected(a, words, backend=be,
                                             backward=bw, stream=st,
                                             stream_stride=3, lengths=ln)
                    check(f, f"proj/{backend}/{backward}/{stream}/"
                             f"{lengths is not None}")
    for backward in ("inverse", "checkpoint", "autodiff"):
        def f(a, bw=backward):
            return ops.projected(a, words, backend="hybrid", backward=bw)
        check(f, f"proj/hybrid/{backward}")
    # inference-only path
    ref = ops.projected_forward_only(x, words, backend="pallas_interpret")
    with sharding_ctx(mesh):
        got = ops.projected_forward_only(x, words,
                                         backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    print("SHARDOK projected")
""")

_GRAM = _PRELUDE + textwrap.dedent("""
    from repro.sigkernel import sig_gram, sig_mmd

    Bx, By, D = 24, 20, 120          # d=3 depth=4 word space
    Sx = jax.random.normal(jax.random.PRNGKey(1), (Bx, D))
    Sy = jax.random.normal(jax.random.PRNGKey(2), (By, D))
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (D,))) + 0.1
    oracle = (Sx * w[None]) @ Sy.T
    for backend in ("jax", "pallas_interpret"):
        with sharding_ctx(mesh):
            got = ops.gram(Sx, Sy, w, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                                   rtol=1e-5, atol=1e-5, err_msg=backend)
    # grads of all three operands through the ring
    def loss(a, b, c):
        return (ops.gram(a, b, c, backend="jax") ** 2).sum()
    g0 = jax.grad(loss, argnums=(0, 1, 2))(Sx, Sy, w)
    with sharding_ctx(mesh):
        g1 = jax.grad(loss, argnums=(0, 1, 2))(Sx, Sy, w)
    for a, b in zip(g1, g0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)

    # the communication law on lowered HLO: collective-permutes move the
    # Y tiles (O(B.D_sig) total); NO all-gather ever carries the
    # (B_x, B_y, D_sig) elementwise intermediate
    with sharding_ctx(mesh):
        txt = jax.jit(lambda a, b, c: ops.gram(a, b, c, backend="jax")
                      ).lower(Sx, Sy, w).compile().as_text()
    st = collective_stats(txt, default_group=8)
    print(st.summary(), flush=True)
    assert "collective-permute" in st.by_kind, st.by_kind
    blowup = Bx * By * D * 4
    ag = st.by_kind.get("all-gather", (0, 0.0, 0.0))
    assert ag[1] < blowup, (ag, blowup)
    # unrolled ring: (P-1) size-1 permutes of one padded Y shard each —
    # the whole of Y crosses the wire at most once
    ring_budget = (By + 8) * D * 4          # B_y_padded * D * 4 bytes
    assert st.by_kind["collective-permute"][2] <= ring_budget, \\
        (st.by_kind, ring_budget)

    # end to end through the signature legs: ragged Gram + MMD
    X = jnp.cumsum(jax.random.normal(jax.random.PRNGKey(5), (10, 9, 2)), 1)
    Y = jnp.cumsum(jax.random.normal(jax.random.PRNGKey(6), (7, 9, 2)), 1)
    xl = jnp.asarray([9, 4, 2, 9, 1, 6, 3, 8, 9, 5], jnp.int32)
    ref_K = sig_gram(X, Y, 3, route="oracle", backend="jax", x_lengths=xl)
    ref_m = sig_mmd(X, Y, 3, backend="jax", x_lengths=xl)
    gref = jax.grad(lambda a: sig_mmd(a, Y, 3, backend="jax",
                                      x_lengths=xl))(X)
    with sharding_ctx(mesh):
        K = sig_gram(X, Y, 3, backend="jax", x_lengths=xl)
        m = sig_mmd(X, Y, 3, backend="jax", x_lengths=xl)
        gm = jax.grad(lambda a: sig_mmd(a, Y, 3, backend="jax",
                                        x_lengths=xl))(X)
    np.testing.assert_allclose(np.asarray(K), np.asarray(ref_K),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(ref_m),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gm), np.asarray(gref),
                               rtol=1e-4, atol=1e-5)
    print("SHARDOK gram")
""")

_TRAIN_SERVE = _PRELUDE + textwrap.dedent("""
    import dataclasses
    import repro.models as M
    from repro.configs import get_config, reduce_config
    from repro.core.signature import signature
    from repro.models.sig_head import SigHeadConfig
    from repro.optim import adamw
    from repro.serve import DynamicBatcher
    from repro.train import TrainLoopConfig, train_loop

    cfg = reduce_config(get_config("qwen3-4b"))
    cfg = dataclasses.replace(cfg, sig_head=SigHeadConfig(
        depth=3, channels=2, backend="jax"))
    loop = TrainLoopConfig(steps=3, log_every=1, loss="sig_mmd")

    def make_iter(seed=0):
        rng = np.random.default_rng(seed)
        while True:
            yield {"tokens": jnp.asarray(rng.integers(
                       1, cfg.vocab_size, (8, 16)), jnp.int32),
                   "paths": jnp.asarray(np.cumsum(rng.normal(
                       size=(8, 17, 2)).astype(np.float32), 1) * 0.3)}

    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    with sharding_ctx(mesh):
        _, _, hist_dp = train_loop(cfg, params, adamw(lr=1e-3),
                                   make_iter(), loop)
    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    _, _, hist_1 = train_loop(cfg, params, adamw(lr=1e-3),
                              make_iter(), loop)
    for a, b in zip(hist_dp, hist_1):
        assert np.isfinite(a["loss"])
        assert abs(a["loss"] - b["loss"]) < 1e-4 * max(1.0, abs(b["loss"])), \\
            (a["loss"], b["loss"])
    print("ok trainer", flush=True)

    rng = np.random.default_rng(1)
    reqs = [np.cumsum(rng.normal(size=(L + 1, 2)).astype(np.float32), 0)
            for L in (5, 40, 12, 3, 63, 21, 9, 2, 31, 17)]
    db = DynamicBatcher.signature_service(2, 3, max_len=64, backend="jax",
                                          min_bucket=8, max_batch=16,
                                          mesh=mesh)
    tickets = [db.submit(r) for r in reqs]
    res = db.flush()
    for t, r in zip(tickets, reqs):
        ref = signature(jnp.asarray(r)[None], 3)[0]
        np.testing.assert_allclose(np.asarray(res[t]), np.asarray(ref),
                                   atol=1e-5)
    st = db.stats()
    assert st["devices"] == 8 and st["rows_per_device"] >= 1, st
    assert 0.0 < st["occupancy"] <= 1.0, st
    for rung, Bp in st["shapes"]:
        assert Bp % 8 == 0, st["shapes"]    # every device owns equal rows
    print("SHARDOK trainserve")
""")

_OBS = _PRELUDE + textwrap.dedent("""
    # observability under shard_map: dispatch spans + counters fire for
    # mesh-routed calls, and the gram ring's ANALYTIC wire-byte counter
    # agrees with the lowered HLO via collective_stats (the double-buffered
    # ring is unrolled: size-1 permute instructions, one shard each, so
    # analytic == total permute wire bytes).
    from repro import obs

    obs.enable()
    obs.start_trace()
    with sharding_ctx(mesh):
        ops.signature(x, depth, backend="jax").block_until_ready()
    assert obs.counter("pathsig_dispatch_calls_total", "",
                       ("op", "backend", "ctx")).value(
        op="signature", backend="jax", ctx="eager") >= 1
    evs = [e for e in obs.trace.TRACER.events
           if e.get("name") == "kernels.signature"]
    assert evs and evs[0]["args"]["ctx"] == "eager", evs[:3]
    obs.stop_trace()

    # gram ring wire accounting: By divisible by n_dev -> no pad rows, the
    # analytic counter is exactly (n_dev - 1) * shard_bytes per eager call
    # (the final ring step consumes the prefetched shard without another
    # permute)
    obs.reset()
    Bx, By, D = 16, 24, 120
    Sx = jax.random.normal(jax.random.PRNGKey(1), (Bx, D))
    Sy = jax.random.normal(jax.random.PRNGKey(2), (By, D))
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (D,))) + 0.1
    with sharding_ctx(mesh):
        ops.gram(Sx, Sy, w, backend="jax").block_until_ready()
    wire_counter = obs.counter("pathsig_ring_wire_bytes_total", "",
                               ("ctx",))
    analytic = wire_counter.value(ctx="eager")
    assert analytic == 7 * (By // 8) * D * 4, analytic

    with sharding_ctx(mesh):
        txt = jax.jit(lambda a, b, c: ops.gram(a, b, c, backend="jax")
                      ).lower(Sx, Sy, w).compile().as_text()
    st = collective_stats(txt, default_group=8)
    n, _, wire = st.by_kind["collective-permute"]
    assert n == 7, st.by_kind           # unrolled: one instr per ring step
    assert analytic == wire, (analytic, n, wire)
    print("SHARDOK obs")
""")

_RETRACE = _PRELUDE + textwrap.dedent("""
    # the efficiency-cliff contract, on lowered artifacts:
    # 1. retrace-free dispatch: repeated same-shape mesh calls across the
    #    weak-scaling sweep compile each sharded site at most ONCE per
    #    (site, shape key) — the per-shard closures are hoisted into
    #    plan-cached callables, so the jit cache does the rest;
    # 2. the data-parallel train step actually ALIASES its donated
    #    (params, opt_state) buffers (hlo.assert_donation);
    # 3. the double-buffered gram ring lowers to an unrolled, overlappable
    #    schedule (hlo.ring_overlap): permutes outside any while loop and
    #    never data-dependent on the tile dots.
    import dataclasses
    from repro import obs
    from repro.distributed import hlo

    obs.enable()
    obs.reset()
    D = 120
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (D,))) + 0.1
    for P in (2, 4, 8):
        m = make_sig_mesh(P)
        xp = jax.random.normal(jax.random.PRNGKey(7), (4 * P, M, d)) * 0.2
        Sp = jax.random.normal(jax.random.PRNGKey(8), (2 * P, D))
        with sharding_ctx(m):
            for _ in range(3):
                ops.signature(xp, depth, backend="jax").block_until_ready()
                jax.grad(lambda a: ops.signature(
                    a, depth, backend="jax").sum())(xp).block_until_ready()
                ops.projected(xp, words, backend="jax").block_until_ready()
                jax.grad(lambda a: ops.projected(
                    a, words, backend="jax").sum())(xp).block_until_ready()
                ops.gram(Sp, Sp, w, backend="jax").block_until_ready()

    sharded = {"sharded_sig", "sharded_proj", "sharded_proj_fwd",
               "gram_ring"}
    snap = obs.snapshot()["metrics"]["pathsig_jit_traces_total"]["values"]
    rows = [v for v in snap if v["labels"]["site"] in sharded]
    assert {r["labels"]["site"] for r in rows} >= {"sharded_sig",
                                                   "gram_ring"}, rows
    bad = [r for r in rows if r["value"] != 1]
    assert not bad, ("retraced sharded sites", bad)
    print("ok retrace-free", len(rows), "site/shape keys", flush=True)

    # 2. donation on the lowered data-parallel train step
    import repro.models as MM
    from repro.configs import get_config, reduce_config
    from repro.models.sig_head import SigHeadConfig
    from repro.optim import adamw
    from repro.train.trainer import make_train_step

    cfg = reduce_config(get_config("qwen3-4b"))
    cfg = dataclasses.replace(cfg, sig_head=SigHeadConfig(
        depth=3, channels=2, backend="jax"))
    params = MM.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    opt = adamw(lr=1e-3)
    opt_state = opt.init(params)
    batch = {"tokens": jnp.ones((8, 16), jnp.int32),
             "paths": jnp.ones((8, 17, 2), jnp.float32)}
    step = obs.instrument_jit(make_train_step(cfg, opt, loss="sig_mmd"),
                              site="train_step_hlo", donate_argnums=(0, 1))
    txt = step.lower(params, opt_state, batch).compile().as_text()
    st = hlo.assert_donation(txt, min_aliased=2)
    print("ok donation:", st.n_aliased, "aliased pairs", flush=True)

    # 3. overlap structure of the lowered ring
    Sx = jax.random.normal(jax.random.PRNGKey(1), (16, D))
    with sharding_ctx(mesh):
        rtxt = jax.jit(lambda a, b, c: ops.gram(a, b, c, backend="jax")
                       ).lower(Sx, Sx, w).compile().as_text()
    ov = hlo.ring_overlap(rtxt)
    assert ov.overlapped, ov.summary()
    assert ov.n_permutes == 7, ov.summary()
    print("ok ring overlap:", ov.summary(), flush=True)
    print("SHARDOK retrace")
""")

_SCRIPTS = {"truncated": (_TRUNCATED, "SHARDOK truncated"),
            "projected": (_PROJECTED, "SHARDOK projected"),
            "gram": (_GRAM, "SHARDOK gram"),
            "trainserve": (_TRAIN_SERVE, "SHARDOK trainserve"),
            "obs": (_OBS, "SHARDOK obs"),
            "retrace": (_RETRACE, "SHARDOK retrace")}


@pytest.mark.parametrize("name", sorted(_SCRIPTS))
def test_sharded_equivalence_subprocess(name):
    script, sentinel = _SCRIPTS[name]
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       timeout=420)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert sentinel in r.stdout


# ---------------------------------------------------------------------------
# in-process: single-device no-op guarantees, cache bounding, mesh helpers
# ---------------------------------------------------------------------------

def test_one_shard_context_is_bit_identical():
    """A context whose batch axis has a single shard never takes the mesh
    branch — outputs and grads match the no-context path to the bit."""
    import jax
    import jax.numpy as jnp
    from repro.distributed import sharding_ctx
    from repro.kernels import ops
    from repro.launch.mesh import make_sig_mesh

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 10, 2)) * 0.3
    lens = jnp.asarray([10, 3, 7, 0], jnp.int32)
    mesh = make_sig_mesh(1)
    for kwargs in ({}, {"stream": True, "stream_stride": 4},
                   {"lengths": lens}, {"backward": "checkpoint"}):
        ref = ops.signature(x, 3, backend="pallas_interpret", **kwargs)
        gref = jax.grad(lambda a: ops.signature(
            a, 3, backend="pallas_interpret", **kwargs).sum())(x)
        with sharding_ctx(mesh):
            got = ops.signature(x, 3, backend="pallas_interpret", **kwargs)
            ggot = jax.grad(lambda a: ops.signature(
                a, 3, backend="pallas_interpret", **kwargs).sum())(x)
        assert (np.asarray(got) == np.asarray(ref)).all()
        assert (np.asarray(ggot) == np.asarray(gref)).all()


def test_make_dev_mesh_validates_device_count():
    from repro.launch.mesh import make_dev_mesh, make_sig_mesh

    with pytest.raises(ValueError, match="devices"):
        make_dev_mesh(data=64, model=64)
    with pytest.raises(ValueError, match="devices"):
        make_sig_mesh(batch=4096)
    with pytest.raises(ValueError, match=">= 1"):
        make_sig_mesh(batch=0)
    with pytest.raises(ValueError, match=">= 1"):
        make_dev_mesh(data=0)
    assert tuple(make_sig_mesh(1).axis_names) == ("data",)


def test_plan_caches_bounded_eviction_and_clear():
    """A maxsize-1 plan-cache policy forces eviction on every alternation of
    word sets — results must be identical to the unbounded policy, and
    clear_plan_caches() must be a pure perf event.  The word engine's closure
    VJP is keyed by the word set, so it runs here."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from tests.conftest import projected_engine

    with projected_engine("words"):
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 2)) * 0.3
        sets = [((0,), (1, 0)), ((1,), (0, 1), (1, 1, 0)), ((0, 0), (1, 0, 1))]
        ref = [np.asarray(ops.projected(x, ws, backend="pallas_interpret"))
               for ws in sets]
        gref = [np.asarray(jax.grad(lambda a, ws=ws: ops.projected(
            a, ws, backend="pallas_interpret").sum())(x)) for ws in sets]

        old = ops.PLAN_CACHE_MAXSIZE
        try:
            ops.set_plan_cache_maxsize(1)
            for _ in range(2):              # alternate -> evict every call
                for i, ws in enumerate(sets):
                    got = np.asarray(ops.projected(x, ws,
                                                   backend="pallas_interpret"))
                    np.testing.assert_array_equal(got, ref[i])
                    ggot = np.asarray(jax.grad(lambda a, ws=ws: ops.projected(
                        a, ws, backend="pallas_interpret").sum())(x))
                    np.testing.assert_array_equal(ggot, gref[i])
            info = ops.plan_cache_info()
            assert info["_pallas_proj_inverse"].maxsize == 1
            assert info["_pallas_proj_inverse"].misses > len(sets)  # evictions
            ops.clear_plan_caches()
            assert ops.plan_cache_info()["_plan_for_words"].currsize == 0
            got = np.asarray(ops.projected(x, sets[0],
                                           backend="pallas_interpret"))
            np.testing.assert_array_equal(got, ref[0])
        finally:
            ops.set_plan_cache_maxsize(old)


def test_plan_cache_policy_is_shared():
    """Every registered cache follows the configured bound."""
    from repro.kernels import ops

    old = ops.PLAN_CACHE_MAXSIZE
    try:
        ops.set_plan_cache_maxsize(7)
        info = ops.plan_cache_info()
        assert info, "no plan caches registered"
        assert all(ci.maxsize == 7 for ci in info.values()), info
        for name in ("_plan_for_words", "_tiled_for_words", "_gram_vjp",
                     "_pallas_sig_inverse", "_sharded_sig", "_gram_ring"):
            assert name in info, sorted(info)
    finally:
        ops.set_plan_cache_maxsize(old)
