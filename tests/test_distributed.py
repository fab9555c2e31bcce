"""Multi-device integration: EXECUTE (not just compile) sharded train and
decode steps on 8 placeholder CPU devices in a subprocess (the main test
process must keep seeing 1 device — XLA locks the count at first init)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    import repro.models as M
    from repro.configs import get_config, reduce_config
    from repro.distributed.sharding import batch_specs, cache_specs, \\
        opt_state_specs, param_specs
    from repro.distributed.ctx import sharding_ctx
    from repro.launch.mesh import make_dev_mesh
    from repro.optim import adamw
    from repro.serve import make_serve_step
    from repro.train import make_train_step

    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_dev_mesh(data=2, model=4)
    cfg = reduce_config(get_config("{arch}"))
    rules = {{}}

    with sharding_ctx(mesh, rules):
        params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        p_specs = param_specs(params, mesh, rules)
        params = jax.device_put(params, p_specs)
        opt = adamw(lr=1e-3)
        o_specs = opt_state_specs(jax.eval_shape(opt.init, params),
                                  p_specs, mesh)
        opt_state = jax.jit(opt.init, out_shardings=o_specs)(params)
        batch = {{"tokens": jnp.ones((4, 16), jnp.int32),
                 "labels": jnp.ones((4, 16), jnp.int32)}}
        b_specs = batch_specs(batch, mesh, rules)
        batch = jax.device_put(batch, b_specs)
        step = jax.jit(make_train_step(cfg, opt),
                       in_shardings=(p_specs, o_specs, b_specs),
                       out_shardings=(p_specs, o_specs, None))
        l0 = None
        for _ in range(3):
            params, opt_state, m = step(params, opt_state, batch)
            loss = float(m["loss"])
            assert np.isfinite(loss), loss
            l0 = loss if l0 is None else l0
        assert loss < l0 + 1e-3, (l0, loss)   # training on repeated batch

        # sharded decode: one token against a cache
        cache = M.init_cache(cfg, 4, 32, jnp.float32)
        c_specs = cache_specs(cache, mesh, rules)
        cache = jax.device_put(cache, c_specs)
        serve = jax.jit(make_serve_step(cfg),
                        in_shardings=(p_specs, c_specs, None, None),
                        out_shardings=(None, c_specs))
        tok = jnp.ones((4, 1), jnp.int32)
        for _ in range(3):
            tok, cache = serve(params, cache, tok, jax.random.PRNGKey(0))
        assert tok.shape == (4, 1) and int(tok.max()) < cfg.vocab_size
    print("DISTOK {arch}")
""")


@pytest.mark.parametrize("arch", ["qwen3-4b", "rwkv6-1.6b", "zamba2-7b"])
def test_sharded_train_and_decode_execute_on_8_devices(arch):
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT.format(arch=arch)],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    assert f"DISTOK {arch}" in r.stdout
