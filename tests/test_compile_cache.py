"""``use_compile_cache``: compiled programs land in ``JAX_COMPILATION_CACHE_DIR``
when it is set and nowhere else, else in the fixed default directory.

Each case runs in a subprocess: the cache directory is process-wide JAX
configuration, which must not leak into the other tests of a worker.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

_SCRIPT = textwrap.dedent("""
    import sys
    from pathlib import Path
    from repro.launch import compile_cache
    compile_cache.DEFAULT_DIR = Path(sys.argv[1])
    print(compile_cache.use_compile_cache())
    import jax, jax.numpy as jnp
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_ready()
""")


@pytest.mark.parametrize("env_set", [True, False])
def test_compiled_programs_land_in_one_directory(tmp_path, env_set):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _SCRIPT, str(default_dir)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    used, unused = (env_dir, default_dir) if env_set else (default_dir,
                                                           env_dir)
    assert r.stdout.strip() == str(used)
    assert any(p.name.endswith("-cache") for p in used.iterdir())
    assert not unused.exists()
