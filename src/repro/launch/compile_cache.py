"""JAX's persistent compilation cache at a fixed path.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``)
call :func:`use_compile_cache` before their first compile; importing the
library never does.  The path is fixed: a directory named by a temp name, a
pid or a timestamp would never be found again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache, gitignored
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that directory
    and no other is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`.  Every compile is cached, not only those over JAX's
    one-second default: a kernel compiles in about a second."""
    import jax
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
