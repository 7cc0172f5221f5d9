"""One place that turns on JAX's persistent compilation cache.

Entry points (``python -m repro.launch.serve``, ``repro.launch.drift``, the
fleet worker, ``chip_smoke.py``) call :func:`enable_compile_cache` first
thing, so a cold process on a chip host reuses what an earlier process
compiled — including the identical stage programs every serve engine
re-jits.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and that directory is used as is; otherwise the cache lives at a fixed
``<checkout>/.jax_cache`` (git-ignored).  The path is part of what makes a
cache hit, so it never depends on a pid, a clock or a temporary directory.

On an accelerator every compilation is written, not only those over JAX's
default one-second threshold: each serve engine jits its own copy of the
stage programs, and those re-jits are served from the cache only if the
first engine's compiles were written, however quick they were.  The CPU
backend keeps JAX's threshold: reloading its cached code is barely cheaper
than compiling it again.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/utils/compile_cache.py -> the checkout root holding src/
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``,
    else at :data:`DEFAULT_DIR`, and return that directory.  Call before the
    first compilation; this initialises the default backend."""
    import jax
    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    if jax.default_backend() != "cpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
