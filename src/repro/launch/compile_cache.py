"""JAX's persistent compilation cache, at one fixed place.

Entry points that compile for the chip (both launchers, ``chip_smoke.py``,
``benchmarks/run.py``) call ``enable_compile_cache()`` first thing, so a
second run of the same program reads its executables back instead of
compiling again. The cache key includes the directory, so the directory
never moves: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads the variable itself, and nothing here overrides it), otherwise
``<repo>/.jax_cache`` (git-ignored).

Deliberately not done at package import: the CPU test suite imports the
package and must not write compiled programs into the checkout.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
