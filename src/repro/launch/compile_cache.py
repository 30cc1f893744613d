"""Where JAX's persistent compilation cache lives — decided here only.

Every launcher (``launch.serve``, ``launch.train``, ``launch.dryrun``)
and ``chip_smoke.py`` call :func:`enable` before their first compile.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set in code; otherwise the cache goes to ``.jax_cache`` at
the root of the checkout (listed in ``.gitignore``).  The path is part
of each cache entry's key, so it is fixed rather than temporary.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
