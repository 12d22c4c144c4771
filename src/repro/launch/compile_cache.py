"""Where JAX keeps this program's persistent compilation cache.

The entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve``) call :func:`configure` once at start-up; importing
``repro`` never touches the cache.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and the program
  sets no other directory;
* unset: the cache goes to ``.jax_cache/`` at the root of the checkout
  (listed in ``.gitignore``).  The path is fixed — no temp name, pid or
  time — because it is part of the cache key: a directory that moves
  never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The in-checkout cache directory used when ``ENV_VAR`` is unset.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> Path:
    """The directory the cache lives in under the rule above."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else DEFAULT_DIR


def configure() -> Path:
    """Point JAX's persistent compilation cache at :func:`cache_dir`.

    Returns the directory.  With ``ENV_VAR`` set this changes nothing:
    JAX already took the directory from the environment.
    """
    path = cache_dir()
    if ENV_VAR not in os.environ:
        import jax
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
