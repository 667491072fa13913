"""Where JAX's persistent compilation cache lives.

The one place in the repo that sets ``jax_compilation_cache_dir``. Process
entry points (``python -m lambdagap_tpu`` via ``cli.run``,
``benchmark/run.py``, ``chip_smoke.py``) call :func:`configure_compile_cache`
once before their first compile; the library itself never does, so an
embedding program keeps its own choice.

The directory is part of the cache key, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
variable itself — nothing is set in code then), else ``.jax_cache`` in the
checkout that holds this package.
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in
    effect."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
