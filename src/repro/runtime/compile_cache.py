"""Where JAX keeps its persistent compilation cache.

A cold process compiles every step program it runs; on a machine that is
handed out per run, that compile is a large share of a short run. The
persistent cache lets a second process in the same checkout load the
executables instead. Its directory is part of every entry's key, so it
must not move between runs: it is either the operator's
``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself — nothing is set in
code then) or the fixed ``.jax_cache/`` at the root of the checkout.

Entry points call :func:`enable_compile_cache` from their ``main``;
importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
