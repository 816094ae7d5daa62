"""JAX's persistent compilation cache, kept at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it.  Otherwise the cache lives in ``.jax_cache``
at the root of the checkout (listed in ``.gitignore``): a fixed path, so
that a later process, or a later run from the same checkout, finds what
an earlier one compiled.
"""
from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable() -> str:
    """Turn the cache on and return its directory.

    Without ``JAX_COMPILATION_CACHE_DIR`` the variable is set to
    :data:`DEFAULT_DIR`, so that JAX (imported later) and child
    processes read it; a JAX already imported is pointed there too.
    Call it before any compile."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    os.environ[ENV_VAR] = DEFAULT_DIR
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir",
                                         DEFAULT_DIR)
    return DEFAULT_DIR
