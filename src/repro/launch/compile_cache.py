"""JAX's persistent compilation cache at a fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there and
nothing here overrides it.  Otherwise, on an accelerator, the cache goes to
``.jax_cache`` at the root of the checkout: a fixed path, because the path is
part of the cache's key and a directory that moves never hits.  On the CPU
backend the cache stays off: its programs compile in seconds.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on; returns the directory used."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
