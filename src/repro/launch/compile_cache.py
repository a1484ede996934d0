"""Persistent XLA compilation cache for the launchers.

Called from the entry points (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``), never on import. A cold TPU run spends much of its time
compiling the model step; with the cache a second run in the same checkout
loads the executable instead.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache (git-ignored). A fixed path: the cache key includes
# nothing of the directory, but a path that moved between runs never hits.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, already configures JAX and wins:
    nothing is changed. Otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
