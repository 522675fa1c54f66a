"""Where JAX's persistent compilation cache lives.

Entry points (``launch/train.py``, the examples, ``chip_smoke.py``) call
``enable_compile_cache()`` once from their ``main``; nothing calls it on
import, and tests never do. A set ``JAX_COMPILATION_CACHE_DIR`` wins: JAX
reads it itself, so no other path is set. Otherwise the cache goes to one
fixed directory inside the checkout, ``<repo>/.jax_cache`` (git-ignored):
the path is part of what a later run must find again, so it never carries
a temp name, a pid or a time.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         "..", ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
