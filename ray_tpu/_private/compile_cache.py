"""Where JAX's persistent compilation cache lives.

One directory for every process of a checkout: the head and the node
agents put it into each worker's spawn environment, and single-process
scripts (``bench.py``) export it before importing jax. JAX reads
``JAX_COMPILATION_CACHE_DIR`` itself, so nothing here touches
``jax.config``.

The path is part of the cache key, so it must not move between runs:
an operator's ``JAX_COMPILATION_CACHE_DIR`` wins and no code sets
another; otherwise it is ``<checkout>/.jax_cache`` — never a temporary
name, a pid, a session id or the time.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def compile_cache_entries() -> int:
    """Files in the cache directory (0 when it does not exist yet)."""
    try:
        return len(os.listdir(compile_cache_dir()))
    except OSError:
        return 0
