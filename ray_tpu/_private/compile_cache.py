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

Also the process's compile listener (``install_listener``): one
``jax.compile`` span for every compile and every load from the cache. jax
stores only compiles that took over a second, so the directory alone
cannot say whether something compiled.
"""

from __future__ import annotations

import os
import threading
import time

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


# -- what compiling costs -----------------------------------------------------
#
# jax tells its ``jax.monitoring`` listeners how long each compile took.
# ``BACKEND_COMPILE`` wraps the whole of "load from the persistent cache or
# compile", on the thread that asked; on a cache hit ``CACHE_RETRIEVAL``
# (the time to read and load the entry) fires inside it, just before. So a
# retrieval marks the thread, and the BACKEND_COMPILE that follows is that
# hit's; one with no mark is a compile.

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_retrieved = threading.local()
_install_lock = threading.Lock()
_installed = False


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == CACHE_RETRIEVAL:
        _retrieved.pending = True
        return
    if event != BACKEND_COMPILE:
        return
    hit = getattr(_retrieved, "pending", False)
    _retrieved.pending = False
    # Buffer only: the interval is over, and a profiler annotation cannot
    # be backdated.
    from ray_tpu.util import tracing

    end = time.time()
    tracing.record_span("jax.compile", end - duration, end, {
        "cache": "hit" if hit else "miss", "seconds": duration,
        "fun": str(kwargs.get("fun_name", ""))})


def install_listener() -> None:
    """Record one ``jax.compile`` span (``cache``: ``hit`` / ``miss``,
    ``seconds``, ``fun``) for each of this process's compiles and loads
    from the cache. Idempotent. Called by the modules of the program that
    import jax anyway; it is never a reason to import jax."""
    global _installed
    with _install_lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)

