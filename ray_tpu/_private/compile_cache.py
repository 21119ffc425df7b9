"""Where JAX's persistent compilation cache lives.

One directory for every process of a checkout: the head and the node
agents put it into each worker's spawn environment, and single-process
scripts export it before importing jax. JAX reads
``JAX_COMPILATION_CACHE_DIR`` itself, so nothing here touches
``jax.config``.

The path is part of the cache key, so it must not move between runs:
an operator's ``JAX_COMPILATION_CACHE_DIR`` wins and no code sets
another; otherwise it is ``<checkout>/.jax_cache`` — never a temporary
name, a pid, a session id or the time.

Also the process's compile listener (``install_listener``): one
``jax.compile`` span for every compile and every load from the cache. jax
stores only compiles that took over a second, so the directory alone
cannot say whether something compiled. The span's start, end and
``seconds`` are the backend's (compile, or load from the cache); what the
jit cost in Python before the compiler was asked rides on it as
attributes: ``trace_s`` (tracing the function to a jaxpr), ``lower_s``
(lowering the jaxpr to an MLIR module) and ``lead_s`` (from the trace's
start to the backend's start: the two and whatever lay between them).
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
#
# Ahead of it, on the same thread, jax times the jit's Python: ``TRACE``
# (the function to a jaxpr) and ``LOWER`` (the jaxpr to an MLIR module).
# Each fires when its work ends, so an inner jit's trace fires before, and
# lies inside, the trace of the jit around it: the LAST trace a thread has
# heard of is the outermost, and it replaces one no compile followed
# (``eval_shape``, ``.lower()``), which is charged to no span. Lowering
# traces too (a rule that calls a jitted helper: ``jax.random`` does, a
# hundred times a draw), after the jit's own trace and before LOWER fires;
# jax announces a lowering's START as a scalar, and a trace heard while
# one is under way is not the jit's own. BACKEND_COMPILE takes the trace
# and the lowering as attributes of its span and forgets them. (A compile
# that had no trace of its own, its jaxpr cached, straight behind an
# ``eval_shape`` would be charged with that trace: not seen in a train
# loop.)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

# This thread's hit mark (``hit``), whether it is ``lowering``, and the
# (start, end) of the ``trace`` and ``lower`` its next compile will take.
_pending = threading.local()
_install_lock = threading.Lock()
_installed = False
# jax tells the true start and end of TRACE / LOWER to time-span listeners
# (0.9.0 has them); a jax without them gives durations only, and the start
# is then the moment the duration arrives less the duration. A jax that
# announces no starts reads ``trace_s`` too low where lowering traces.
_hears_time_spans = False


def _on_scalar(event: str, value: float, **kwargs) -> None:
    if event == LOWER:      # announced as it starts (the value is when)
        _pending.lowering = True


def _on_time_span(event: str, start: float, end: float, **kwargs) -> None:
    if event == TRACE:
        if not getattr(_pending, "lowering", False):
            _pending.trace = (start, end)
    elif event == LOWER:
        _pending.lower = (start, end)
        _pending.lowering = False


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == CACHE_RETRIEVAL:
        _pending.hit = True
        return
    if event in (TRACE, LOWER):
        if not _hears_time_spans:
            end = time.time()
            _on_time_span(event, end - duration, end)
        return
    if event != BACKEND_COMPILE:
        return
    hit = getattr(_pending, "hit", False)
    trace = getattr(_pending, "trace", None)
    lower = getattr(_pending, "lower", None)
    _pending.hit, _pending.trace, _pending.lower = False, None, None
    # Buffer only: the interval is over, and a profiler annotation cannot
    # be backdated.
    from ray_tpu.util import tracing

    end = time.time()
    start = end - duration
    trace_s = trace[1] - trace[0] if trace else 0.0
    lower_s = lower[1] - lower[0] if lower else 0.0
    first = trace or lower
    tracing.record_span("jax.compile", start, end, {
        "cache": "hit" if hit else "miss", "seconds": duration,
        "fun": str(kwargs.get("fun_name", "")),
        "trace_s": trace_s, "lower_s": lower_s,
        "lead_s": max(start - first[0], trace_s + lower_s) if first else 0.0})


def install_listener() -> None:
    """Record one ``jax.compile`` span for each of this process's compiles
    and loads from the cache: its start, end and ``seconds`` the backend's,
    ``cache`` (``hit`` / ``miss``), ``fun``, and the Python ahead of it as
    ``trace_s``, ``lower_s`` and ``lead_s`` (the module's docstring; no
    span of their own: a set-up with hundreds of small jits must not
    triple what the span buffer holds). Idempotent. Called by the modules
    of the program that import jax anyway; it is never a reason to import
    jax."""
    global _installed, _hears_time_spans
    with _install_lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    register = getattr(monitoring, "register_event_time_span_listener", None)
    if register is not None:
        register(_on_time_span)
        _hears_time_spans = True
    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_duration_secs_listener(_on_duration)

