"""``train/compile``: every backend compile of this process as a span.

JAX reports each compile through ``jax.monitoring`` — a scalar when it
starts, the persistent cache's hit or miss while it runs, a duration when it
ends (a load from the cache is a "compile" of a few hundred milliseconds
there). The three listeners below turn that into one ``profile_span`` a
compile, so it lands in the task-event buffer as a slice at the time it
happened (``ray_tpu.timeline()``: which step recompiled, in any job) and, under
a profiler session, on the profiler's clock beside the device's idle gap.
"""

from __future__ import annotations

import threading

from ray_tpu.analysis import sanitizers as _san
from ray_tpu.tracing import names
from ray_tpu.tracing.events import named_span

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_OUTCOMES = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_registered = False
_register_lock = _san.make_lock("tracing.compiles")
# the compiles open on this thread (JAX compiles on the caller's thread and
# reports on it, so start, outcome and end of one compile meet here)
_open = threading.local()


def _on_scalar(event: str, value, **kw) -> None:
    if event != BACKEND_COMPILE_EVENT:
        return
    span = named_span(names.TRAIN_COMPILE,
                      {"fun_name": kw.get("fun_name", "?")})
    span.__enter__()
    stack = getattr(_open, "spans", None)
    if stack is None:
        stack = _open.spans = []
    stack.append([span, None])        # the span, the cache's outcome


def _on_event(event: str, **kw) -> None:
    outcome = _CACHE_OUTCOMES.get(event)
    stack = getattr(_open, "spans", None)
    if outcome is not None and stack:
        stack[-1][1] = outcome


def _on_duration(event: str, seconds: float, **kw) -> None:
    stack = getattr(_open, "spans", None)
    if event != BACKEND_COMPILE_EVENT or not stack:
        return
    span, cache = stack.pop()
    span.args = {"fun_name": span.args["fun_name"], "seconds": seconds,
                 "cache": cache}
    span.__exit__(None, None, None)


def record_compiles() -> None:
    """Record this process's backend compiles from now on (idempotent: one
    set of listeners a process, however many train loops it starts)."""
    global _registered
    with _register_lock:
        if _registered:
            return
        import jax

        jax.monitoring.register_scalar_listener(_on_scalar)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _registered = True
