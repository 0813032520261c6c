"""Compile spans: one process-wide ``jax.monitoring`` listener.

JAX reports each stage of making a program ready as a time span with the
function's name: tracing to a jaxpr, lowering to an MLIR module, and the
backend compile (which also wraps a load from the persistent compilation
cache, reported as a cache hit inside it).  While an enabled
``TelemetrySession`` is open, each such span becomes a ``compile`` span of
that session (``args``: ``phase`` trace / lower / backend, ``fun``, and on
backend spans ``cache_hit``) and bumps its ``COMPILE_COUNTERS``.  Compiles
are process-wide, so every open enabled session sees all of them.

The listener is registered once, on the first enabled session, and
returns at once while no enabled session is open.  Nested jits nest as
spans: a reader takes their union, never their sum.
"""
from __future__ import annotations

import threading
import time
import weakref

from .schema import COMPILE_COUNTERS

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
PHASES = {TRACE_EVENT: "trace", LOWER_EVENT: "lower",
          BACKEND_EVENT: "backend"}
COUNTER_OF = dict(zip((LOWER_EVENT, BACKEND_EVENT, CACHE_HIT_EVENT,
                       CACHE_MISS_EVENT), COMPILE_COUNTERS))

_open = weakref.WeakSet()       # enabled sessions not yet closed
# whether this thread's current backend compile was a cache hit
_hit = threading.local()
_registered = False


def watch(session) -> None:
    """Send compile spans and counts to ``session`` until ``unwatch``
    (or until it is garbage)."""
    global _registered
    if not _registered:
        import jax
        jax.monitoring.register_event_time_span_listener(_on_span)
        jax.monitoring.register_event_listener(_on_event)
        _registered = True
    for name in COUNTER_OF.values():
        session.registry.counter(name)
    _open.add(session)


def unwatch(session) -> None:
    _open.discard(session)


def _on_event(event: str, **_) -> None:
    if not _open or event not in COUNTER_OF:
        return
    if event == CACHE_HIT_EVENT:
        _hit.seen = True
    for s in list(_open):
        s.registry.counter(COUNTER_OF[event]).inc()


def _on_span(event: str, start: float, end: float, fun_name: str = "",
             **_) -> None:
    phase = PHASES.get(event)
    if not _open or phase is None:
        return
    args = {"phase": phase, "fun": fun_name}
    if phase == "backend":
        args["cache_hit"] = getattr(_hit, "seen", False)
        _hit.seen = False
    # JAX stamps the span on the wall clock; spans live on perf_counter
    off = time.time_ns() - time.perf_counter_ns()
    start_ns, end_ns = int(start * 1e9) - off, int(end * 1e9) - off
    for s in list(_open):
        if event in COUNTER_OF:
            s.registry.counter(COUNTER_OF[event]).inc()
        s.complete("compile", start_ns, end_ns, **args)


def seconds(events) -> float:
    """Seconds covered by the ``compile`` spans among Chrome trace
    ``events`` (``Tracer.events``): the union, so that a nested jit's time
    counts once."""
    ivs = sorted((e["ts"], e["ts"] + e["dur"]) for e in events
                 if e["name"] == "compile")
    total, end = 0.0, float("-inf")
    for s, e in ivs:
        if e > end:
            total += e - max(s, end)
            end = e
    return total / 1e6
