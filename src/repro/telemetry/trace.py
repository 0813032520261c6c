"""Host-side tracer: nested spans exported as Chrome trace-event JSON.

``Tracer.span(name, **args)`` is a context manager instrumenting the
host stages of a run (build / upload / schedule / pack / put / dispatch /
fetch / eval / finalize / compile ... — see ``schema.SPAN_NAMES``).  The
recorded timeline exports as Chrome trace-event JSON, loadable in
Perfetto (https://ui.perfetto.dev — drag the file in) or
``chrome://tracing``.  ``Tracer.complete`` records a span whose bounds are
known only afterwards (a ``Simulator``'s construction, a compile that
``jax.monitoring`` reports when it ends).

A disabled tracer returns a shared null context: span call sites stay
unconditional in the hot loop at ~zero cost.  ``jax_profiler=True``
additionally wraps each span in ``jax.profiler.TraceAnnotation`` so host
spans line up with device events inside a ``jax.profiler.trace()``
capture.

One clock: every event's ``ts`` counts from the tracer's epoch ``_t0``
(``time.perf_counter_ns``).  With ``jax_profiler=True`` the epoch is taken
inside a zero-length ``tracer.epoch`` annotation, so in a capture that was
running when the tracer was made, that event's start is ``ts = 0``: adding
it to every ``ts`` maps ``trace.json`` onto the profiler's host clock,
``complete`` spans included.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional

_NULL_SPAN = contextlib.nullcontext()
EPOCH_SPAN = "tracer.epoch"


def capture_span(name: str):
    """A ``jax.profiler.TraceAnnotation`` while a profiler capture runs,
    else the null span — for work that runs before any session exists
    (``Simulator`` construction)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name) if TraceAnnotation.is_enabled() \
        else _NULL_SPAN


class _Span:
    """One enabled span: reads the clock once at each end, inside the
    profiler annotation it holds (which so also covers the span's own
    bookkeeping); ``histogram``, where set, observes the span's seconds."""
    __slots__ = ("tracer", "name", "args", "ann", "start", "histogram")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, object]):
        self.tracer, self.name, self.args = tracer, name, args
        self.ann = self.histogram = None

    def __enter__(self):
        if self.tracer._annotation is not None:
            self.ann = self.tracer._annotation(self.name)
            self.ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.tracer.complete(self.name, self.start, end, **self.args)
        if self.histogram is not None:
            self.histogram.observe((end - self.start) / 1e9)
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class Tracer:
    """Records "X" (complete) trace events with µs timestamps."""

    def __init__(self, enabled: bool = True,
                 jax_profiler: bool = False) -> None:
        self.enabled = enabled
        self.jax_profiler = jax_profiler
        self.events: List[Dict[str, object]] = []
        self._annotation = self._step = None
        if enabled and jax_profiler:
            from jax.profiler import StepTraceAnnotation, TraceAnnotation
            self._annotation = TraceAnnotation
            self._step = StepTraceAnnotation
            with TraceAnnotation(EPOCH_SPAN):
                self._t0 = time.perf_counter_ns()
        else:
            self._t0 = time.perf_counter_ns()

    def span(self, name: str, **args):
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def step(self, name: str, step_num: int):
        """``jax.profiler.StepTraceAnnotation`` (the profiler's step
        marker) when this tracer is on and feeds the profiler, else the
        null span; it records no event of its own."""
        if not (self.enabled and self._step is not None):
            return _NULL_SPAN
        return self._step(name, step_num=step_num)

    def complete(self, name: str, start_ns: int, end_ns: int,
                 **args) -> None:
        """Record a span from its bounds on ``time.perf_counter_ns``."""
        if not self.enabled:
            return
        ev: Dict[str, object] = {
            "name": name, "ph": "X", "pid": os.getpid(),
            "tid": threading.get_ident() % 2**31,
            "ts": (start_ns - self._t0) / 1e3,   # µs from the epoch
            "dur": (end_ns - start_ns) / 1e3,
        }
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker (crash/fault injections, etc.)."""
        if not self.enabled:
            return
        ev: Dict[str, object] = {
            "name": name, "ph": "i", "s": "g", "pid": os.getpid(),
            "tid": threading.get_ident() % 2**31,
            "ts": (time.perf_counter_ns() - self._t0) / 1e3,
        }
        if args:
            ev["args"] = args
        self.events.append(ev)

    def chrome_trace(self) -> Dict[str, object]:
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms"}

    def export(self, path: str) -> Optional[str]:
        """Write Chrome trace-event JSON; returns the path (None if empty)."""
        if not self.events:
            return None
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(), fh)
        return path
