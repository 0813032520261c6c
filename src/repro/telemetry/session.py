"""TelemetrySession: one run's telemetry sinks, tied together.

A session owns the metrics registry (single source of truth for guard
and dispatch counters), the host-side tracer, and the JSONL writers.
Every ``RoundPipeline`` has one — a directory-less default session costs
~nothing (null spans, no writers) but still backs ``PipelineStats``
with a live registry.

Exported artifacts (written under ``dir``):

  rounds.jsonl    per-round events, pinned schema, deterministic fields
                  only — joins the bitwise crash→resume contract
  events.jsonl    fault / crash / lifecycle events (wall-order, exempt
                  from the resume contract)
  trace.json      Chrome trace-event timeline (open in Perfetto)
  metrics.prom    Prometheus text-format counter snapshot

``state()`` / ``restore()`` carry the rounds.jsonl byte offset through
run snapshots: on resume into the same directory the log is truncated
back to the last checkpoint and replayed, so crash→resume produces the
byte-identical round log of an uninterrupted run.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

from . import compile as compile_spans
from .export import JsonlWriter, write_prometheus
from .registry import MetricsRegistry
from .schema import LANE_FIELDS, LANE_INT_FIELDS
from .trace import Tracer


class TelemetrySession:
    def __init__(self, dir: Optional[str] = None, *,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 jax_profiler: bool = False) -> None:
        self.dir = dir
        self.registry = registry if registry is not None else MetricsRegistry()
        if tracer is None:
            tracer = Tracer(enabled=dir is not None, jax_profiler=jax_profiler)
        self.tracer = tracer
        self._rounds: Optional[JsonlWriter] = None
        self._events: Optional[JsonlWriter] = None
        if dir is not None:
            os.makedirs(dir, exist_ok=True)
            self._rounds = JsonlWriter(os.path.join(dir, "rounds.jsonl"))
            self._events = JsonlWriter(os.path.join(dir, "events.jsonl"))
        self._closed = False
        self._span_hists: Dict[str, object] = {}
        if tracer.enabled:
            compile_spans.watch(self)

    # -- spans ---------------------------------------------------------------
    def span(self, name: str, **args):
        """Trace span; an enabled one also observes its wall duration (the
        tracer's own reading) into the registry's ``span_seconds_<name>``
        histogram (metrics.prom only — timings are wall-clock and stay out
        of the deterministic round log).  A disabled tracer's: null span."""
        sp = self.tracer.span(name, **args)
        if self.tracer.enabled:
            sp.histogram = self._histogram(name)
        return sp

    def _histogram(self, name: str):
        hist = self._span_hists.get(name)
        if hist is None:
            hist = self._span_hists[name] = self.registry.histogram(
                f"span_seconds_{name}")
        return hist

    def complete(self, name: str, start_ns: int, end_ns: int,
                 **args) -> None:
        """A span whose bounds are known only afterwards
        (``time.perf_counter_ns``), recorded like ``span``'s."""
        if not self.tracer.enabled:
            return
        self.tracer.complete(name, start_ns, end_ns, **args)
        self._histogram(name).observe((end_ns - start_ns) / 1e9)

    # -- events --------------------------------------------------------------
    def round_event(self, cell: str, lane_row, rec) -> Dict[str, object]:
        """Build (and log) one per-round event from a lane row + RoundRecord.

        ``lane_row`` is the fp32 lane vector (``schema.LANE_FIELDS`` order);
        ``rec`` is the host-side ``RoundRecord`` for the same round.  The
        dict is returned for in-memory round logs regardless of whether a
        JSONL sink exists.  Deterministic fields only — no wall clock.
        """
        ev: Dict[str, object] = {"event": "round", "cell": cell}
        for name, v in zip(LANE_FIELDS, lane_row):
            ev[name] = int(v) if name in LANE_INT_FIELDS else float(v)
        ev["resource_used"] = float(rec.resource_used)
        ev["resource_wasted"] = float(rec.resource_wasted)
        ev["unique_participants"] = int(rec.unique_participants)
        ev["accuracy"] = None if rec.accuracy != rec.accuracy \
            else float(rec.accuracy)
        ev["loss"] = None if rec.loss != rec.loss else float(rec.loss)
        if self._rounds is not None:
            self._rounds.write(ev)
        return ev

    def event(self, kind: str, **fields) -> Dict[str, object]:
        """Log a non-round event (fault injection, crash, lifecycle)."""
        ev: Dict[str, object] = {"event": kind, **fields}
        self.registry.counter(f"events_{kind}").inc()
        if self._events is not None:
            self._events.write(ev)
        self.tracer.instant(kind, **fields)
        return ev

    # -- guard accounting (single writer) ------------------------------------
    def note_guard(self, acct, nonfinite: int, norm: int,
                   applied: bool) -> None:
        """The one call site that counts guard outcomes.

        Increments the registry counters (``PipelineStats.guard`` is a view
        over them) and forwards to the per-sim ``Accounting`` so summaries
        keep their pinned guard fields.
        """
        reg = self.registry
        if nonfinite:
            reg.counter("guard_rejected_nonfinite").inc(int(nonfinite))
        if norm:
            reg.counter("guard_rejected_norm").inc(int(norm))
        if not applied:
            reg.counter("guard_quorum_skips").inc()
        acct.note_guard(int(nonfinite), int(norm), applied)

    def note_robust(self, acct, rejected: int, trimmed: int) -> None:
        """The one call site that counts robust-aggregator outcomes
        (krum/norm-screen rejections, coordinate-band trims)."""
        reg = self.registry
        if rejected:
            reg.counter("guard_robust_rejected").inc(int(rejected))
        if trimmed:
            reg.counter("guard_robust_trimmed").inc(int(trimmed))
        acct.note_robust(int(rejected), int(trimmed))

    # -- lifecycle / resume --------------------------------------------------
    def flush(self) -> None:
        if self._rounds is not None:
            self._rounds.tell()
        if self._events is not None:
            self._events.tell()

    def state(self) -> Dict[str, int]:
        """Snapshot-carried state: the round-log byte offset."""
        return {"rounds_offset":
                self._rounds.tell() if self._rounds is not None else 0}

    def restore(self, state: Optional[Dict[str, int]]) -> None:
        """Re-enter the resume contract: truncate the round log back to the
        snapshot's offset so the resumed tail continues it exactly."""
        if state and self._rounds is not None:
            self._rounds.truncate_to(int(state.get("rounds_offset", 0)))

    def close(self) -> None:
        """Flush writers and export trace.json + metrics.prom (idempotent)."""
        if self._closed:
            return
        self._closed = True
        compile_spans.unwatch(self)
        if self._rounds is not None:
            self._rounds.close()
        if self._events is not None:
            self._events.close()
        if self.dir is not None:
            self.tracer.export(os.path.join(self.dir, "trace.json"))
            write_prometheus(self.registry,
                             os.path.join(self.dir, "metrics.prom"))
