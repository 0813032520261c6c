"""Reduction of a profiler trace to the events the metrics read.

A trace (``.xplane.pb``, written by ``jax.profiler.trace``) is reduced to
a plain dict, which is also the format of the recorded trace the tests
check against:

- ``device``: for each device plane (``/device:TPU:<i>``), the events of
  its op line (``XLA Ops``) as ``[name, start_ns, dur_ns]``, named by the
  HLO instruction (``while.388``, ``sweep_fused_staleness_apply.2``); an
  op that holds others (a loop) spans them, so the events nest;
- ``spans``: the program's host spans (``jax.profiler.TraceAnnotation``
  events with one of the names asked for) as ``[name, start_ns, dur_ns]``.

Host and device events share the profiler's clock.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"


def op_name(name: str) -> str:
    """``%fusion.91 = f32[208] fusion(...)`` -> ``fusion.91``."""
    return name[1:].split(" ", 1)[0] if name.startswith("%") else name


def load(trace_dir: str, span_names) -> dict:
    import jax
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = set(span_names)
    out = {"device": {}, "spans": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend([op_name(e.name), int(e.start_ns),
                                int(e.duration_ns)]
                               for e in line.events)
            out["device"][plane.name] = sorted(evs, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name in names)
    out["spans"].sort(key=lambda e: e[1])
    return out


def union(intervals) -> list:
    """Merged ``[start, end]`` intervals of ``(start, end)`` pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(events) -> int:
    """Length of the union of the events' intervals."""
    return sum(e - s for s, e in union((ev[1], ev[1] + ev[2])
                                       for ev in events))


def busy_s(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the device planes
    (None when the trace holds no device events)."""
    planes = [evs for evs in trace["device"].values() if evs]
    if not planes:
        return None
    return sum(busy_ns(evs) for evs in planes) / len(planes) / 1e9


def self_ns(spans, names) -> int:
    """Self time of the spans named ``names``: each one's duration less
    the part of it that spans nested inside it cover."""
    names = set(names)
    ivs = sorted((s, s + d, n) for n, s, d in spans)
    total = 0
    for i, (s, e, n) in enumerate(ivs):
        if n not in names:
            continue
        inner = []
        for s2, e2, _ in ivs[i + 1:]:
            if s2 >= e:
                break
            if e2 <= e and (s2, e2) != (s, e):
                inner.append((s2, e2))
        total += (e - s) - sum(b - a for a, b in union(inner))
    return total


def kernel_events(trace: dict, names) -> list:
    """Device events whose name starts with one of ``names``, all planes."""
    names = tuple(names)
    return [ev for evs in trace["device"].values() for ev in evs
            if ev[0].startswith(names)]


def op_self_ns(events) -> dict:
    """Self time per op name: each event's duration less the events
    nested directly inside it."""
    tot, stack = {}, []          # stack of [end, name]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][1]
            tot[parent] = tot.get(parent, 0) - min(d, stack[-1][0] - s)
        tot[name] = tot.get(name, 0) + d
        stack.append([s + d, name])
    return tot


def top_ops(trace: dict, k: int = 10) -> list:
    """The ``k`` device operations with the most self time, in seconds
    summed over the planes."""
    tot = {}
    for evs in trace["device"].values():
        for name, d in op_self_ns(evs).items():
            tot[name] = tot.get(name, 0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in best]


def idle_gaps(trace: dict, k: int = 10) -> list:
    """The ``k`` longest idle gaps of the first device, each named by the
    host span that covers most of it (``idle`` where none does)."""
    planes = [evs for evs in trace["device"].values() if evs]
    if not planes:
        return []
    busy = union((e[1], e[1] + e[2]) for e in planes[0])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        best, cover = "idle", 0
        for n, s2, d in trace["spans"]:
            c = min(e, s2 + d) - max(s, s2)
            if c > cover:
                best, cover = n, c
        out.append([best, (e - s) / 1e9])
    return out
