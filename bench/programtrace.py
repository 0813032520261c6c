"""The program's own spans and the scopes of its device ops, read from the
traced window's profile.

``tracefile.load`` keeps the host spans of the round loop alone and names
device ops by their HLO instruction. The readers of the program's
lifecycle, of its idle time and of its named scopes need more of the same
capture:

- ``spans``: every host event named in the program's
  ``repro.telemetry.schema.SPAN_NAMES`` (``build``, ``upload``, ``put``,
  ``finalize`` ... as far as the program under test has them), as
  ``[name, start_ns, dur_ns]``;
- ``device``: for each device plane, its ``XLA Ops`` events as
  ``[name, start_ns, dur_ns, scope]``, where ``scope`` is the op's
  ``jax.named_scope`` path: on a TPU the ``tf_op`` stat of the event's
  metadata (``jit(prog)/while/body/train/dot_general:``), "" where the op
  has none. ``jax.profiler.ProfileData`` shows an event's own stats only,
  so the metadata is read from the file's ``XSpace`` protobuf, with a
  schema here that holds just the fields read.

A reader takes ``ctx.program_trace`` where the harness gives it (the tests
give a recorded one), else the capture under ``.bench_out/trace``, reduced
once per process. A program without the spans a reader needs yields None
there, never an error.
"""
from __future__ import annotations

import functools
import glob
import os
import pathlib

import tracefile

TRACE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".bench_out" \
    / "trace"
SCOPE_STAT = "tf_op"


def of(ctx):
    """The reduced program trace of the run ``ctx`` describes (None where
    no capture exists)."""
    if getattr(ctx, "program_trace", None) is not None:
        return ctx.program_trace
    paths = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        return None
    return _load(paths[0], os.path.getmtime(paths[0]))


# The fields of tsl/profiler/protobuf/xplane.proto read here: (message,
# [(field, number, type, repeated, message type)]); the maps are their
# wire form, repeated key/value entries.
_SCHEMA = [
    ("XSpace", [("planes", 1, "message", True, "XPlane")]),
    ("XPlane", [("name", 2, "string", False, None),
                ("lines", 3, "message", True, "XLine"),
                ("event_metadata", 4, "message", True, "EventMetadataEntry"),
                ("stat_metadata", 5, "message", True, "StatMetadataEntry")]),
    ("EventMetadataEntry", [("key", 1, "int64", False, None),
                            ("value", 2, "message", False,
                             "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, "int64", False, None),
                           ("value", 2, "message", False, "XStatMetadata")]),
    ("XLine", [("name", 2, "string", False, None),
               ("events", 4, "message", True, "XEvent")]),
    ("XEvent", [("metadata_id", 1, "int64", False, None)]),
    ("XStat", [("metadata_id", 1, "int64", False, None),
               ("str_value", 5, "string", False, None),
               ("ref_value", 7, "uint64", False, None)]),
    ("XEventMetadata", [("name", 2, "string", False, None),
                        ("stats", 5, "message", True, "XStat")]),
    ("XStatMetadata", [("name", 2, "string", False, None)]),
]


@functools.lru_cache(maxsize=1)
def _xspace():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto
    for msg, fields in _SCHEMA:
        m = fdp.message_type.add(name=msg)
        for name, number, typ, repeated, ref in fields:
            m.field.add(name=name, number=number,
                        type=getattr(F, "TYPE_" + typ.upper()),
                        label=F.LABEL_REPEATED if repeated
                        else F.LABEL_OPTIONAL,
                        type_name=f".bench_xplane.{ref}" if ref else None)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def op_scopes(path: str) -> dict:
    """For each device plane, the ``tf_op`` scope path of each ``XLA Ops``
    event, in the events' order (with the event's name, for a check)."""
    space = _xspace()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        scope_of = {}
        for entry in plane.event_metadata:
            scope = ""
            for st in entry.value.stats:
                if stat_names.get(st.metadata_id) == SCOPE_STAT:
                    scope = st.str_value or stat_names.get(st.ref_value, "")
            scope_of[entry.key] = (entry.value.name, scope)
        out[plane.name] = [scope_of.get(e.metadata_id, ("", ""))
                           for line in plane.lines
                           if line.name == tracefile.OPS_LINE
                           for e in line.events]
    return out


@functools.lru_cache(maxsize=1)
def _load(path: str, _mtime: float) -> dict:
    import jax
    from repro.telemetry.schema import SPAN_NAMES
    data = jax.profiler.ProfileData.from_file(path)
    scopes = op_scopes(path)
    names = set(SPAN_NAMES)
    out = {"device": {}, "spans": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = [e for line in plane.lines
                   if line.name == tracefile.OPS_LINE for e in line.events]
            tags = scopes.get(plane.name, [])
            if len(tags) != len(evs):
                tags = [("", "")] * len(evs)
            evs = [[tracefile.op_name(e.name), int(e.start_ns),
                    int(e.duration_ns), scope if name == e.name else ""]
                   for e, (name, scope) in zip(evs, tags)]
            out["device"][plane.name] = sorted(evs, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            out["spans"].extend([e.name, int(e.start_ns), int(e.duration_ns)]
                                for line in plane.lines
                                for e in line.events if e.name in names)
    out["spans"].sort(key=lambda e: e[1])
    return out


def spans(trace: dict, names) -> list:
    names = set(names)
    return [s for s in trace["spans"] if s[0] in names]


def has(trace: dict, *names) -> bool:
    """Whether the program emitted every span named."""
    present = {s[0] for s in trace["spans"]}
    return all(n in present for n in names)


def in_scope(scope: str, name: str) -> bool:
    """Whether a ``named_scope`` path (``jit(prog)/while/body/train/...``)
    passes through the scope ``name``."""
    return name in scope.split("/")


def window(trace: dict):
    """The traced window as the program saw it: first span start to last
    span end (the benchmark's code around the simulations is outside)."""
    if not trace["spans"]:
        return None
    return (min(s[1] for s in trace["spans"]),
            max(s[1] + s[2] for s in trace["spans"]))
