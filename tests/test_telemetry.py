"""Telemetry subsystem contracts (ISSUE PR-7):

  * the device lane / round-event schema is pinned — renaming, reordering
    or widening it is an intentional breaking change that must edit this
    file;
  * level-2 telemetry is bit-transparent: the instrumented run's summary
    AND per-round records equal a telemetry-off run's on every fused
    substrate (single-dispatch, K-round chunked, participant-sharded);
  * the lane rides the existing round program: still at most ONE
    cross-shard collective (the aggregation psum) in the compiled HLO, and
    the hot loop stays clean under ``jax.transfer_guard("disallow")``;
  * guard accounting has ONE writer — the session's registry counters, the
    pipeline's ``stats.guard`` view and the per-cell ``Accounting`` fields
    all agree under injected faults;
  * exports are loadable: ``rounds.jsonl`` rows carry exactly
    ``ROUND_EVENT_KEYS`` in order, ``trace.json`` is a Chrome trace-event
    JSON (Perfetto-loadable), ``metrics.prom`` parses as Prometheus 0.0.4
    text.
"""
import dataclasses
import json
import math
import re

import jax
import numpy as np
import pytest

from repro.faults import FaultPlan, FaultSpec
from repro.sim import SimConfig, Simulator
from repro.sim.pipeline import RoundPipeline
from repro.sweeps.runner import summaries_equal
from repro.telemetry import (MetricsRegistry, TelemetrySession, Tracer,
                             write_prometheus)
from repro.telemetry.registry import CounterView
from repro.telemetry.schema import (GUARD_COUNTERS, LANE_FIELDS,
                                    LANE_INT_FIELDS, LANE_WIDTH, N_LANE_HOST,
                                    ROUND_EVENT_KEYS)

BASE = dict(n_learners=30, rounds=8, eval_every=4, n_target=4,
            mapping="label_uniform", saa=True, selector="priority")
N_DEV = len(jax.devices())


def _cfg(**kw):
    return SimConfig(**{**BASE, **kw})


def _records_equal(a, b) -> bool:
    if len(a.records) != len(b.records):
        return False
    for ra, rb in zip(a.records, b.records):
        ka = (ra.round_idx, ra.sim_time, ra.n_selected, ra.n_fresh,
              ra.n_stale, ra.resource_used, ra.resource_wasted,
              ra.unique_participants)
        kb = (rb.round_idx, rb.sim_time, rb.n_selected, rb.n_fresh,
              rb.n_stale, rb.resource_used, rb.resource_wasted,
              rb.unique_participants)
        accs = (ra.accuracy == rb.accuracy
                or (ra.accuracy != ra.accuracy and rb.accuracy != rb.accuracy))
        if ka != kb or not accs:
            return False
    return True


# ---------------------------------------------------------------------------
# Pinned schema
# ---------------------------------------------------------------------------


def test_lane_schema_is_pinned():
    assert LANE_FIELDS == (
        "round", "sim_time", "cohort", "fresh", "stale_landed",
        "cache_occupancy", "l2_min", "l2_mean", "l2_max", "nonfinite_rows",
        "rejected_nonfinite", "rejected_norm", "robust_rejected",
        "robust_trimmed", "survivors", "applied")
    assert LANE_WIDTH == 16
    assert N_LANE_HOST == 6
    assert LANE_FIELDS[:N_LANE_HOST] == (
        "round", "sim_time", "cohort", "fresh", "stale_landed",
        "cache_occupancy")
    assert LANE_INT_FIELDS <= set(LANE_FIELDS)


def test_round_event_schema_is_pinned():
    assert ROUND_EVENT_KEYS == ("event", "cell") + LANE_FIELDS + (
        "resource_used", "resource_wasted", "unique_participants",
        "accuracy", "loss")


# ---------------------------------------------------------------------------
# Registry / tracer units
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help")
    c.inc()
    c.inc(4)
    assert reg.value("c_total") == 5
    assert reg.counter("c_total") is c          # get-or-create
    with pytest.raises(TypeError):
        reg.gauge("c_total")                    # kind mismatch
    reg.gauge("g").set(2.5)
    h = reg.histogram("h")
    for v in (0.0005, 0.05, 5.0, 50.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["c_total"] == 5 and snap["g"] == 2.5
    txt = reg.prometheus_text()
    assert "# TYPE c_total counter" in txt
    assert 'h_bucket{le="+Inf"} 4' in txt
    assert "h_count 4" in txt


def test_counter_view_is_a_dict_over_registry_counters():
    reg = MetricsRegistry()
    view = CounterView(reg, "guard_", ("a", "b"))
    view["a"] += 3
    view["b"] = 7
    assert reg.value("guard_a") == 3 and reg.value("guard_b") == 7
    assert dict(view) == {"a": 3, "b": 7}
    assert view == {"a": 3, "b": 7} and len(view) == 2 and "a" in view


def test_tracer_spans_and_export(tmp_path):
    tr = Tracer()
    with tr.span("outer", rounds=2):
        with tr.span("inner"):
            pass
    tr.instant("mark", round=1)
    doc = tr.chrome_trace()
    names = [e["name"] for e in doc["traceEvents"]]
    assert set(names) == {"outer", "inner", "mark"}
    by = {e["name"]: e for e in doc["traceEvents"]}
    assert by["inner"]["ph"] == "X" and by["mark"]["ph"] == "i"
    # nesting: inner lies within outer on the timeline
    assert by["outer"]["ts"] <= by["inner"]["ts"]
    assert (by["inner"]["ts"] + by["inner"]["dur"]
            <= by["outer"]["ts"] + by["outer"]["dur"])
    p = tmp_path / "trace.json"
    tr.export(p)
    assert json.loads(p.read_text())["traceEvents"]
    off = Tracer(enabled=False)
    with off.span("x"):
        pass
    assert not off.chrome_trace()["traceEvents"]


# ---------------------------------------------------------------------------
# Level-2 bit-transparency on every fused substrate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sub", ["fused", "chunked", "sharded"])
def test_level2_is_bit_transparent(sub, tmp_path):
    extra = {"fused": {},
             "chunked": {"rounds_per_dispatch": 4},
             "sharded": {"shard_participants": True}}[sub]
    ref = Simulator(_cfg(**extra)).run()
    sess = TelemetrySession(str(tmp_path / sub))
    got = Simulator(_cfg(telemetry=2, **extra)).run(telemetry=sess)
    sess.close()
    assert summaries_equal(dict(ref.summary()), dict(got.summary())), \
        (sub, ref.summary(), got.summary())
    assert _records_equal(ref, got)
    # one pinned-schema event per recorded round, in the JSONL and in memory
    evs = [json.loads(l) for l in
           (tmp_path / sub / "rounds.jsonl").read_text().splitlines()]
    assert len(evs) == got.summary()["rounds"]
    assert got.round_events == evs
    for ev in evs:
        assert tuple(ev) == ROUND_EVENT_KEYS
        assert ev["event"] == "round"
        for k in LANE_INT_FIELDS:
            assert isinstance(ev[k], int), k


def test_round_events_reflect_the_schedule(tmp_path):
    """Device-computed lane values agree with the host accounting records:
    cohort/fresh/stale per event match the Accounting row for that round."""
    sess = TelemetrySession(str(tmp_path))
    acct = Simulator(_cfg(telemetry=2)).run(telemetry=sess)
    sess.close()
    assert len(acct.round_events) == len(acct.records)
    for ev, rec in zip(acct.round_events, acct.records):
        assert ev["round"] == rec.round_idx
        assert ev["cohort"] == rec.n_selected
        assert ev["fresh"] == rec.n_fresh
        assert ev["stale_landed"] == rec.n_stale
        assert ev["resource_used"] == rec.resource_used
        eva = math.nan if ev["accuracy"] is None else ev["accuracy"]
        assert eva == rec.accuracy or (eva != eva
                                       and rec.accuracy != rec.accuracy)
        if ev["applied"]:
            assert ev["l2_max"] >= ev["l2_mean"] >= ev["l2_min"] > 0


# ---------------------------------------------------------------------------
# Guard accounting: one writer, three agreeing views
# ---------------------------------------------------------------------------


def test_guard_counters_single_writer(tmp_path):
    plan = FaultPlan(n_learners=BASE["n_learners"], rounds=BASE["rounds"],
                     specs=(FaultSpec("nan", prob=0.2),
                            FaultSpec("scale", prob=0.1, scale=1e4)), seed=7)
    sess = TelemetrySession(str(tmp_path))
    sim = Simulator(_cfg(telemetry=2, guard=True, guard_reject_mult=5.0),
                    fault_plan=plan)
    pipe = RoundPipeline([sim], telemetry=sess)
    accts = pipe.run()
    s = accts[0].summary()
    assert s["rejected_nonfinite"] > 0
    # stats.guard is a live view over the session registry's counters
    assert dict(pipe.stats.guard) == {
        "rejected_nonfinite": sess.registry.value("guard_rejected_nonfinite"),
        "rejected_norm": sess.registry.value("guard_rejected_norm"),
        "quorum_skips": sess.registry.value("guard_quorum_skips"),
        "robust_rejected": sess.registry.value("guard_robust_rejected"),
        "robust_trimmed": sess.registry.value("guard_robust_trimmed")}
    # ... and both equal the sum over the per-cell Accounting fields
    assert pipe.stats.guard["rejected_nonfinite"] == sum(
        a.rejected_nonfinite for a in accts)
    assert pipe.stats.guard["rejected_norm"] == sum(
        a.rejected_norm for a in accts)
    assert pipe.stats.guard["quorum_skips"] == sum(
        a.quorum_skips for a in accts)
    for name in GUARD_COUNTERS:
        assert name in sess.registry
    # the lane's guard tail reconciles with the same totals
    assert sum(e["rejected_nonfinite"] for e in accts[0].round_events) \
        == s["rejected_nonfinite"]
    sess.close()


# ---------------------------------------------------------------------------
# Program-structure invariants survive the lane
# ---------------------------------------------------------------------------


def test_lane_program_keeps_one_collective():
    cfg = _cfg(telemetry=2, shard_participants=True, rounds_per_dispatch=4)
    pipe = RoundPipeline([Simulator(cfg)],
                         telemetry=TelemetrySession())
    orig, captured = pipe._prog, []

    def wrapper(*args):
        if not captured:
            captured.append(orig.lower(*args).compile().as_text())
        return orig(*args)

    pipe._prog = wrapper
    pipe.run()
    txt = captured[0]
    n_all_reduce = len(re.findall(r"all-reduce(?:-start)?\(", txt))
    for op in ("all-gather", "all-to-all", "collective-permute",
               "reduce-scatter"):
        assert f"{op}(" not in txt, f"unexpected {op} with the lane enabled"
    if N_DEV > 1:
        assert n_all_reduce == 1, f"expected 1 all-reduce, found {n_all_reduce}"
    else:
        assert n_all_reduce <= 1


def test_lane_clean_under_transfer_guard(tmp_path):
    cfg = _cfg(telemetry=2, shard_participants=True, rounds_per_dispatch=4)
    RoundPipeline([Simulator(cfg)]).run()            # warm compiles
    sess = TelemetrySession(str(tmp_path))
    pipe = RoundPipeline([Simulator(cfg)], telemetry=sess)
    accts = pipe.run(transfer_guard=True)
    sess.close()
    assert accts[0].summary()["rounds"] > 0
    assert len(accts[0].round_events) == accts[0].summary()["rounds"]


# ---------------------------------------------------------------------------
# Session exports + host-level (level 1) spans
# ---------------------------------------------------------------------------


def test_session_exports_are_loadable(tmp_path):
    sess = TelemetrySession(str(tmp_path))
    Simulator(_cfg(telemetry=2)).run(telemetry=sess)
    sess.close()
    trace = json.loads((tmp_path / "trace.json").read_text())
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"schedule", "pack", "dispatch", "fetch"} <= names
    for e in trace["traceEvents"]:
        assert e["ph"] in ("X", "i")
        assert isinstance(e["ts"], (int, float))
    prom = (tmp_path / "metrics.prom").read_text()
    assert re.search(r"^pipeline_rounds \d+$", prom, re.M)
    assert re.search(r"^guard_rejected_nonfinite \d+$", prom, re.M)
    # span durations land as histograms (wall-clock — prom snapshot only)
    assert re.search(r"^span_seconds_dispatch_count \d+$", prom, re.M)
    # close() is idempotent and the registry snapshot stays readable
    sess.close()
    assert sess.registry.value("pipeline_rounds") > 0


def test_checked_in_counter_sums_the_census():
    # pipeline_checked_in: the census each round hands to selection, once a
    # round; dynamic availability so the census changes round to round
    census = []

    class Census(Simulator):
        def _available_now(self, t_now):
            available = super()._available_now(t_now)
            census.append(len(available))
            return available

    sess = TelemetrySession()
    Census(_cfg(n_learners=60, rounds=12, dynamic_availability=True)).run(
        telemetry=sess)
    assert len(census) == 12 and len(set(census)) > 1
    assert sess.registry.value("pipeline_checked_in") == sum(census) > 0


def test_write_prometheus_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("x_total").inc(3)
    p = tmp_path / "m.prom"
    write_prometheus(reg, p)
    assert "x_total 3" in p.read_text()


def test_level1_spans_without_lane(tmp_path):
    """telemetry=1 on the legacy engine loop: spans + registry, no lane, no
    round events, summary untouched."""
    ref = Simulator(_cfg(fast_path=False, fused_rounds=False)).run()
    sess = TelemetrySession(str(tmp_path))
    got = Simulator(_cfg(fast_path=False, fused_rounds=False,
                         telemetry=1)).run(telemetry=sess)
    sess.close()
    assert summaries_equal(dict(ref.summary()), dict(got.summary()))
    assert got.round_events == []
    trace = json.loads((tmp_path / "trace.json").read_text())
    assert {"schedule", "dispatch", "fetch"} <= \
        {e["name"] for e in trace["traceEvents"]}


def test_sweep_round_logs_accessor(tmp_path):
    from repro.sweeps import SweepRunner, SweepSpec
    cells = SweepSpec(axes={"saa": [False, True]},
                      base={k: v for k, v in BASE.items() if k != "saa"},
                      seeds=(0,)).expand()
    cells = [dataclasses.replace(c, config=dataclasses.replace(
        c.config, telemetry=2)) for c in cells]
    sess = TelemetrySession(str(tmp_path))
    results = SweepRunner(cells, telemetry=sess).run()
    sess.close()
    logs = results.round_logs()
    assert set(logs) == {c.name for c in cells}
    for name, evs in logs.items():
        assert all(ev["cell"] == name for ev in evs)
    # the summary payload stays lean: no round logs in the JSON dict
    assert "round_logs" not in results.to_json_dict()
    # per-cell JSONL rows equal the in-memory logs, interleaved by round
    evs = [json.loads(l) for l in
           (tmp_path / "rounds.jsonl").read_text().splitlines()]
    for name in logs:
        assert [e for e in evs if e["cell"] == name] == logs[name]
