"""Tracing inside the program: lifecycle and compile spans, one clock with
the JAX profiler, named scopes in the round program, row counters.

- the tracer's epoch maps its events (``span`` and ``complete`` alike)
  onto the profiler's host plane within 100 µs, on the CPU backend;
- a run under an enabled session emits ``build``, ``upload``, ``put`` and
  ``finalize`` in order (the legacy loop ``build`` and ``finalize``); a
  disabled session records nothing;
- a fresh ``jax.jit`` under an enabled session yields ``compile`` spans
  and counters, and none once it is closed or when it is off;
- the round and eval programs carry their named scopes, and a traced run
  is bitwise the run without a session;
- the trained and padding row counters add up to the padding bucket.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import SimConfig, Simulator
from repro.sim.pipeline import RoundPipeline
from repro.sweeps.runner import summaries_equal
from repro.telemetry import COMPILE_COUNTERS, TelemetrySession, Tracer
from repro.telemetry import compile as compile_spans
from repro.telemetry.trace import EPOCH_SPAN

BASE = dict(n_learners=30, rounds=8, eval_every=4, n_target=4,
            mapping="label_uniform", saa=True, selector="priority")
LIFECYCLE = ("build", "upload", "put", "finalize")


def _cfg(**kw):
    return SimConfig(**{**BASE, **kw})


def _ordered(tele, skip=("compile",)):
    return sorted((e for e in tele.tracer.events if e["name"] not in skip),
                  key=lambda e: e["ts"])


def test_tracer_epoch_lines_up_with_the_profiler_host_plane(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        tele = TelemetrySession(tracer=Tracer(enabled=True,
                                              jax_profiler=True))
        sim = Simulator(_cfg(rounds=4, eval_every=2))
        sim.run(telemetry=tele)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    prof = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats) if e.name == "round" else {}
                    prof.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.duration_ns), stats))
    (epoch,) = prof[EPOCH_SPAN]
    offset = epoch[0]
    ours = {}
    for e in tele.tracer.events:
        if e["name"] != "compile":
            ours.setdefault(e["name"], []).append(e)
    # ``build`` came through Tracer.complete, the rest through spans
    assert {"build", "upload", "schedule", "pack", "put", "dispatch",
            "finalize"} <= set(ours)
    for name, evs in ours.items():
        assert len(evs) == len(prof[name]), name
        for e, (start, dur, _) in zip(evs, sorted(prof[name])):
            assert abs(offset + e["ts"] * 1e3 - start) < 100_000, name
            assert abs(e["dur"] * 1e3 - dur) < 100_000, name
    # one profiler step per dispatch, numbered by the chunk's first round
    steps = [s[2]["step_num"] for s in sorted(prof["round"])]
    assert len(steps) == len(ours["dispatch"])
    assert steps == sorted(set(steps))
    assert all(0 <= k < sim.cfg.rounds for k in steps)


def test_run_emits_lifecycle_spans_in_order():
    tele = TelemetrySession(tracer=Tracer(enabled=True))
    Simulator(_cfg()).run(telemetry=tele)
    names = [e["name"] for e in _ordered(tele)]
    assert names[:2] == ["build", "upload"]
    assert names[-1] == "finalize"
    assert all(n in names for n in LIFECYCLE)
    # put sits between pack and dispatch, nested in neither
    evs = _ordered(tele)
    for i, e in enumerate(evs):
        if e["name"] == "put":
            assert evs[i - 1]["name"] == "pack"
            assert evs[i - 1]["ts"] + evs[i - 1]["dur"] <= e["ts"]
            assert evs[i + 1]["name"] == "dispatch"
            assert e["ts"] + e["dur"] <= evs[i + 1]["ts"]
    # each span's histogram observed the tracer's own reading
    hist = tele.registry.histogram("span_seconds_put")
    puts = [e["dur"] for e in evs if e["name"] == "put"]
    assert hist.count == len(puts)
    assert hist.sum == pytest.approx(sum(puts) / 1e6, rel=1e-9)


def test_legacy_loop_emits_build_and_finalize():
    tele = TelemetrySession(tracer=Tracer(enabled=True))
    Simulator(_cfg(fast_path=False, fused_rounds=False)).run(telemetry=tele)
    names = [e["name"] for e in _ordered(tele)]
    assert names[0] == "build" and names[-1] == "finalize"
    assert "upload" not in names


def test_disabled_session_records_nothing():
    tele = TelemetrySession()
    Simulator(_cfg()).run(telemetry=tele)
    assert tele.tracer.events == []
    assert not any(n in tele.registry for n in COMPILE_COUNTERS)
    assert "span_seconds_build" not in tele.registry


def test_compile_spans_and_counters_only_while_open():
    on = TelemetrySession(tracer=Tracer(enabled=True))
    off = TelemetrySession()
    x = jnp.arange(7.0)
    jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    spans = [e for e in on.tracer.events if e["name"] == "compile"]
    phases = {e["args"]["phase"] for e in spans}
    assert {"trace", "lower", "backend"} <= phases
    assert all("cache_hit" in e["args"] for e in spans
               if e["args"]["phase"] == "backend")
    assert on.registry.value("compile_programs_lowered") >= 1
    assert on.registry.value("compile_backend_compiles") >= 1
    assert 0 < compile_spans.seconds(on.tracer.events)
    assert off.tracer.events == []
    on.close()
    n, lowered = len(on.tracer.events), \
        on.registry.value("compile_programs_lowered")
    jax.jit(lambda v: v * 5.0 - 1.0)(x).block_until_ready()
    assert len(on.tracer.events) == n
    assert on.registry.value("compile_programs_lowered") == lowered


def test_compile_seconds_take_the_union_of_nested_spans():
    ev = lambda ts, dur: {"name": "compile", "ts": ts, "dur": dur}
    events = [ev(0, 100), ev(10, 50), ev(200, 10),
              {"name": "pack", "ts": 0, "dur": 1e6}]
    assert compile_spans.seconds(events) == pytest.approx(110 / 1e6)


def _capture(pipe, attr):
    """Wrap a pipeline's jitted program to keep the abstract arguments of
    its first call (the statics as they are)."""
    real, seen = getattr(pipe, attr), []

    def spy(*args):
        if not seen:
            seen.append(tuple(jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
                if hasattr(a, "shape") else a, args)))
        return real(*args)
    setattr(pipe, attr, spy)
    return real, seen


def test_named_scopes_in_the_programs_and_bitwise_parity():
    ref = Simulator(_cfg())
    ref.run()
    sim = Simulator(_cfg())
    tele = TelemetrySession(tracer=Tracer(enabled=True))
    pipe = RoundPipeline([sim], telemetry=tele)
    prog, prog_args = _capture(pipe, "_prog")
    ev, ev_args = _capture(pipe, "_eval")
    pipe.run()
    np.testing.assert_array_equal(np.asarray(sim.flat_params),
                                  np.asarray(ref.flat_params))
    assert summaries_equal(dict(sim.acct.summary()),
                           dict(ref.acct.summary()))
    hlo = prog.lower(*prog_args[0]).compile().as_text()
    for scope in ("train", "cache", "aggregate", "apply"):
        assert f"/{scope}/" in hlo, scope
    assert "/eval/" in ev.lower(*ev_args[0]).compile().as_text()


def test_trained_and_pad_rows_fill_the_bucket():
    """Trained rows are the planned learners that do not drop; with the
    padding they fill each dispatch's row bucket."""
    class Counting(Simulator):
        survivors = 0

        def _schedule_round(self, r, plan):
            Counting.survivors += int(np.sum(~np.isfinite(plan.drop_at)))
            return super()._schedule_round(r, plan)

    sim = Counting(_cfg())
    pipe = RoundPipeline([sim])
    real, buckets = pipe._materialize, []

    def spy(works):
        out = real(works)
        buckets.append(out[2][0] * len(works))      # r_b x rounds
        return out
    pipe._materialize = spy
    pipe.run()
    st = pipe.stats.as_dict()
    assert st["trained_rows"] == Counting.survivors > 0
    assert st["trained_rows"] + st["pad_rows"] == sum(buckets)
