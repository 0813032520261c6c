"""CPU tests of the readers of the program's own spans and named scopes
(``programtrace.py`` and the metrics that use it): by hand on a small
trace, on a recorded TPU capture, and on a program without those spans,
where each reads None; and the pipeline's trained-row counter against the
rows the harness's recording ``Simulator`` counts.
"""
import argparse
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import programtrace  # noqa: E402
import run  # noqa: E402

NEW = ("host.dispatch_ms_per_round", "host.lifecycle_ms_per_sim",
       "device.idle_unattributed_share", "device.train_ms_per_round")
TRAIN = "jit(prog)/while/body/train/dot_general"

# two simulations of 2 and 1 rounds; the device runs one op between them
HAND = {
    "spans": [["build", 0, 100], ["upload", 100, 200],
              ["schedule", 300, 100], ["pack", 400, 50], ["put", 450, 20],
              ["dispatch", 470, 30], ["fetch", 500, 20], ["eval", 520, 80],
              ["schedule", 600, 100], ["pack", 700, 50], ["put", 750, 10],
              ["dispatch", 760, 40], ["finalize", 800, 100],
              ["build", 1000, 50], ["upload", 1050, 50],
              ["schedule", 1100, 100], ["pack", 1200, 50], ["put", 1250, 10],
              ["dispatch", 1260, 40], ["finalize", 1300, 100]],
    "device": {"/device:TPU:0": [
        ["while.1", 470, 60, "jit(prog)/while"],
        ["fusion.1", 475, 20, TRAIN],
        ["fusion.2", 500, 10, "jit(prog)/while/body/apply/add"],
        ["fusion.1", 770, 20, TRAIN],
        ["fusion.9", 940, 30, "jit(f)/mul"],
        ["fusion.3", 1270, 20, "jit(prog)/while/body/train/mul"]]},
}


def _read(trace, rounds=3):
    ctx = argparse.Namespace(program_trace=trace, rounds=rounds)
    return {m: run._module(BENCH / "metrics" / f"{m}.py").read(ctx)
            for m in NEW}


def test_readers_by_hand():
    got = _read(HAND)
    # put 40 + dispatch 110 + fetch 20 + eval 80 ns over 3 rounds
    assert got["host.dispatch_ms_per_round"] == pytest.approx(250 / 3 / 1e6)
    # build 150 + upload 250 + finalize 200 ns over 2 simulations
    assert got["host.lifecycle_ms_per_sim"] == pytest.approx(300 / 1e6)
    # idle 1270 ns of [0, 1400]; no span in [900, 1000] but the op's 30
    assert got["device.idle_unattributed_share"] == pytest.approx(
        100 * 70 / 1270)
    # the train ops' own time (the loop op holds them), 60 ns over 3 rounds
    assert got["device.train_ms_per_round"] == pytest.approx(20 / 1e6)


def test_readers_read_nothing_of_a_program_without_the_spans():
    old = {"spans": [s for s in HAND["spans"]
                     if s[0] in run.SPAN_NAMES],
           "device": {k: [e[:3] + [""] for e in v]
                      for k, v in HAND["device"].items()}}
    assert _read(old) == dict.fromkeys(NEW)


def test_readers_on_a_recorded_tpu_capture():
    """``program_trace.json``: 45 ms of a traced refl window on a TPU v5
    lite, around the start of a simulation (13 rounds, one finalize, one
    build); the readings are of the order the whole window gives."""
    with open(BENCH / "tests" / "program_trace.json") as fh:
        trace = json.load(fh)
    got = _read(trace, trace["rounds"])
    assert 0.5 < got["host.dispatch_ms_per_round"] < 2
    assert 1 < got["host.lifecycle_ms_per_sim"] < 100
    assert 0 < got["device.idle_unattributed_share"] < 10
    assert 0.01 < got["device.train_ms_per_round"] < 0.1


def test_scopes_of_a_recorded_tpu_capture():
    """``tpu_scopes.xplane.pb``: a TPU v5 lite capture of a small jitted
    scan whose body has a ``train`` and an ``apply`` named scope; the
    scope path is the ``tf_op`` stat of each op's metadata."""
    trace = programtrace._load(str(BENCH / "tests" / "tpu_scopes.xplane.pb"),
                               0.0)
    (ops,) = trace["device"].values()
    train = [e for e in ops if programtrace.in_scope(e[3], "train")]
    assert train and {e[0] for e in train} == {"fusion.8"}
    assert any(e[3] and not programtrace.in_scope(e[3], "train")
               for e in ops)
    got = _read(trace, rounds=3)
    assert got["device.train_ms_per_round"] == pytest.approx(
        sum(e[2] for e in train) / 3 / 1e6)
    assert got["host.lifecycle_ms_per_sim"] is None


def test_scope_paths():
    assert programtrace.in_scope(TRAIN, "train")
    assert not programtrace.in_scope("jit(prog)/trainer/x", "train")


def test_trained_row_counter_matches_the_round_log():
    from repro.telemetry import TelemetrySession, Tracer
    c = run.load_cell("mlp-speech-n1000.refl")
    c["traffic"]["sim"].update(rounds=40, eval_every=20)
    world = run.build_world(c["config"], c["traffic"], 2**31 + 5)
    tele = TelemetrySession(tracer=Tracer(enabled=True))
    sim, _ = run.simulate(world, tele)
    assert tele.registry.value("pipeline_trained_rows") == \
        sum(e["trained"] for e in sim.round_log) > 0
