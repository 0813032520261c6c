"""CPU tests of the chip benchmark's yardstick: counts from shapes, the
trace reduction on a recorded trace, and the correctness check, which a
sound run passes and the lower-precision control and planted faults fail.

The runs here are small (the mlp cell cut to 40 rounds, the LM cell to toy
widths and 4 rounds) and skip the harness's look for a chip.
"""
import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import tracefile  # noqa: E402
from bench_cells import LM, MLP, run_quiet, small  # noqa: E402
from counts import work  # noqa: E402


# --- counts ----------------------------------------------------------------

@pytest.mark.parametrize("config,expected", [("mlp-speech-n1000", 12_835),
                                             ("lm-minicpm2b-l1", 65_772_288)])
def test_param_count_matches_learner(config, expected):
    import jax
    from repro.learners import DataMeta, build_model
    conf = run._json(BENCH / "configs" / f"{config}.json")
    m, d = conf["model"], conf["data"]
    assert work.params(m) == expected
    if m["kind"] == "mlp":
        meta = DataMeta(kind="classifier", feature_dim=d["dim"],
                        n_classes=d["n_classes"])
    else:
        meta = DataMeta(kind="tokens", vocab=d["vocab"],
                        seq_len=d["seq_len"])
    fns = build_model(conf["sim"]["model"],
                      tuple(tuple(kv) for kv in conf["sim"]["model_params"]),
                      meta)
    shapes = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == expected


def test_lm_flop_per_token_is_six_n_plus_attention():
    m = run._json(BENCH / "configs" / "lm-minicpm2b-l1.json")["model"]
    n_matmul = 65_772_288 - 1024 * 2304 - 3 * 2304     # no embedding, norms
    assert work.train_flop_per_sample(m, 64) == \
        64 * (6 * n_matmul + 12 * 64 * 2304)


# --- trace reduction -------------------------------------------------------

def test_union_busy_and_self_time_by_hand():
    evs = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5]]
    assert tracefile.union([(0, 10), (5, 15), (30, 35)]) == [[0, 15],
                                                             [30, 35]]
    assert tracefile.busy_ns(evs) == 20
    spans = [["schedule", 0, 100], ["pack", 10, 20], ["dispatch", 100, 50],
             ["schedule", 200, 10]]
    assert tracefile.self_ns(spans, ("schedule",)) == 100 - 20 + 10
    assert tracefile.self_ns(spans, ("schedule", "pack")) == 110


def test_recorded_trace():
    """A trace of the LM cell recorded on a TPU v5e, cut to its first
    events: the reduction's numbers, worked out by hand from the file."""
    trace = json.loads((BENCH / "tests" / "lm_trace.json").read_text())
    (plane,) = trace["device"].values()
    ivs = sorted((s, s + d) for _n, s, d in plane)
    busy, end = 0, None
    for s, e in ivs:
        if end is None or s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    assert tracefile.busy_s(trace) == busy / 1e9
    mod = run._module(BENCH / "metrics" / "saa_kernel_roofline.py")
    kern = tracefile.kernel_events(trace, mod.KERNEL_NAMES)
    assert kern and all(e[0] in trace["kernel_names"] for e in kern)
    assert len(kern) == trace["kernel_calls"]
    # the host was inside one ``eval`` span (waiting on the device) the
    # whole time: its self time is its duration, and it names every gap
    (span,) = trace["spans"]
    assert span[0] == "eval"
    assert tracefile.self_ns(trace["spans"], ("eval",)) == span[2]
    assert {g[0] for g in tracefile.idle_gaps(trace)} == {"eval"}


# --- the correctness check -------------------------------------------------

@pytest.fixture
def fresh_programs():
    """The program's compiled-program caches, emptied around a test that
    plants a fault in them."""
    from repro.sim import pipeline
    from repro.sim import engine

    def clear():
        pipeline._chunk_program.cache_clear()
        pipeline._eval_program.cache_clear()
        engine._cohort_step_fn.cache_clear()

    clear()
    yield
    clear()


def test_param_change_gap_is_each_leafs_own():
    """A leaf's gap is taken over that leaf's own change, so a fault in a
    small leaf (a bias) is not damped by the large ones; a leaf that the
    reference moves by rounding alone is left out."""
    import reference
    p0 = [np.zeros(4), np.zeros(100), np.zeros(100), np.zeros(3)]
    ref = [np.full(4, 0.01), np.ones(100), np.ones(100), np.full(3, 1e-9)]
    prog = [np.full(4, 0.02), np.ones(100), np.ones(100), np.full(3, 5e-9)]
    assert reference.leaf_norm_gap(p0, prog, ref) == pytest.approx(1.0)
    assert reference.leaf_norm_gap(p0, ref, ref) == 0.0


def test_sound_run_is_correct(fresh_programs):
    res = run_quiet(small(MLP))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}


@pytest.mark.parametrize("name", [MLP, LM])
def test_lower_precision_control_fails(name):
    import jax.numpy as jnp
    c = small(name)
    world = run.build_world(c["config"], c["traffic"], 2**31 + 5)
    sim, acct = run.simulate(world)
    losses, log = run.eval_losses(acct), sim.round_log
    ref_p, ref_l = run.replay(world, log, losses)
    ctl_p, ctl_l = run.replay(world, log, losses, dtype=jnp.bfloat16)
    got = run.compare(world, run.leaves_flat(ctl_p), ctl_l, ref_p, ref_l)
    assert any(got[k] > lim for k, lim in c["limits"].items()), got


def _unchanged(pipeline, monkeypatch):
    orig = pipeline._round_body

    def body(params, cache, opt_state, *a, **k):
        return (params,) + orig(params, cache, opt_state, *a, **k)[1:]

    monkeypatch.setattr(pipeline, "_round_body", body)


def _half_batch(pipeline, monkeypatch):
    from repro.sim import learner as ln
    orig = ln.local_train_flat

    def train(flat, xs, ys, **k):
        half = xs.shape[1] // 2
        return orig(flat, xs[:, :half], ys[:, :half], **k)

    monkeypatch.setattr(ln, "local_train_flat", train)


def _answer_altered(pipeline, monkeypatch):
    orig = pipeline._eval_program

    def ev(spec, evaluate):
        f = orig(spec, evaluate)

        def altered(*a):
            acc, loss = f(*a)
            return acc, loss * 1.05

        return altered

    monkeypatch.setattr(pipeline, "_eval_program", ev)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _answer_altered])
def test_planted_fault_is_not_correct(fault, fresh_programs, monkeypatch):
    from repro.sim import pipeline
    fault(pipeline, monkeypatch)
    res = run_quiet(small(MLP))
    assert not res["correct"], res["checks"]


def test_no_chip_no_result(capsys):
    assert run.main(["--workload", MLP, "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""
