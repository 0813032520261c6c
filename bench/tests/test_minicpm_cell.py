"""CPU tests of the ``lm-minicpm2b-l4.silo8`` cell at toy widths: the
program's muP, tied-head ``transformer`` learner against the ``minicpm``
reference (``models/minicpm.py``), the cell run through ``run.run_cell``
with the round program streaming its participants, and two planted faults
that the check must catch. ``bench_cells.shrink`` keeps only the knobs of
the older cells, so this file has its own."""
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import kinds  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from counts import work  # noqa: E402

CELL = "lm-minicpm2b-l4.silo8"
TOY = dict(hidden_size=64, num_attention_heads=4, intermediate_size=128,
           num_hidden_layers=4, vocab_size=256)
TOY_KNOBS = dict(n_layers=4, d_model=64, n_heads=4, d_ff=128, vocab=256)
SEQ = 16


def shrink(c: dict) -> dict:
    """The cell at toy widths: the model, its knobs and the data cut to
    ``TOY``; muP, the tied head, the silos and the policy as they are."""
    c["config"]["model"].update(TOY)
    c["config"]["data"].update(vocab=TOY["vocab_size"], seq_len=SEQ,
                               n_test=16)
    knobs = dict(c["config"]["sim"]["model_params"])
    knobs.update(TOY_KNOBS)
    c["config"]["sim"]["model_params"] = sorted(knobs.items())
    c["traffic"]["sim"].update(rounds=2, eval_every=1)
    return c


def small() -> dict:
    return shrink(run.load_cell(CELL))


@pytest.fixture
def streamed(monkeypatch):
    """The round program forced to stream, its compiled programs emptied
    around the test."""
    from repro.sim import engine, pipeline

    def clear():
        pipeline._chunk_program.cache_clear()
        pipeline._eval_program.cache_clear()
        engine._cohort_step_fn.cache_clear()

    monkeypatch.setattr(pipeline, "stream_cohort",
                        lambda d, rows, limit: True)
    clear()
    yield pipeline
    clear()


def _quiet(c: dict, seed: int) -> dict:
    return run.run_cell(c, seed, 0.05, False, require_tpu=False,
                        log=lambda *a, **k: None)


def test_learner_matches_reference_loss_and_gradients():
    """On the reference's seeded weights, at ``highest`` precision, the
    program's learner gives each sequence's loss and every leaf of the
    gradient of the mean loss within 1e-5 relative."""
    import jax
    from repro.learners import DataMeta, build_model
    c = small()
    m = c["config"]["model"]
    fns = build_model("transformer", tuple(c["config"]["sim"]["model_params"]),
                      DataMeta(kind="tokens", vocab=1024, seq_len=SEQ))
    model = reference.Model(m)
    p = model.init(2**31 + 41)
    prog = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    assert "head" not in p
    assert jax.tree.structure(p) == jax.tree.structure(prog)
    assert [a.shape for a in jax.tree.leaves(p)] == \
        [a.shape for a in jax.tree.leaves(prog)]
    tok = np.random.default_rng(11).integers(0, 256, size=(6, SEQ + 1)) \
        .astype(np.int32)
    x, y = tok[:, :-1], tok[:, 1:]
    with jax.default_matmul_precision("highest"):
        (_, prog_per), prog_g = jax.jit(jax.value_and_grad(
            fns.loss, has_aux=True))(p, x, y)
    ref_per = model._loss(p, x, y)[1]
    ref_g = jax.jit(jax.grad(model.mean_loss))(p, x, y)
    np.testing.assert_allclose(np.asarray(prog_per), np.asarray(ref_per),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(prog_g), jax.tree.leaves(ref_g)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b)


def test_mup_terms_are_in_the_reference():
    """Each muP term moves the reference's losses: none is left out."""
    c = small()
    m = c["config"]["model"]
    p = reference.Model(m).init(2**31 + 43)
    tok = np.random.default_rng(5).integers(0, 256, size=(2, SEQ + 1)) \
        .astype(np.int32)
    x, y = tok[:, :-1], tok[:, 1:]
    base = np.asarray(reference.Model(m)._loss(p, x, y)[1])
    for key, value in (("scale_emb", 1), ("scale_depth", 40 ** 0.5),
                       ("dim_model_base", m["hidden_size"])):
        other = np.asarray(reference.Model(dict(m, **{key: value}))._loss(
            p, x, y)[1])
        assert np.max(np.abs(other - base) / base) > 1e-3, key


def test_sound_streamed_cell_is_correct(streamed):
    res = _quiet(small(), 2**31 + 47)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}


def _fresh_row_left_out(pipeline, monkeypatch, c):
    """The streamed loop stops one row short: the last silo's fresh row is
    neither trained nor summed, while the mean still divides by n_F."""
    orig = pipeline._stream_rows

    def rows(*a, single):
        return orig(*a[:-1], a[-1] - 1, single=single)

    monkeypatch.setattr(pipeline, "_stream_rows", rows)
    return c


def _logit_divisor_dropped(pipeline, monkeypatch, c):
    knobs = dict(c["config"]["sim"]["model_params"], dim_model_base=0)
    c["config"]["sim"]["model_params"] = sorted(knobs.items())
    return c


@pytest.mark.parametrize("fault", [_fresh_row_left_out,
                                   _logit_divisor_dropped])
def test_planted_fault_is_not_correct(fault, streamed, monkeypatch):
    c = fault(streamed, monkeypatch, small())
    res = _quiet(c, 2**31 + 53)
    assert not res["correct"], res["checks"]


def test_lower_precision_control_fails():
    import jax.numpy as jnp
    c = small()
    world = run.build_world(c["config"], c["traffic"], 2**31 + 59)
    sim, acct = run.simulate(world)
    losses, log = run.eval_losses(acct), sim.round_log
    ref_p, ref_l = run.replay(world, log, losses)
    ctl_p, ctl_l = run.replay(world, log, losses, dtype=jnp.bfloat16)
    got = run.compare(world, run.leaves_flat(ctl_p), ctl_l, ref_p, ref_l)
    assert any(got[k] > lim for k, lim in c["limits"].items()), got


def test_counts_of_the_configuration():
    """The cell's configuration as it is run: 279,597,312 parameters and
    436.68 GFLOP a trained sequence of 256 tokens, from the kind's file."""
    c = run.load_cell(CELL)
    m = c["config"]["model"]
    assert kinds.model(m["kind"]).params(m) == work.params(m) == 279_597_312
    flop = work.train_flop_per_sample(m, c["config"]["data"]["seq_len"])
    assert flop == pytest.approx(436.68e9, abs=0.005e9)


def test_toy_tree_is_the_learners_width():
    """The toy reference's parameter count is the learner's flat D."""
    import jax
    from repro.learners import DataMeta, build_model
    c = small()
    fns = build_model("transformer", tuple(c["config"]["sim"]["model_params"]),
                      DataMeta(kind="tokens", vocab=1024, seq_len=SEQ))
    tree = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree)) \
        == kinds.model("minicpm").params(c["config"]["model"])


# a streamed round: the row loop holds training, the fresh sum and a cache
# write; after it the mean, the kernel and the server step
LOOP = "jit(prog)/while/body/while/body"
STREAMED = {"spans": [["dispatch", 0, 1000]], "device": {"/device:TPU:0": [
    ["while.1", 0, 900, "jit(prog)/while"],
    ["fusion.1", 10, 400, LOOP + "/train/dot_general:"],
    ["fusion.2", 420, 30, LOOP + "/fresh_sum/add:"],
    ["fusion.3", 460, 20, LOOP + "/cache/dynamic_update_slice:"],
    ["fusion.4", 500, 10, "jit(prog)/while/body/fresh_sum/div:"],
    ["staleness_apply", 520, 40, "jit(prog)/while/body/aggregate/pallas:"],
    ["fusion.5", 570, 20, "jit(prog)/while/body/apply/scatter:"],
    ["fusion.6", 950, 40, "jit(f)/eval/dot_general:"]]}}


def _lm_read(name, trace, **kw):
    import argparse
    ctx = argparse.Namespace(program_trace=trace, rounds=2, agg_rows=[8, 8],
                             d=1000, peak={"hbm_bytes_per_s": 1e12,
                                           "bf16_flops": 1e15}, **kw)
    return run._module(BENCH / "metrics" / f"{name}.py").read(ctx)


def test_lm_readers_by_hand():
    # train: 400 ns of own time over 2 rounds
    assert _lm_read("device.train_ms_per_round.lm", STREAMED) == \
        pytest.approx(200 / 1e6)
    # least time: 2 rounds of 4 (8 D + 2 D) bytes at 1e12 B/s = 80 ns,
    # over fresh_sum 40 + cache 20 + aggregate 40 + apply 20 = 120 ns
    assert _lm_read("agg.roofline.lm", STREAMED) == \
        pytest.approx(100 * 80 / 120)


def test_lm_readers_read_nothing_without_the_scopes():
    bare = {"spans": STREAMED["spans"], "device": {"/device:TPU:0": [
        [n, s, d, ""] for n, s, d, _ in STREAMED["device"]["/device:TPU:0"]]}}
    assert _lm_read("device.train_ms_per_round.lm", bare) is None
    assert _lm_read("agg.roofline.lm", bare) is None
