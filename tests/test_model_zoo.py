"""Learner-model zoo contracts (the `MODEL_TABLE` strategy table):

  * ``model="mlp"`` IS the default — naming it changes nothing, bitwise,
    and the default cell stays bit-identical across the fused / chunked /
    participant-sharded / per-stage-flat substrates (PARITY_KEYS-level
    agreement with the legacy pytree engine, which never grew an
    accuracy-parity contract);
  * a tiny transformer LM (``benchmark="tokens"``) runs end-to-end through
    the same substrates with full bit-parity, fused vs flat vs chunked
    (vs sharded on multi-device legs);
  * the D-blocked kernel layout — ``use_agg_kernel=True`` keeps all round
    buffers at D rounded up to the kernel's column quantum — matches
    the unblocked per-stage reference bitwise, and the pad columns stay
    exactly zero for the life of the run;
  * the LM round program keeps the hot-path hygiene invariants: clean
    under ``jax.transfer_guard("disallow")`` and at most ONE cross-shard
    collective (the aggregation psum) at level-2 telemetry;
  * FLIPS on token workloads clusters on top-k unigram histograms
    (closed-form oracle) instead of crashing on missing class labels;
  * static-key plumbing: ``model_key`` rides ``pipeline_key``, knob typos
    and data-kind mismatches fail loudly at config/build time.
"""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro.learners import MODEL_TABLE, DataMeta, build_model, model_key
from repro.selection.flips import (FlipsSelector, kmeans_labels,
                                   learner_histograms, token_histograms)
from repro.sim import SimConfig, Simulator
from repro.sim.pipeline import RoundPipeline, pipeline_key
from repro.sweeps.runner import summaries_equal

N_DEV = len(jax.devices())

# the schedule/accounting fields the legacy pytree engine is pinned on
PARITY_KEYS = ("rounds", "sim_time", "resource_used", "resource_wasted",
               "unique_participants")

BASE = dict(n_learners=24, rounds=4, eval_every=2, n_target=4,
            mapping="label_uniform", saa=True, seed=0)

TINY_LM = (("d_ff", 8), ("d_model", 4), ("n_heads", 1), ("n_layers", 1))
LM_BASE = dict(benchmark="tokens", model="transformer", model_params=TINY_LM,
               n_learners=16, rounds=4, eval_every=2, n_target=4,
               local_steps=1, local_batch=4, saa=True,
               dynamic_availability=False, seed=0)


def _records_equal(a, b):
    assert len(a.records) == len(b.records)
    for ra, rb in zip(a.records, b.records):
        assert (ra.sim_time, ra.n_selected, ra.n_fresh, ra.n_stale,
                ra.resource_used, ra.resource_wasted) == \
               (rb.sim_time, rb.n_selected, rb.n_fresh, rb.n_stale,
                rb.resource_used, rb.resource_wasted)


# ---------------------------------------------------------------------------
# mlp: the registered default, bit-identical however the cell executes
# ---------------------------------------------------------------------------


def test_mlp_is_the_registered_default():
    cfg = SimConfig(**BASE)
    assert cfg.model == "mlp" and cfg.model_params == ()
    named = dataclasses.replace(cfg, model="mlp")
    assert pipeline_key(named) == pipeline_key(cfg)
    a, b = Simulator(cfg).run(), Simulator(named).run()
    assert summaries_equal(dict(a.summary()), dict(b.summary()))
    _records_equal(a, b)


SUBSTRATES = {
    "chunked": dict(rounds_per_dispatch=2),
    "sharded": dict(shard_participants=True),
    "flat": dict(fused_rounds=False),
    "legacy": dict(fast_path=False),
}


@pytest.mark.parametrize("name", sorted(SUBSTRATES))
def test_mlp_default_parity_across_substrates(name):
    cfg = SimConfig(model="mlp", **BASE)
    ref = dict(Simulator(cfg).run().summary())
    got = dict(Simulator(
        dataclasses.replace(cfg, **SUBSTRATES[name])).run().summary())
    if name == "legacy":
        # the legacy pytree engine pins schedule/accounting, not accuracy
        for k in PARITY_KEYS:
            assert got[k] == ref[k], (name, k)
    else:
        assert summaries_equal(ref, got), (name, ref, got)


# ---------------------------------------------------------------------------
# tiny transformer: full bit-parity through every fast-path substrate
# ---------------------------------------------------------------------------


LM_VARIANTS = {
    "flat": dict(fused_rounds=False),
    "chunked": dict(rounds_per_dispatch=2),
    "sharded": dict(shard_participants=True),
}


@pytest.mark.parametrize("name", sorted(LM_VARIANTS))
def test_transformer_substrate_parity(name):
    cfg = SimConfig(**LM_BASE)
    ref = Simulator(cfg).run()
    got = Simulator(dataclasses.replace(cfg, **LM_VARIANTS[name])).run()
    assert summaries_equal(dict(ref.summary()), dict(got.summary())), \
        (name, ref.summary(), got.summary())
    _records_equal(ref, got)


def test_legacy_engine_rejects_non_mlp_models():
    with pytest.raises(ValueError, match="flat fast path"):
        SimConfig(fast_path=False, **LM_BASE)


# ---------------------------------------------------------------------------
# D-blocked kernel layout vs the unblocked reference
# ---------------------------------------------------------------------------


def test_dblocked_kernel_matches_unblocked_reference():
    """use_agg_kernel keeps the fused pipeline's buffers at d_pad (a
    ``col_quantum`` multiple > D for the LM, 2048 at its width); the
    per-stage flat path pads transiently per kernel call.  Same math, same
    bits."""
    cfg = SimConfig(use_agg_kernel=True, **LM_BASE)
    blocked = Simulator(cfg).run()
    unblocked = Simulator(
        dataclasses.replace(cfg, fused_rounds=False)).run()
    assert summaries_equal(dict(blocked.summary()),
                           dict(unblocked.summary()))
    _records_equal(blocked, unblocked)


@pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device mesh")
def test_dblocked_kernel_sharded_matches_unblocked_reference():
    cfg = SimConfig(use_agg_kernel=True, shard_participants=2, **LM_BASE)
    sharded = Simulator(cfg).run()
    unblocked = Simulator(dataclasses.replace(
        cfg, shard_participants=False, fused_rounds=False)).run()
    assert summaries_equal(dict(sharded.summary()),
                           dict(unblocked.summary()))
    _records_equal(sharded, unblocked)


def test_padded_layout_pad_columns_stay_zero():
    from repro.kernels.staleness_agg.staleness_agg import col_quantum
    cfg = SimConfig(use_agg_kernel=True, **LM_BASE)
    pipe = RoundPipeline([Simulator(cfg)])
    assert pipe.d_pad > pipe.d and pipe.d_pad % col_quantum(pipe.d) == 0
    pipe.run()
    rows = np.asarray(jax.device_get(pipe.params)).reshape(-1, pipe.d_pad)
    assert (rows[:, pipe.d:] == 0).all(), \
        "pad columns leaked nonzero values into the persistent layout"
    # without the kernel there is nothing to block for: layout is exact-D
    flat_pipe = RoundPipeline(
        [Simulator(dataclasses.replace(cfg, use_agg_kernel=False))])
    assert flat_pipe.d_pad == flat_pipe.d


# ---------------------------------------------------------------------------
# LM hot-path hygiene: transfer-guard clean, one collective at telemetry 2
# ---------------------------------------------------------------------------


def test_lm_round_loop_transfer_clean_single_collective():
    from repro.telemetry import TelemetrySession
    cfg = SimConfig(telemetry=2, shard_participants=True, **LM_BASE)
    RoundPipeline([Simulator(cfg)]).run()            # warm compiles
    pipe = RoundPipeline([Simulator(cfg)], telemetry=TelemetrySession())
    orig, captured = pipe._prog, []

    def wrapper(*args):
        if not captured:
            captured.append(orig.lower(*args).compile().as_text())
        return orig(*args)

    pipe._prog = wrapper
    accts = pipe.run(transfer_guard=True)
    assert accts[0].summary()["rounds"] == LM_BASE["rounds"]
    txt = captured[0]
    n_all_reduce = len(re.findall(r"all-reduce(?:-start)?\(", txt))
    for op in ("all-gather", "all-to-all", "collective-permute",
               "reduce-scatter"):
        assert f"{op}(" not in txt, f"unexpected {op} in the LM round program"
    if N_DEV > 1:
        assert n_all_reduce == 1, \
            f"expected exactly 1 all-reduce (the psum), found {n_all_reduce}"
    else:
        assert n_all_reduce <= 1


# ---------------------------------------------------------------------------
# FLIPS on token workloads: top-k unigram histogram adapter + quotas
# ---------------------------------------------------------------------------


class _TokData:
    kind = "tokens"
    vocab = 16
    x_train = np.array([[0, 0, 1], [2, 2, 2], [3, 3, 0]], np.int32)
    shards = (np.array([0]), np.array([1, 2]))


class _ClsData:
    kind = "classifier"
    n_classes = 3
    y_train = np.array([0, 0, 1, 2])
    shards = (np.array([0, 1]), np.array([2, 3]))


def test_token_histograms_closed_form():
    # global counts: tok0 x3, tok2 x3, tok3 x2, tok1 x1 -> top-2 = [0, 2]
    # (count desc, token id asc on ties)
    h = token_histograms(_TokData(), top_k=2)
    np.testing.assert_allclose(h, [[1.0, 0.0],        # shard0: [0,0,1]
                                   [0.25, 0.75]])     # shard1: 2x3, 3x2, 0x1
    # the adapter dispatches on FederatedDataset.kind
    np.testing.assert_allclose(learner_histograms(_TokData(), top_k=2), h)
    cls = learner_histograms(_ClsData())
    np.testing.assert_allclose(cls, [[1.0, 0.0, 0.0], [0.0, 0.5, 0.5]])


def test_token_quota_closed_form():
    sel = FlipsSelector(np.array([0, 0, 0, 0, 0, 1, 1, 1, 2]))
    # equal split 2/2/2; cluster 2 holds 1 member -> spill 1 goes to the
    # largest cluster with headroom
    assert sel.quotas([5, 3, 1], 6) == [3, 2, 1]
    # end-to-end: a token clustering's cohort honors the quota split
    rng = np.random.default_rng(0)
    chosen = sel.select_ids(0, list(range(9)), 6, rng)
    counts = np.bincount(sel.cluster_of[chosen], minlength=3)
    assert list(counts) == [3, 2, 1]


def test_flips_selects_on_token_benchmark():
    cfg = SimConfig(**dict(LM_BASE, selector="flips",
                           selector_params={"n_clusters": 3,
                                            "token_top_k": 32}))
    acct = Simulator(cfg).run()
    assert acct.summary()["rounds"] == LM_BASE["rounds"]


# ---------------------------------------------------------------------------
# static keys + loud failures
# ---------------------------------------------------------------------------


def test_model_key_rides_pipeline_key():
    a = SimConfig(**LM_BASE)
    b = dataclasses.replace(a, model_params=TINY_LM[:-1] + (("n_layers", 2),))
    c = dataclasses.replace(a, model="rwkv6", model_params=TINY_LM)
    assert model_key(a) != model_key(b) != model_key(c)
    assert len({pipeline_key(a), pipeline_key(b), pipeline_key(c)}) == 3


def test_unknown_model_and_knob_typos_fail_at_config_time():
    with pytest.raises(ValueError, match="unknown model"):
        SimConfig(model="resnet", **BASE)
    with pytest.raises((KeyError, ValueError)):
        SimConfig(model="transformer", model_params=(("dmodel", 4),),
                  **{k: v for k, v in LM_BASE.items()
                     if k not in ("model", "model_params")})


def test_data_kind_mismatch_fails_at_build_time():
    cfg = SimConfig(model="transformer", model_params=TINY_LM, **BASE)
    with pytest.raises(ValueError, match="tokens"):
        Simulator(cfg)


def test_model_table_lists_the_zoo():
    assert {"mlp", "transformer", "moe", "rwkv6"} <= set(MODEL_TABLE)
    meta = DataMeta(kind="tokens", vocab=64, seq_len=8)
    fns = build_model("transformer", TINY_LM, meta)
    assert fns is build_model("transformer", TINY_LM, meta), \
        "build_model must return cached-identical function objects"


@pytest.mark.parametrize("name", ["transformer", "rwkv6"])
def test_use_kernels_on_tpu_backend_raises(name, monkeypatch):
    """A learner trains through its kernels, and neither kernel has a VJP
    (wkv6 has no TPU lowering either): on a TPU backend, use_kernels=1
    fails when the learner is built instead of running the Pallas
    interpreter there."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = MODEL_TABLE[name]
    knobs = dict({k.name: k.default for k in spec.knobs}, use_kernels=1)
    meta = DataMeta(kind="tokens", vocab=64, seq_len=8)
    with pytest.raises(NotImplementedError, match="use_kernels=1"):
        spec.build(knobs, meta)
    spec.build(dict(knobs, use_kernels=0), meta)    # the jnp path builds
