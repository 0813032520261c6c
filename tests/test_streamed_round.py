"""The streamed round program: a round's rows trained one at a time into a
fresh sum (``pipeline._stream_rows``), against the vmapped round body on
the very same round inputs, and the choice between the two
(``pipeline.stream_cohort``)."""
import functools

import jax
import numpy as np
import pytest

from repro.sim import SimConfig, Simulator
from repro.sim import learner as ln
from repro.sim import pipeline
from repro.sim.pipeline import RoundPipeline

# fresh-only: every learner taken and every update fresh; stragglers:
# REFL's over-commit with SAA, so rows enter the cache and land later
CASES = {
    "fresh_only": dict(n_learners=8, n_target=8, overcommit=1.0,
                       dynamic_availability=False, saa=True),
    "stragglers": dict(n_learners=30, dynamic_availability=False, saa=True),
}


def _cfg(case, kernel, **kw):
    return SimConfig(rounds=6, eval_every=3, selector="priority",
                     use_agg_kernel=kernel, seed=5, **dict(CASES[case], **kw))


def _force(monkeypatch, streamed):
    monkeypatch.setattr(pipeline, "stream_cohort",
                        lambda d, rows, limit: streamed)
    pipeline._chunk_program.cache_clear()


@functools.lru_cache(maxsize=4)
def _captured(case, kernel):
    """Every round's inputs of a forced-streamed run, taken before the
    dispatch donates them, with the pipeline that made them."""
    orig = pipeline.stream_cohort
    pipeline.stream_cohort = lambda d, rows, limit: True
    pipeline._chunk_program.cache_clear()
    try:
        pipe = RoundPipeline([Simulator(_cfg(case, kernel))])
        prog, rounds = pipe._prog, []

        def spy(params, cache, opt, x, y, ints, floats, shapes):
            rounds.append(tuple(np.asarray(a) for a in
                                (params, cache, ints, floats)) + (shapes,))
            return prog(params, cache, opt, x, y, ints, floats, shapes)

        pipe._prog = spy
        pipe.run()
    finally:
        pipeline.stream_cohort = orig
        pipeline._chunk_program.cache_clear()
    return pipe, rounds


def _body(pipe):
    cfg = pipe.cfg0
    padded = pipe.d_pad != pipe.d
    train_unit = functools.partial(
        ln.local_train_flat, spec=pipe.spec, lr=cfg.local_lr,
        prox_mu=cfg.prox_mu, loss=pipe._model_fns.loss,
        out_dim=pipe.d_pad if padded else None)
    stream_unit = functools.partial(
        ln.local_train_leaves, spec=pipe.spec, lr=cfg.local_lr,
        prox_mu=cfg.prox_mu, loss=pipe._model_fns.loss)
    body = functools.partial(
        pipeline._round_body, train_unit=train_unit, steps=cfg.local_steps,
        batch=cfg.local_batch, yogi=False, use_kernel=cfg.use_agg_kernel,
        kernel_rule=cfg.scaling_rule, single=True,
        norm_d=pipe.d if padded else None, stream_unit=stream_unit)
    return jax.jit(lambda p, c, x, y, i, f, shapes: body(
        p, c, None, x, y, i, f, shapes), static_argnums=(6,))


def _kinds(rnd):
    """What a captured round holds: stale rows landing, straggler rows
    scattered to the cache, pad rows in its bucket."""
    _p, cache, ints, _f, shapes = rnd
    r_b, tb, _g_b, _nf_b, ns_b = shapes[:5]
    i = ints[0]
    n_rows = int(i[-1])
    scat = i[r_b * tb + 2 * r_b:r_b * tb + 3 * r_b][:n_rows]
    return dict(landing=ns_b > 0,
                scattered=bool(np.any(scat != cache.shape[0] - 1)),
                pad=r_b > n_rows, n_rows=n_rows)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("case", ["fresh_only", "stragglers", "pad_rows"])
def test_streamed_body_matches_vmapped(case, kernel):
    """On each captured round the streamed body gives the vmapped body's
    parameters, cache slots, losses and l2s within 1e-6 relative (the
    fresh rows are summed, then divided, instead of weighed one by one)."""
    pipe, rounds = _captured("fresh_only" if case == "fresh_only"
                             else "stragglers", kernel)
    kinds = [_kinds(r) for r in rounds]
    pick = {"fresh_only": lambda k: not (k["landing"] or k["scattered"]),
            "stragglers": lambda k: k["landing"] and k["scattered"],
            "pad_rows": lambda k: k["pad"]}[case]
    chosen = [(r, k) for r, k in zip(rounds, kinds) if pick(k)]
    assert chosen, kinds
    body = _body(pipe)
    x, y = pipe.x_tr, pipe.y_tr
    for (params, cache, ints, floats, shapes), k in chosen:
        assert shapes[-1] is True
        outs = {}
        for streamed in (True, False):
            outs[streamed] = body(params, cache, x, y, ints[0],
                                  floats[0], shapes[:-1] + (streamed,))
        (ps, cs, *_s), (pv, cv, *_v) = outs[True][:2], outs[False][:2]
        n = k["n_rows"]
        assert _rel(ps, pv) <= 1e-6
        # every slot but the trash row, which only the vmapped body writes
        assert _rel(cs[:-1], cv[:-1]) <= 1e-6
        for j in (3, 4):                     # losses, l2s of the real rows
            assert _rel(outs[True][j][:n], outs[False][j][:n]) <= 1e-6
        assert not np.any(np.asarray(outs[True][3][n:]))


@pytest.mark.parametrize("case", list(CASES))
def test_streamed_simulation_matches_vmapped(case, monkeypatch):
    """A whole simulation through ``Simulator.run`` agrees either way, and
    only the streamed one counts its trained rows as streamed."""
    runs = {}
    for streamed in (False, True):
        _force(monkeypatch, streamed)
        sim = Simulator(_cfg(case, kernel=False))
        pipe = RoundPipeline([sim])
        acct = pipe.run()[0]
        runs[streamed] = (np.asarray(sim.flat_params), acct,
                          pipe.stats.as_dict())
    (pv, av, sv), (ps, as_, ss) = runs[False], runs[True]
    assert _rel(ps, pv) <= 1e-6
    lv = [r.loss for r in av.records if r.loss == r.loss]
    ls = [r.loss for r in as_.records if r.loss == r.loss]
    np.testing.assert_allclose(ls, lv, rtol=1e-6)
    assert sv["streamed_rows"] == 0
    assert ss["streamed_rows"] == ss["trained_rows"] > 0


def test_choice_keeps_vmap_for_the_mlp_cells():
    """The mlp cells' rows (D = 12,835) stay vmapped on a 16 GB chip and on
    a backend that reports no memory; MiniCPM-2B's 4-layer rows stream."""
    gb16 = 16 * 1024 ** 3
    for rows in (1, 16, 128, 1024):
        assert not pipeline.stream_cohort(12_835, rows, gb16)
    assert not pipeline.stream_cohort(12_835, 16, None)
    assert not pipeline.stream_cohort(279_597_312, 8, None)
    assert pipeline.stream_cohort(279_597_312, 8, gb16)


@pytest.mark.parametrize("kw,named", [
    (dict(guard=True), "guarded aggregation"),
    (dict(aggregator="coord_median"), "a robust aggregator"),
    (dict(telemetry=2), "the telemetry lane"),
], ids=["guard", "robust", "lane"])
def test_streaming_refuses_what_needs_the_operand(kw, named, monkeypatch):
    _force(monkeypatch, True)
    with pytest.raises(NotImplementedError, match=named):
        Simulator(_cfg("fresh_only", kernel=False, **kw)).run()


def test_finished_pipeline_is_freed_at_once(monkeypatch):
    """A finished simulation's pipeline, with its parameter and cache
    buffers, is freed when the caller drops it, not at the next garbage
    collection (at MiniCPM-2B's widths those buffers hold 5.6 GB)."""
    import gc
    import weakref
    made = []
    orig = RoundPipeline.__init__

    def init(self, *a, **k):
        orig(self, *a, **k)
        made.append(weakref.ref(self))

    monkeypatch.setattr(RoundPipeline, "__init__", init)
    gc.disable()
    try:
        Simulator(_cfg("fresh_only", kernel=False)).run()
        assert made and made[0]() is None
    finally:
        gc.enable()
