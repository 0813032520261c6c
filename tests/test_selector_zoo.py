"""Selector-zoo plugin interface: ported-strategy bit-parity, the new
strategies' closed-form oracles, selector_key program-variant folding, and
the sweep/CLI surfaces.

The ported selectors (random/oort/priority/safa) moved from
``repro.core.selection`` onto the strategy table verbatim; the frozen
pre-refactor implementations embedded here are driven through identical
RNG streams and feedback sequences to pin that the move changed no
selection decision (RNG-stream bit-parity — the host half of the zoo's
"bit-identical to HEAD" gate; the substrate half is the batched-vs-serial
parity asserts below and in tests/test_sweep_parity.py).
"""
import copy
import math
import pickle

import numpy as np
import pytest

from repro.selection import (SELECTOR_TABLE, ContributionSelector,
                             FlipsSelector, LearnerView, OortSelector,
                             PrioritySelector, RandomSelector, SafaSelector,
                             Selector, SelectorSpec, UcbSelector,
                             build_selector, normalize_selector_params,
                             register_selector, selector_key)
from repro.selection.flips import kmeans_labels, label_histograms
from repro.sim.engine import SimConfig, Simulator
from repro.sim.pipeline import pipeline_key
from repro.sweeps import SweepSpec, assert_parity, run_batched, run_serial
from repro.sweeps.grid import axis_updates
from repro.sweeps.runner import compat_key

# ---------------------------------------------------------------------------
# Frozen pre-refactor implementations (verbatim selection logic at the time
# of the move to repro.selection; do NOT "fix" these — they are the oracle)
# ---------------------------------------------------------------------------


class _LegacyRandom:
    def select_ids(self, round_idx, ids, n_target, rng):
        if len(ids) <= n_target:
            return list(ids)
        return list(rng.choice(ids, size=n_target, replace=False))


class _LegacyPriority:
    def __init__(self, holdoff=5):
        self.holdoff = holdoff
        self._held_until = {}

    def select(self, round_idx, checked_in, n_target, rng):
        eligible = [v for v in checked_in
                    if self._held_until.get(v.learner_id, -1) < round_idx]
        if not eligible:
            eligible = list(checked_in)
        jitter = rng.random(len(eligible))
        order = sorted(range(len(eligible)),
                       key=lambda i: (eligible[i].availability_prob, jitter[i]))
        chosen = [eligible[i].learner_id for i in order[:n_target]]
        for lid in chosen:
            self._held_until[lid] = round_idx + self.holdoff
        return chosen


class _LegacyOort:
    def __init__(self, alpha=2.0, pacer_delta=10.0, pacer_window=20,
                 eps0=0.9, eps_min=0.2, eps_decay=0.98):
        self.alpha = alpha
        self.pacer_delta = pacer_delta
        self.pacer_window = pacer_window
        self.eps = eps0
        self.eps_min = eps_min
        self.eps_decay = eps_decay
        self.t_pref = None
        self._util_history = []
        self._stat_util = {}
        self._duration = {}

    def _utility(self, v):
        stat = self._stat_util.get(v.learner_id, v.last_stat_util)
        dur = self._duration.get(v.learner_id, v.est_duration) or 1.0
        if self.t_pref is not None and dur > self.t_pref:
            stat *= (self.t_pref / dur) ** self.alpha
        return stat

    def select(self, round_idx, checked_in, n_target, rng):
        if self.t_pref is None:
            durs = [v.est_duration for v in checked_in if v.est_duration > 0]
            self.t_pref = float(np.percentile(durs, 50)) if durs else 100.0
        explored = [v for v in checked_in if v.learner_id in self._stat_util]
        unexplored = [v for v in checked_in
                      if v.learner_id not in self._stat_util]
        n_explore = int(round(self.eps * n_target))
        n_exploit = n_target - n_explore
        exploit_order = sorted(explored, key=self._utility, reverse=True)
        chosen = [v.learner_id for v in exploit_order[:n_exploit]]
        unexplored.sort(key=lambda v: v.est_duration or 1e9)
        chosen += [v.learner_id for v in unexplored[:n_target - len(chosen)]]
        if len(chosen) < n_target:
            rest = [v.learner_id for v in exploit_order[n_exploit:]
                    if v.learner_id not in chosen]
            chosen += rest[:n_target - len(chosen)]
        self.eps = max(self.eps_min, self.eps * self.eps_decay)
        window_util = sum(self._utility(v) for v in checked_in
                          if v.learner_id in chosen)
        self._util_history.append(window_util)
        h = self._util_history
        if len(h) >= 2 * self.pacer_window:
            recent = sum(h[-self.pacer_window:])
            prev = sum(h[-2 * self.pacer_window:-self.pacer_window])
            if recent <= prev:
                self.t_pref += self.pacer_delta
                self._util_history = h[-self.pacer_window:]
        return chosen[:n_target]

    def update_feedback(self, learner_id, *, stat_util=None, duration=None,
                        round_idx=None):
        if stat_util is not None:
            self._stat_util[learner_id] = stat_util
        if duration is not None:
            self._duration[learner_id] = duration


def _census(rng, n, probs, durs):
    """One round's check-in as the engine hands it over: a random subset of
    the population in ascending id order, as arrays and as the equivalent
    views (probabilities as floats, durations as numpy scalars)."""
    ids = np.flatnonzero(rng.random(n) < 0.4)
    views = [LearnerView(lid, availability_prob=float(probs[lid]),
                         est_duration=durs[lid]) for lid in ids]
    return ids, probs[ids], durs[ids], views


def _held_dict(sel):
    return {lid: h for lid, h in enumerate(sel._held_until.tolist())
            if h >= 0}


def test_random_ported_bit_identical():
    legacy, new = _LegacyRandom(), RandomSelector()
    for seed in range(5):
        r1 = np.random.default_rng(seed)
        r2 = np.random.default_rng(seed)
        ids = list(range(30))
        for r in range(10):
            assert (legacy.select_ids(r, ids, 7, r1)
                    == new.select_ids(r, ids, 7, r2))


def test_safa_ported_bit_identical():
    new = SafaSelector()
    ids = [3, 5, 9, 12]
    assert new.select_ids(0, ids, 2, np.random.default_rng(0)) == ids


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n", [25, 1000])
def test_priority_ported_bit_identical(n, seed):
    """The oracle on views against ``select_arrays`` and the ``select``
    adapter, over the engine's census of a changing check-in: exact ties in
    the probabilities, and a round in which every learner is held off."""
    legacy, new, via_views = (_LegacyPriority(), PrioritySelector(),
                              PrioritySelector())
    setup = np.random.default_rng(100 + seed)
    # a third of the probabilities on a coarse grid: exact ties
    probs = setup.random(n)
    probs[: n // 3] = np.round(probs[: n // 3], 1)
    durs = 10 + 90 * setup.random(n)
    rngs = [np.random.default_rng(seed) for _ in range(3)]
    n_target = max(3, n // 40)
    prev, all_held = [], 0
    for r in range(30):
        ids, p, d, views = _census(setup, n, probs, durs)
        if r == 12:   # only last round's cohort checks in: all held off
            ids = np.asarray(sorted(prev), np.int64)
            p, d = probs[ids], durs[ids]
            views = [LearnerView(lid, availability_prob=float(probs[lid]),
                                 est_duration=durs[lid]) for lid in ids]
            all_held += all(legacy._held_until.get(lid, -1) >= r
                            for lid in ids)
        a = legacy.select(r, views, n_target, rngs[0])
        b = new.select_arrays(r, ids, p, d, n_target, rngs[1])
        c = via_views.select(r, views, n_target, rngs[2])
        assert a == b == c, r
        prev = a
    assert all_held == 1
    assert legacy._held_until == _held_dict(new) == _held_dict(via_views)
    assert (rngs[0].bit_generator.state == rngs[1].bit_generator.state
            == rngs[2].bit_generator.state)


@pytest.mark.parametrize("inputs", ["engine", "float"])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n", [25, 1000])
def test_oort_ported_bit_identical(n, seed, inputs):
    """The oracle on views against ``select_arrays`` and the ``select``
    adapter through exploration, exploitation, the slow-learner penalty and
    the pacer, with exact ties in utilities and durations.  The pacer's
    Python sum depends on the inputs' types: ``engine`` gives durations as
    numpy scalars (as the engine's arrays hold them) and utilities as
    floats; ``float`` gives every number as an exact float, in the views and
    in the feedback, which only views can carry, so ``select_arrays`` sits
    that case out."""
    as_float = inputs == "float"
    knobs = dict(pacer_window=8, alpha=(2.0, 1.5, 3.0)[seed])
    legacy, new, via_views = (_LegacyOort(**knobs), OortSelector(**knobs),
                              OortSelector(**knobs))
    sels = (legacy, via_views) if as_float else (legacy, new, via_views)
    setup = np.random.default_rng(200 + seed)
    probs = setup.random(n)
    durs = np.round(10 + 90 * setup.random(n))      # exact duration ties
    fb = np.random.default_rng(300 + seed)
    rngs = [np.random.default_rng(seed) for _ in range(3)]
    n_target = max(4, n // 60)
    for r in range(60):
        ids, p, d, views = _census(setup, n, probs, durs)
        if as_float:
            for v in views:
                v.est_duration = float(v.est_duration)
        a = legacy.select(r, views, n_target, rngs[0])
        c = via_views.select(r, views, n_target, rngs[2])
        assert a == c, r
        if not as_float:
            assert a == new.select_arrays(r, ids, p, d, n_target, rngs[1]), r
        # the round's window utility, to the bit and the type
        w = legacy._util_history[-1]
        for sel in sels[1:]:
            assert sel._util_history[-1] == w, r
            assert type(sel._util_history[-1]) is type(w), r
        t_pref0 = legacy.t_pref if r == 0 else t_pref0
        # identical post-round feedback (same utilities, same durations):
        # even ids' utilities on a coarse grid, so the exploit order meets
        # ties; odd ids' heavy-tailed, so slow learners are exploited too
        for lid in a:
            u = float(np.round(fb.random(), 1) if lid % 2 == 0
                      else fb.lognormal(0.0, 2.0))
            dur = np.float64(durs[lid] * (0.5 + fb.random()))
            if as_float:
                dur = float(dur)
            for sel in sels:
                sel.update_feedback(lid, stat_util=u, duration=dur,
                                    round_idx=r)
    assert legacy.t_pref > t_pref0                  # the pacer moved it
    for sel in sels[1:]:
        assert sel.eps == legacy.eps
        assert sel.t_pref == legacy.t_pref
        assert sel._util_history == legacy._util_history
        assert [type(u) for u in sel._util_history] == [
            type(u) for u in legacy._util_history]
        assert sel._stat_util == legacy._stat_util
        assert sel._duration == legacy._duration


def test_oort_view_types_reach_the_pacer():
    """Through ``select``, a view's own statistical utility and the numpy or
    float type of its numbers enter the window utility as the oracle's
    ``_utility`` gives them."""
    legacy, via_views = _LegacyOort(), OortSelector()
    views = [LearnerView(0, last_stat_util=0.1, est_duration=np.float64(90)),
             LearnerView(1, last_stat_util=np.float64(0.2),
                         est_duration=20.0),
             LearnerView(2, last_stat_util=0.3, est_duration=80.0),
             LearnerView(3, last_stat_util=0.7, est_duration=0.0),
             LearnerView(4, last_stat_util=1e-17, est_duration=10.0)]
    for sel in (legacy, via_views):
        assert sel.select(0, views, 5, np.random.default_rng(0)) == [
            4, 1, 2, 0, 3]
        sel.update_feedback(2, stat_util=np.float64(0.4), duration=0.0)
    for r in (1, 2):
        a = legacy.select(r, views, 5, np.random.default_rng(r))
        assert via_views.select(r, views, 5, np.random.default_rng(r)) == a
    assert via_views._util_history == legacy._util_history
    assert [type(u) for u in via_views._util_history] == [
        type(u) for u in legacy._util_history]


def test_view_selectors_restore_dict_checkpoints():
    # selectors pickled before the array form hold their per-learner state
    # as {learner_id: value} dicts; unpickling converts it
    pri = PrioritySelector.__new__(PrioritySelector)
    pri.__setstate__({"holdoff": 5, "_held_until": {3: 7, 0: 9}})
    assert _held_dict(pri) == {0: 9, 3: 7}
    oort = OortSelector.__new__(OortSelector)
    state = dict(vars(OortSelector()), eps=0.0, t_pref=50.0,
                 _util_history=[1.0],
                 _stat_util={4: 0.5, 1: 2.0}, _duration={4: 30.0})
    for k in ("_known", "_stat", "_stat_np", "_has_dur", "_dur", "_dur_np"):
        del state[k]
    oort.__setstate__(state)
    assert oort._stat_util == {1: 2.0, 4: 0.5}
    assert oort._duration == {4: 30.0}
    assert oort.t_pref == 50.0 and oort._util_history == [1.0]
    ids = np.arange(6)
    assert oort.select_arrays(0, ids, np.zeros(6), np.full(6, 40.0), 2,
                              np.random.default_rng(0)) == [1, 4]


class _ViewsOnly(Selector):
    """An out-of-tree view selector: implements ``select`` alone, so the
    engine reaches it through the base ``select_arrays`` (LearnerViews)."""

    def __init__(self, inner):
        self.inner = inner

    def select(self, round_idx, checked_in, n_target, rng):
        return self.inner.select(round_idx, checked_in, n_target, rng)

    def update_feedback(self, learner_id, **kw):
        self.inner.update_feedback(learner_id, **kw)


@pytest.mark.parametrize("selector", ["priority", "oort"])
def test_view_adapter_engine_parity(selector):
    """A Simulator whose view selector is reached through LearnerViews and
    the ``select`` adapter takes the array path's decisions, round for round,
    and leaves the RNG stream and the selector's state where the array path
    does."""
    cfg = SimConfig(n_learners=200, rounds=60, eval_every=30, n_target=6,
                    selector=selector, mapping="label_uniform",
                    dynamic_availability=True)

    class Logged(Simulator):
        def _begin_round(self, r):
            plan = super()._begin_round(r)
            self.log.append(None if plan is None else list(plan.chosen))
            return plan

    sims = []
    for via_views in (False, True):
        sim = Logged(cfg)
        sim.log = []
        if via_views:
            sim.selector = _ViewsOnly(sim.selector)
        sim.final = dict(sim.run().summary())
        sims.append(sim)
    a, b = sims
    assert len(a.log) == 60 and sum(c is not None for c in a.log) > 30
    assert a.log == b.log
    assert a.rng.bit_generator.state == b.rng.bit_generator.state
    assert a.final == b.final
    assert pickle.dumps(a.selector) == pickle.dumps(b.selector.inner)


# ---------------------------------------------------------------------------
# New strategies: closed-form oracles
# ---------------------------------------------------------------------------


def test_flips_quotas_oracle():
    f = FlipsSelector(np.zeros(1))
    # even split
    assert f.quotas([10, 10, 10, 10], 8) == [2, 2, 2, 2]
    # remainder to the largest clusters first, cluster id breaks ties
    assert f.quotas([5, 3, 2], 7) == [3, 2, 2]
    assert f.quotas([3, 5, 2], 7) == [2, 3, 2]
    assert f.quotas([4, 4, 2], 7) == [3, 2, 2]
    # overflow past a cluster's population is redistributed
    assert f.quotas([1, 9], 6) == [1, 5]
    assert f.quotas([0, 4, 4], 6) == [0, 3, 3]
    # cannot exceed the total population
    assert f.quotas([1, 1], 6) == [1, 1]
    rng = np.random.default_rng(0)
    for _ in range(50):
        sizes = list(rng.integers(0, 8, size=int(rng.integers(1, 6))))
        n_t = int(rng.integers(1, 12))
        q = f.quotas(sizes, n_t)
        assert all(0 <= qc <= s for qc, s in zip(q, sizes))
        assert sum(q) == min(n_t, sum(sizes))


def test_flips_cluster_balanced_selection():
    cluster_of = np.array([0, 0, 0, 0, 1, 1, 1, 1, 2, 2])
    f = FlipsSelector(cluster_of)
    chosen = f.select_ids(0, list(range(10)), 6, np.random.default_rng(0))
    counts = np.bincount(cluster_of[chosen], minlength=3)
    assert list(counts) == [2, 2, 2]
    assert len(set(chosen)) == 6


def test_flips_kmeans_deterministic():
    rng = np.random.default_rng(3)
    hists = rng.random((40, 10))
    hists /= hists.sum(1, keepdims=True)
    a = kmeans_labels(hists, 4, seed=17)
    b = kmeans_labels(hists, 4, seed=17)
    assert (a == b).all()
    assert a.shape == (40,) and set(a) <= set(range(4))


def test_flips_label_histograms_from_shards():
    class Data:
        y_train = np.array([0, 0, 1, 1, 2, 2])
        n_classes = 3
        shards = [np.array([0, 1, 2]), np.array([4, 5])]
    h = label_histograms(Data())
    assert h.shape == (2, 3)
    np.testing.assert_allclose(h[0], [2 / 3, 1 / 3, 0])
    np.testing.assert_allclose(h[1], [0, 0, 1])


def test_ucb_score_formula_and_ordering():
    sel = UcbSelector(c=1.5)
    for lid, (s, n) in {0: (3.0, 3), 1: (1.0, 1), 2: (4.0, 2)}.items():
        sel._sum[lid], sel._n[lid] = s, n
    sel.rounds = 10
    means = {0: 1.0, 1: 1.0, 2: 2.0}
    for lid in means:
        expect = (means[lid] / 2.0
                  + 1.5 * math.sqrt(2 * math.log(10) / sel._n[lid]))
        assert sel.score(lid) == pytest.approx(expect)
    # unexplored arms take strict priority over any explored score
    chosen = sel.select_ids(10, [0, 1, 2, 7, 8], 2, np.random.default_rng(0))
    assert set(chosen) == {7, 8}
    # with no unexplored arms left, picks descend by UCB score (the
    # under-pulled arm 1 wins on its exploration bonus)
    chosen = sel.select_ids(11, [0, 1, 2], 2, np.random.default_rng(0))
    scores = sel._scores()           # rounds already advanced by the call
    assert chosen == sorted([0, 1, 2], key=lambda a: -scores[a])[:2]
    assert chosen[0] == 1


def test_contribution_decay_and_fairness_floor():
    sel = ContributionSelector(decay=0.5, fairness_frac=0.2)
    sel.update_feedback(3, stat_util=4.0)
    sel.update_feedback(3, stat_util=1.0)
    assert sel._score[3] == pytest.approx(0.5 * 4.0 + 1.0)
    # ceil(0.2 * 5) = 1 slot reserved for the longest-starved learner even
    # when its contribution score is the lowest on the board
    sel = ContributionSelector(decay=0.9, fairness_frac=0.2)
    ids = list(range(10))
    for lid in range(9):
        sel._score[lid] = 10.0 + lid
        sel._last_sel[lid] = 5
    sel._score[9] = 0.0              # never selected, worst score
    chosen = sel.select_ids(6, ids, 5, np.random.default_rng(0))
    assert 9 in chosen
    top = sorted(range(9), key=lambda k: -sel._score[k])[:4]
    assert set(chosen) - {9} == set(top)
    assert sel._last_sel[9] == 6


def test_zoo_selectors_pickle_and_deepcopy():
    # capture_state deep-copies the selector for crash-safe resume; every
    # zoo strategy must round-trip plain pickle too (checkpoint files)
    cfg = SimConfig(n_learners=20, rounds=2)
    for name in SELECTOR_TABLE:
        sel = build_selector(
            SimConfig(n_learners=20, rounds=2, selector=name),
            substrate=Simulator(cfg).substrate)
        sel2 = pickle.loads(pickle.dumps(sel))
        assert type(sel2) is type(sel)
        copy.deepcopy(sel)


# ---------------------------------------------------------------------------
# selector_key: per-selector program variants, selector-uniform batches
# ---------------------------------------------------------------------------


def test_selector_key_structure():
    assert selector_key(SimConfig(selector="random")) == \
        ("random", (), False, False)
    assert selector_key(SimConfig(selector="oort"))[2] is True
    assert selector_key(SimConfig(selector="safa"))[3] is True
    k = selector_key(SimConfig(selector="ucb",
                               selector_params={"c": 2.0}))
    assert k == ("ucb", (("c", 2.0),), True, False)


def test_selector_key_folds_into_pipeline_and_compat_key():
    base = SimConfig(rounds=10)
    for name in SELECTOR_TABLE:
        cfg = SimConfig(rounds=10, selector=name)
        assert selector_key(cfg) in pipeline_key(cfg)
        if name != "random":
            assert pipeline_key(cfg) != pipeline_key(base)
            assert compat_key(cfg) != compat_key(base)
    # knob values split program variants too
    a = SimConfig(rounds=10, selector="flips")
    b = SimConfig(rounds=10, selector="flips",
                  selector_params={"n_clusters": 2})
    assert compat_key(a) != compat_key(b)


def test_unknown_selector_and_knob_rejected():
    with pytest.raises(ValueError, match="unknown selector"):
        SimConfig(selector="nope")
    with pytest.raises(ValueError, match="unknown knob"):
        SimConfig(selector="random", selector_params={"k": 1})
    with pytest.raises(ValueError, match="unknown knob"):
        normalize_selector_params("ucb", {"c": 1.0, "zz": 2})
    with pytest.raises(ValueError, match="selector"):
        axis_updates("selector", "nope")
    assert axis_updates("selector", "flips") == {"selector": "flips"}


def test_register_selector_name_collision():
    spec = SELECTOR_TABLE["random"]
    register_selector(spec)            # idempotent re-registration is fine
    clash = SelectorSpec(name="random", factory=lambda p, c: RandomSelector())
    with pytest.raises(ValueError, match="already registered"):
        register_selector(clash)


# ---------------------------------------------------------------------------
# Substrate parity: every zoo strategy, batched vs serial vs chunked
# ---------------------------------------------------------------------------

_ZOO_BASE = dict(n_learners=30, rounds=4, eval_every=2, n_target=4,
                 mapping="label_uniform")


def test_zoo_batched_vs_serial_parity():
    spec = SweepSpec(axes={"selector": list(SELECTOR_TABLE)},
                     base=dict(_ZOO_BASE), seeds=(0,))
    cells = spec.expand()
    results, _ = run_batched(cells)
    serial, _ = run_serial(cells)
    assert_parity(results, serial)


def test_feedback_free_selectors_chunk_bit_identically():
    import dataclasses
    free = [n for n, s in SELECTOR_TABLE.items()
            if not s.needs_feedback and not s.select_all]
    assert {"random", "priority", "flips"} <= set(free)
    spec = SweepSpec(axes={"selector": free}, base=dict(_ZOO_BASE), seeds=(0,))
    cells = spec.expand()
    results, _ = run_batched(cells)
    chunked = [dataclasses.replace(c, config=dataclasses.replace(
        c.config, rounds_per_dispatch=2)) for c in cells]
    results_k, _ = run_batched(chunked)
    for a, b in zip(results, results_k):
        assert dict(a.summary) == dict(b.summary), a.cell.name


def test_feedback_selector_forces_k1():
    from repro.sim.pipeline import RoundPipeline
    for name, want_k in (("ucb", 1), ("flips", 2)):
        cfg = SimConfig(selector=name, rounds_per_dispatch=2, **_ZOO_BASE)
        sim = Simulator(cfg)
        pipe = RoundPipeline([sim])
        assert pipe.k_rounds == want_k
        assert pipe._fetch_l2s == (name == "ucb")


def test_selector_params_reach_the_policy():
    cfg = SimConfig(selector="priority", selector_params={"holdoff": 2},
                    **_ZOO_BASE)
    assert cfg.selector_params == (("holdoff", 2),)
    sim = Simulator(cfg)
    assert sim.selector.holdoff == 2
    cfg2 = SimConfig(selector="flips", selector_params={"n_clusters": 2},
                     **_ZOO_BASE)
    sim2 = Simulator(cfg2)
    assert len(set(sim2.selector.cluster_of.tolist())) <= 2


def test_list_selectors_cli(capsys):
    from repro.sweeps.__main__ import main
    main(["--list-selectors", "--list-aggregators"])
    out = capsys.readouterr().out
    for name in SELECTOR_TABLE:
        assert name in out
    assert "trimmed_mean" in out
