"""Oort utility-guided selection (Lai et al., OSDI'21).

Ported from the pre-zoo ``repro.core.selection`` onto arrays, decision for
decision.  Oort is the archetypal ``needs_feedback`` selector: its
statistical utility comes from the per-row device loss stats, so the fused
pipeline fetches the round's l2s vector and caps ``rounds_per_dispatch`` at
1 (see ``repro.selection.base``).
"""
from __future__ import annotations

from typing import List

import numpy as np

from repro.selection.base import (Knob, Selector, SelectorSpec, class_factory,
                                  grown, views_to_arrays)
from repro.selection.registry import register_selector


class OortSelector(Selector):
    """Oort (Lai et al., OSDI'21), faithful to its core mechanics:

    util(i) = stat_util(i) * (T_pref / t_i)^alpha  if t_i > T_pref else stat_util(i)

    with epsilon-greedy exploration of never-selected learners (epsilon decays
    0.9 -> 0.2) and a pacer that raises T_pref by ``pacer_delta`` when the
    aggregate utility of selected participants stalls.

    Per-learner feedback lives in arrays indexed by learner id (``_known`` /
    ``_stat`` for the statistical utility, ``_has_dur`` / ``_dur`` for the
    measured duration); ``_stat_util`` and ``_duration`` read them back as
    ``{learner_id: value}`` dicts.  ``_stat_np`` / ``_dur_np`` record which
    values arrived as numpy scalars rather than exact floats: the pacer's
    Python sum depends on it (see ``_select``).
    """
    name = "oort"

    def __init__(self, alpha: float = 2.0, pacer_delta: float = 10.0,
                 pacer_window: int = 20, eps0: float = 0.9, eps_min: float = 0.2,
                 eps_decay: float = 0.98):
        self.alpha = alpha
        self.pacer_delta = pacer_delta
        self.pacer_window = pacer_window
        self.eps = eps0
        self.eps_min = eps_min
        self.eps_decay = eps_decay
        self.t_pref = None            # preferred round duration, set lazily
        self._util_history: List[float] = []
        self._clear_feedback()

    def _clear_feedback(self):
        self._known = np.zeros(0, bool)
        self._stat = np.zeros(0)
        self._stat_np = np.zeros(0, bool)
        self._has_dur = np.zeros(0, bool)
        self._dur = np.zeros(0)
        self._dur_np = np.zeros(0, bool)

    @property
    def _stat_util(self):
        return {int(i): float(self._stat[i])
                for i in np.flatnonzero(self._known)}

    @property
    def _duration(self):
        return {int(i): float(self._dur[i])
                for i in np.flatnonzero(self._has_dur)}

    def __setstate__(self, state):
        # checkpoints written before the array form hold the feedback as
        # {learner_id: value} dicts
        old = {k: state.pop(k) for k in ("_stat_util", "_duration")
               if k in state}
        self.__dict__.update(state)
        if old:
            self._clear_feedback()
            for lid, v in old["_stat_util"].items():
                self.update_feedback(lid, stat_util=v)
            for lid, v in old["_duration"].items():
                self.update_feedback(lid, duration=v)

    def _grow(self, n: int):
        self._known = grown(self._known, n, False)
        self._stat = grown(self._stat, n, 0.0)
        self._stat_np = grown(self._stat_np, n, False)
        self._has_dur = grown(self._has_dur, n, False)
        self._dur = grown(self._dur, n, 0.0)
        self._dur_np = grown(self._dur_np, n, False)

    def select(self, round_idx, checked_in, n_target, rng):
        ids, _, durs = views_to_arrays(checked_in)
        stat0 = [v.last_stat_util for v in checked_in]
        return self._select(
            round_idx, ids, durs, n_target, rng, np.array(stat0, np.float64),
            np.array([_is_np(u) for u in stat0], bool),
            np.array([_is_np(v.est_duration) for v in checked_in], bool))

    def select_arrays(self, round_idx, ids, probs, durs, n_target, rng):
        # an array's durations are numpy scalars; an unexplored learner's
        # utility is the float 0.0 (a LearnerView's default)
        return self._select(round_idx, ids, durs, n_target, rng,
                            stat0=0.0, stat0_np=False, durs_np=True)

    def _select(self, round_idx, ids, durs, n_target, rng, stat0, stat0_np,
                durs_np):
        """Selection over the check-in (``ids``, ``durs``); ``stat0`` is an
        unexplored learner's statistical utility, and ``stat0_np`` /
        ``durs_np`` say which of ``stat0`` and ``durs`` are numpy scalars."""
        if self.t_pref is None:
            positive = durs[durs > 0]
            self.t_pref = (float(np.percentile(positive, 50)) if len(positive)
                           else 100.0)
        self._grow(int(ids.max(initial=-1)) + 1)
        explored = self._known[ids]
        n_explore = int(round(self.eps * n_target))
        n_exploit = n_target - n_explore

        # utility of every checked-in learner, and whether it is a numpy
        # scalar: a numpy factor makes the product one
        stat = np.where(explored, self._stat[ids], stat0)
        stat_np = np.where(explored, self._stat_np[ids], stat0_np)
        has_dur = self._has_dur[ids]
        dur = np.where(has_dur, self._dur[ids], durs)
        dur_np = np.where(has_dur, self._dur_np[ids], durs_np)
        zero = dur == 0
        dur[zero] = 1.0               # `dur or 1.0`: the float 1.0
        dur_np &= ~zero
        slow = dur > self.t_pref
        util_np = stat_np | (slow & dur_np)
        # the power is taken one learner at a time: numpy's vector power may
        # round differently from the scalar one the decisions were made with
        stat[slow] *= [r ** self.alpha
                       for r in (self.t_pref / dur[slow]).tolist()]

        # positions in the check-in; descending utility, ties in check-in
        # order (as sorted(reverse=True))
        exp_pos = np.flatnonzero(explored)
        exploit = exp_pos[np.argsort(-stat[exp_pos], kind="stable")]
        pos = exploit[:n_exploit]
        # exploration favors fast unexplored learners (Oort's speed heuristic)
        unexp_pos = np.flatnonzero(~explored)
        speed = durs[unexp_pos]
        speed[speed == 0] = 1e9
        unexp_pos = unexp_pos[np.argsort(speed, kind="stable")]
        pos = np.concatenate([pos, unexp_pos[:n_target - len(pos)]])
        picked = np.zeros(len(ids), bool)
        picked[pos] = True
        if len(pos) < n_target:  # backfill from remaining explored
            rest = exploit[n_exploit:]
            rest = rest[~picked[rest]][:n_target - len(pos)]
            pos = np.concatenate([pos, rest])
            picked[rest] = True
        self.eps = max(self.eps_min, self.eps * self.eps_decay)

        # pacer: if utility over the last window stalls, relax T_pref.  The
        # window's utility is a Python sum in check-in order over the chosen,
        # each utility of the type it would have had: Python compensates a
        # float sum only over exact floats, so the types are part of the result
        window_util = sum(np.float64(u) if t else u for u, t in
                          zip(stat[picked].tolist(), util_np[picked].tolist()))
        self._util_history.append(window_util)
        h = self._util_history
        if len(h) >= 2 * self.pacer_window:
            recent = sum(h[-self.pacer_window:])
            prev = sum(h[-2 * self.pacer_window:-self.pacer_window])
            if recent <= prev:
                self.t_pref += self.pacer_delta
                self._util_history = h[-self.pacer_window:]
        return ids[pos[:n_target]].tolist()

    def update_feedback(self, learner_id, *, stat_util=None, duration=None,
                        round_idx=None):
        self._grow(learner_id + 1)
        if stat_util is not None:
            self._known[learner_id] = True
            self._stat[learner_id] = stat_util
            self._stat_np[learner_id] = _is_np(stat_util)
        if duration is not None:
            self._has_dur[learner_id] = True
            self._dur[learner_id] = duration
            self._dur_np[learner_id] = _is_np(duration)


def _is_np(x) -> bool:
    return isinstance(x, np.generic)


register_selector(SelectorSpec(
    name="oort",
    factory=class_factory(OortSelector),
    cls=OortSelector,
    needs_feedback=True,
    doc="Oort: stat utility x completion-time penalty, eps-greedy + pacer",
    knobs=(Knob("alpha", 2.0, "completion-time penalty exponent"),
           Knob("pacer_delta", 10.0, "T_pref step when utility stalls"),
           Knob("pacer_window", 20, "pacer comparison window (rounds)"),
           Knob("eps0", 0.9, "initial exploration fraction"),
           Knob("eps_min", 0.2, "exploration floor"),
           Knob("eps_decay", 0.98, "per-round exploration decay")),
))
