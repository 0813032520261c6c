"""RELAY's IPS (paper Alg. 1): least-available-first priority selection.

Ported from the pre-zoo ``repro.core.selection`` onto arrays: the same
eligibility, the same single jitter draw (`rng.random(len(eligible))`, part
of the RNG-stream parity contract) and the same (probability, jitter)
order, so the decisions are bit-identical to the list-of-views original.
"""
from __future__ import annotations

import numpy as np

from repro.selection.base import (Knob, Selector, SelectorSpec, class_factory,
                                  grown, views_to_arrays)
from repro.selection.registry import register_selector


class PrioritySelector(Selector):
    """RELAY IPS (Alg. 1): sort availability probabilities ascending, shuffle
    ties, take the top n_target. Participants then hold off from checking in
    for ``holdoff`` rounds (Bonawitz et al., 2019 pacing)."""
    name = "priority"

    def __init__(self, holdoff: int = 5):
        self.holdoff = holdoff
        # round until which each learner id is held off; -1 = never chosen
        self._held_until = np.full(0, -1, np.int64)

    def __setstate__(self, state):
        # checkpoints written before the array form hold a {lid: round} dict
        held = state["_held_until"]
        if isinstance(held, dict):
            arr = np.full(max(held, default=-1) + 1, -1, np.int64)
            arr[list(held)] = list(held.values())
            state["_held_until"] = arr
        self.__dict__.update(state)

    def select(self, round_idx, checked_in, n_target, rng):
        return self.select_arrays(round_idx, *views_to_arrays(checked_in),
                                  n_target, rng)

    def select_arrays(self, round_idx, ids, probs, durs, n_target, rng):
        self._held_until = grown(self._held_until,
                                 int(ids.max(initial=-1)) + 1, -1)
        eligible = self._held_until[ids] < round_idx
        if not eligible.any():
            eligible[:] = True
        ids, probs = ids[eligible], probs[eligible]
        # ascending availability; random shuffle breaks ties (Alg. 1).
        # lexsort is stable, as sorted() on the (prob, jitter) key was
        jitter = rng.random(len(ids))
        chosen = ids[np.lexsort((jitter, probs))[:n_target]]
        self._held_until[chosen] = round_idx + self.holdoff
        return chosen.tolist()


register_selector(SelectorSpec(
    name="priority",
    factory=class_factory(PrioritySelector),
    cls=PrioritySelector,
    doc="RELAY IPS: least-available-first with tie shuffling + hold-off",
    knobs=(Knob("holdoff", 5, "rounds a participant holds off after "
                "selection"),),
))
