"""Selector plugin base: the host-side policy interface + strategy spec.

A participant selector is a *host-side* sequential decision process (it
consumes the engine's ``np.random.Generator`` stream and mutates its own
plain-attribute state), unlike the robust aggregators, which are pure jnp
cell functions.  What the two strategy tables share is the static-key
contract: every selector registers a ``SelectorSpec`` whose static
properties (``needs_feedback``, ``select_all``) describe how the fused
round program must be built around it, and ``repro.selection.selector_key``
folds those into ``repro.sim.pipeline.pipeline_key`` — so each selector
compiles to its own fused-program variant and sweep batches stay uniform.

The spec properties and the program structure they pin:

``needs_feedback``
    The selector consumes the per-row statistical-utility feedback
    (``update_feedback(stat_util=...)`` from the device's loss stats).
    The fused pipeline then fetches the per-round ``(R,)`` l2s vector
    (device->host) and defers feedback to post-dispatch; since the *next*
    round's selection depends on it, prescheduling is capped at K=1
    (``rounds_per_dispatch`` forced to 1).  Feedback-free selectors keep
    the round loop's device->host traffic at zero and chunk freely.

``select_all``
    SAFA semantics: the cohort is every available learner and the round
    ends when ``safa_target_ratio`` of them report (capped by the
    deadline).  Cohort sizes then vary wildly round to round, so the
    pipeline keeps padded shape buckets instead of exact shapes.

Selector state must deep-copy/pickle cleanly (plain attributes only):
``Simulator.capture_state`` snapshots the selector for crash-safe resume.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core import registry as _registry


@dataclasses.dataclass
class LearnerView:
    """What the server may know about a checked-in learner.  The array form
    (``Selector.select_arrays``) carries the id, ``availability_prob`` and
    ``est_duration``; the built-in policies keep utilities and participation
    in their own state.  Of ``last_stat_util`` only Oort's ``select`` reads
    it, as an unexplored learner's utility (0.0 in the array form)."""
    learner_id: int
    availability_prob: float = 1.0   # learner-reported P(available in [mu, 2mu])
    last_stat_util: float = 0.0      # |B_i| * sqrt(mean loss^2) from last participation
    est_duration: float = 0.0        # estimated on-device round time (seconds)
    explored: bool = False           # has participated before


def views_to_arrays(checked_in: Sequence[LearnerView]):
    """(ids, probs, durs) of a list of views, in the views' order: the
    ``select_arrays`` form of a ``select`` call."""
    ids = np.array([v.learner_id for v in checked_in], np.int64)
    probs = np.array([v.availability_prob for v in checked_in], np.float64)
    durs = np.array([v.est_duration for v in checked_in], np.float64)
    return ids, probs, durs


def grown(a: np.ndarray, n: int, fill) -> np.ndarray:
    """``a`` extended with ``fill`` to at least ``n`` entries (doubling), for
    per-learner state indexed by learner id."""
    if n <= len(a):
        return a
    out = np.full(max(n, 2 * len(a)), fill, a.dtype)
    out[:len(a)] = a
    return out


class Selector:
    """Selection policy.  The engine hands a policy the round's check-in in
    one of two forms, by ``needs_views``:

    - ``select_arrays(round_idx, ids, probs, durs, n_target, rng)`` for
      ``needs_views = True``: three aligned arrays in ascending id order —
      learner ids, forecast availability probabilities and estimated round
      durations.  The default builds ``LearnerView`` objects and calls
      ``select``, so a policy written against views keeps working; the
      built-in view policies (priority, oort) implement it on the arrays.
    - ``select_ids(round_idx, ids, n_target, rng)`` for ``needs_views =
      False``: the ids alone; the engine then skips the forecaster window
      queries.  The queries are pure reads, so skipping them never changes
      forecaster state or the RNG stream.

    ``select(views)`` is the list-of-views entry for older callers; a policy
    that implements ``select_arrays`` answers it by unpacking the views with
    ``views_to_arrays``.
    """
    name = "base"
    needs_views = True

    def select(self, round_idx: int, checked_in: Sequence[LearnerView],
               n_target: int, rng: np.random.Generator) -> List[int]:
        raise NotImplementedError

    def select_arrays(self, round_idx: int, ids: np.ndarray,
                      probs: np.ndarray, durs: np.ndarray, n_target: int,
                      rng: np.random.Generator) -> List[int]:
        """For a policy that implements ``select`` alone: the check-in as
        ``LearnerView``s, built as the engine built them for ``select``."""
        views = [LearnerView(lid, availability_prob=float(p), est_duration=d)
                 for lid, p, d in zip(ids, probs, durs)]
        return self.select(round_idx, views, n_target, rng)

    def select_ids(self, round_idx: int, ids, n_target: int,
                   rng: np.random.Generator) -> List[int]:
        """View-free selection for ``needs_views = False`` selectors; ``ids``
        is the checked-in learner ids in ascending order."""
        raise NotImplementedError

    def update_feedback(self, learner_id: int, *, stat_util: float = None,
                        duration: float = None, round_idx: int = None):
        """Post-round feedback hook (Oort utilities, hold-offs...)."""


@dataclasses.dataclass(frozen=True)
class BuildContext:
    """Build-time world state a selector factory may consume.

    ``substrate`` is the seed-built ``repro.sim.engine.Substrate`` (dataset
    + shards, device profiles, traces); ``durations`` the per-learner
    config-determined round durations.  Factories must only *read* — the
    substrate is shared by every cell of a sweep seed.
    """
    cfg: object
    substrate: object = None
    durations: Optional[np.ndarray] = None


# One documented ``SimConfig.selector_params`` knob — the shared
# strategy-table dataclass (re-exported here for selector files).
Knob = _registry.Knob


@dataclasses.dataclass(frozen=True)
class SelectorSpec:
    """One registered selection strategy (a row of ``SELECTOR_TABLE``).

    ``factory(params, ctx)`` builds the per-run policy object from the
    cell's ``selector_params`` dict and a ``BuildContext``;
    ``needs_feedback`` / ``select_all`` are the static program-structure
    descriptors ``selector_key`` folds into ``pipeline_key`` (see module
    docstring); ``knobs`` documents the accepted ``selector_params`` and
    is enforced — an unknown knob is a config error, not a silent no-op.
    """
    name: str
    factory: Callable[[Dict, BuildContext], Selector]
    doc: str = ""
    needs_feedback: bool = False
    select_all: bool = False
    knobs: tuple = ()                 # Knob(...) entries
    cls: Optional[type] = None        # policy class, when 1:1 (listing aid)

    def knob_names(self) -> tuple:
        return tuple(k.name for k in self.knobs)

    def build(self, cfg, substrate=None, durations=None) -> Selector:
        params = dict(cfg.selector_params or ())
        unknown = set(params) - set(self.knob_names())
        if unknown:
            raise ValueError(
                f"selector {self.name!r}: unknown knob(s) {sorted(unknown)} "
                f"(accepted: {list(self.knob_names()) or 'none'})")
        return self.factory(params, BuildContext(cfg, substrate, durations))


def class_factory(cls: type) -> Callable[[Dict, BuildContext], Selector]:
    """Factory for selectors that are plain ``cls(**knobs)`` constructions."""
    return lambda params, ctx: cls(**params)
