"""Participant-selection strategy zoo (ROADMAP item 4).

A selector is a **file, not an engine change**: one module defining a
``Selector`` subclass plus one ``register_selector(SelectorSpec(...))``
call at import time.  The spec's static properties (``needs_feedback``,
``select_all``) describe the fused-program structure the strategy needs,
and ``selector_key`` folds them — with the strategy name and its
``selector_params`` knobs — into ``repro.sim.pipeline.pipeline_key``, so
every selector compiles to its own program variant and sweeps batch
selector-uniformly on shared seeds.  ``docs/extending.md`` is the
contributor guide; ``repro.robust.aggregators`` is the sibling table for
the device-side aggregation strategies.

Registered strategies (``python -m repro.sweeps --list-selectors``):

  random        uniform sampling (FedAvg baseline)
  oort          utility x speed, eps-greedy + pacer (Lai et al., OSDI'21)
  priority      RELAY IPS Alg. 1: least-available-first + hold-off
  safa          select-all, target-ratio round end (Wu et al., 2021)
  flips         label-distribution k-means, cluster-balanced budgets
  ucb           UCB1 bandit on stat-utility rewards
  contribution  decayed contribution ranking + fairness floor
"""
from repro.selection.base import (BuildContext, Knob, LearnerView,  # noqa: F401
                                  Selector, SelectorSpec, class_factory,
                                  views_to_arrays)
from repro.selection.registry import (SELECTOR_TABLE,  # noqa: F401
                                      build_selector, describe_selectors,
                                      normalize_selector_params,
                                      register_selector, selector_key)

# importing a strategy module registers it; table order = listing order
from repro.selection.uniform import RandomSelector  # noqa: F401,E402
from repro.selection.oort import OortSelector  # noqa: F401,E402
from repro.selection.priority import PrioritySelector  # noqa: F401,E402
from repro.selection.safa import SafaSelector  # noqa: F401,E402
from repro.selection.flips import FlipsSelector  # noqa: F401,E402
from repro.selection.ucb import UcbSelector  # noqa: F401,E402
from repro.selection.contribution import ContributionSelector  # noqa: F401,E402

# name -> class map kept for pre-zoo callers (`SELECTORS[name]()`); new
# code should go through SELECTOR_TABLE / build_selector, which honor
# selector_params and build-time context (FLIPS needs the substrate)
SELECTORS = {name: spec.cls for name, spec in SELECTOR_TABLE.items()
             if spec.cls is not None}

__all__ = [
    "BuildContext", "Knob", "LearnerView", "Selector", "SelectorSpec",
    "SELECTOR_TABLE", "SELECTORS", "build_selector", "class_factory",
    "describe_selectors", "normalize_selector_params", "register_selector",
    "selector_key", "views_to_arrays",
    "RandomSelector", "OortSelector", "PrioritySelector", "SafaSelector",
    "FlipsSelector", "UcbSelector", "ContributionSelector",
]
