"""Shared benchmark helpers: run simulator variants, emit CSV rows.

Scale note: the paper uses 1000-3000 learners / 500-1000 rounds on a GPU
cluster; these benchmarks run the same *system* at CPU scale (default 100
learners, 60 rounds) — the comparisons, not the absolute numbers, are the
reproduction target.  Scale up with REPRO_BENCH_SCALE=full.
"""
from __future__ import annotations

import os
import time

from repro.compile_cache import enable_compile_cache
from repro.sim import SimConfig, Simulator

FULL = os.environ.get("REPRO_BENCH_SCALE", "small") == "full"
N_LEARNERS = 1000 if FULL else 100
ROUNDS = 500 if FULL else 60
EVAL_EVERY = 20 if FULL else 15


def run_variant(name: str, **overrides):
    enable_compile_cache()
    cfg_kw = dict(n_learners=N_LEARNERS, rounds=ROUNDS, eval_every=EVAL_EVERY,
                  seed=overrides.pop("seed", 0))
    cfg_kw.update(overrides)
    t0 = time.time()
    acct = Simulator(SimConfig(**cfg_kw)).run()
    wall = time.time() - t0
    s = acct.summary()
    return acct, s, wall


def emit(table: str, variant: str, s: dict, wall: float, extra: str = ""):
    """name,us_per_call,derived CSV convention."""
    us_per_round = wall / max(s["rounds"], 1) * 1e6
    derived = (f"acc={s['final_accuracy']:.4f};res={s['resource_used']:.0f}s;"
               f"waste={s['waste_fraction']:.3f};time={s['sim_time']:.0f}s;"
               f"unique={s['unique_participants']}")
    if extra:
        derived += ";" + extra
    print(f"{table}/{variant},{us_per_round:.0f},{derived}")
