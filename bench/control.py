"""Readings that set a cell's limits; not part of a benchmark run.

  python3 bench/control.py --workload <cell> --seeds 1 2 3 ... [--faults 3]

For every seed it runs one simulation of the cell through the program and
prints, as one JSON line, the numbers ``run.py`` compares (``program``:
the sound readings). For the first ``--faults`` seeds it also prints the
same numbers with the reference's replay put in the program's place:

- ``control``: the replay in bfloat16 (parameters, activations, updates
  and aggregation), the precision below the configuration's float32;
- ``half_batch``: the replay with each local step training on half of its
  batch (a planted fault).

A state left unchanged reads 1 on ``param_change_gap`` by definition and
needs no run. The limits in ``limits/<cell>.json`` are set from these
readings (``PERF.md`` gives them).
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def readings(c: dict, seed: int, faults: bool) -> dict:
    import jax.numpy as jnp
    world = run.build_world(c["config"], c["traffic"], seed)
    sim, acct = run.simulate(world)
    flat = run.np.asarray(sim.flat_params)[:world.substrate.flat_params0.size]
    losses, log = run.eval_losses(acct), sim.round_log
    del sim, acct
    ref_p, ref_l = run.replay(world, log, losses)
    out = {"seed": seed,
           "program": run.compare(world, flat, losses, ref_p, ref_l),
           "eval_losses": losses}
    if faults:
        for label, kw in (("control", {"dtype": jnp.bfloat16}),
                          ("half_batch", {"half_batch": True})):
            p, l = run.replay(world, log, losses, **kw)
            out[label] = run.compare(world, run.leaves_flat(p), l, ref_p,
                                     ref_l)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3)
    a = ap.parse_args(argv)
    c = run.load_cell(a.workload)
    run.device_info(int(c["cell"]["chips"]), require_tpu=True)
    run.place_compile_cache()
    for k, seed in enumerate(a.seeds):
        print(json.dumps(readings(c, seed, faults=k < a.faults)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
