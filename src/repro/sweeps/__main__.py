"""Sweep demo / smoke entry point.

  PYTHONPATH=src python -m repro.sweeps                  # demo grid
  PYTHONPATH=src python -m repro.sweeps --smoke          # small CI grid
  PYTHONPATH=src python -m repro.sweeps --list-selectors # strategy tables
  PYTHONPATH=src python -m repro.sweeps --selector random,oort,flips,ucb

Expands a policy x SAA x hardware grid (or, with ``--selector``, a
selector-zoo grid racing strategies from ``repro.selection`` under
matched seeds), runs it batched, re-runs every cell serially to assert
bit-identical metrics, prints the paper-style resource-to-accuracy table,
and writes ``BENCH_sweeps.json`` (batched vs serial wall-clock) at the
repo root.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib

from repro.sweeps import (SweepSpec, assert_parity, resume_sweep, run_batched,
                          run_serial)
from repro.sweeps.report import savings_line, text_table


def demo_spec(smoke: bool) -> SweepSpec:
    if smoke:
        return SweepSpec(
            axes={"policy": ["random", "relay"], "saa": [False, True]},
            base=dict(n_learners=60, rounds=8, eval_every=4, n_target=5,
                      mapping="label_uniform"),
            seeds=(0,))
    return SweepSpec(
        axes={"policy": ["random", "oort", "safa", "relay"],
              "saa": [False, True],
              "hardware": ["HS1", "HS3"]},
        base=dict(n_learners=100, rounds=40, eval_every=10,
                  mapping="label_uniform"),
        seeds=(0, 1))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="small CI grid")
    ap.add_argument("--sharded", action="store_true",
                    help="shard the sweep axis over the local device mesh")
    ap.add_argument("--participant-shards", type=int, default=0,
                    help="shard each round's cohort rows over N participant "
                         "mesh shards (with --sharded: a (devices/N) x N "
                         "('s', 'p') mesh; alone: N of the local devices)")
    ap.add_argument("--rounds-per-dispatch", type=int, default=1,
                    help="K rounds per device dispatch (lax.scan chunking)")
    ap.add_argument("--out", default=None, help="BENCH_sweeps.json path")
    ap.add_argument("--checkpoint", default=None,
                    help="write crash-safe sweep snapshots to this path")
    ap.add_argument("--checkpoint-every", type=int, default=2,
                    help="rounds between snapshots (with --checkpoint)")
    ap.add_argument("--resume", default=None, metavar="CKPT",
                    help="resume a crashed sweep from its snapshot and "
                         "write the completed results (bit-identical to an "
                         "uninterrupted run)")
    ap.add_argument("--crash-after", type=int, default=None, metavar="R",
                    help="chaos: inject a crash once round R completes")
    ap.add_argument("--crash-hard", action="store_true",
                    help="chaos: crash via SIGKILL instead of an exception")
    ap.add_argument("--telemetry-dir", default=None, metavar="DIR",
                    help="run at full telemetry (level 2) and export the run "
                         "timeline there: rounds.jsonl / events.jsonl, "
                         "trace.json (Perfetto), metrics.prom")
    ap.add_argument("--aggregator", default=None, metavar="A,B",
                    help="add a robust-aggregator sweep axis (comma list "
                         "from saa, coord_median, trimmed_mean, krum, "
                         "multi_krum, norm_median_clip)")
    ap.add_argument("--attack", default=None, metavar="X,Y",
                    help="add a coordinated-attack sweep axis (comma list "
                         "from none, collude_signflip, collude_same_value, "
                         "alie, adaptive); attacked and clean cells share "
                         "seeds, so every comparison is matched-condition")
    ap.add_argument("--attack-frac", type=float, default=0.25,
                    help="attacker fraction of the population (with --attack)")
    ap.add_argument("--selector", default=None, metavar="A,B",
                    help="race selection strategies: replaces the demo grid's "
                         "policy axis with a selector axis (comma list from "
                         "the repro.selection zoo; see --list-selectors)")
    ap.add_argument("--model", default=None, metavar="A,B",
                    help="add a learner-model sweep axis (comma list from "
                         "the repro.learners zoo; see --list-models; LM "
                         "models need --benchmark tokens)")
    ap.add_argument("--benchmark", default=None, metavar="B",
                    help="override the grid's benchmark (classifier: speech/"
                         "cifar10/openimage; LM: tokens/tokens_skew)")
    ap.add_argument("--list-selectors", action="store_true",
                    help="print the registered selector strategy table "
                         "(name, cadence, knobs) and exit")
    ap.add_argument("--list-aggregators", action="store_true",
                    help="print the registered robust-aggregator strategy "
                         "table and exit")
    ap.add_argument("--list-models", action="store_true",
                    help="print the registered learner-model strategy table "
                         "(name, family, data kind, kernel, knobs) and exit")
    args = ap.parse_args(argv)

    if args.list_selectors or args.list_aggregators or args.list_models:
        if args.list_selectors:
            from repro.selection import describe_selectors
            print(describe_selectors())
        if args.list_aggregators:
            from repro.robust.aggregators import describe_aggregators
            print(describe_aggregators())
        if args.list_models:
            from repro.learners import describe_models
            print(describe_models())
        return

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    telemetry = None
    if args.telemetry_dir:
        from repro.telemetry import TelemetrySession
        telemetry = TelemetrySession(args.telemetry_dir)
    try:
        _run(args, telemetry)
    finally:
        if telemetry is not None:
            telemetry.close()
            print(f"# telemetry exported to {args.telemetry_dir}")


def _run(args, telemetry) -> None:
    if args.resume:
        results, wall = resume_sweep(args.resume, telemetry=telemetry)
        print(f"# resumed from {args.resume} in {wall:.2f}s "
              f"({len(results)} cells)")
        print(text_table(results))
        if args.out:
            payload = {"bench": "sweeps", "mode": "resume",
                       "resumed_from": args.resume, "cells": len(results),
                       "results": results.to_json_dict()}
            pathlib.Path(args.out).write_text(
                json.dumps(payload, indent=2) + "\n")
            print(f"\n# wrote {args.out}")
        return

    spec = demo_spec(args.smoke)
    if args.selector:
        # the selector axis REPLACES the policy axis: policy presets differ
        # (partly) by selector, so stacking both would collapse cells onto
        # identical configs (expand() rejects that); shared-seed pairing
        # makes the zoo race matched-condition
        axes = {k: v for k, v in spec.axes.items() if k != "policy"}
        spec.axes = {"selector": args.selector.split(","), **axes}
    # --aggregator / --attack extend the grid: both are raw SimConfig
    # fields, so they ride the grid's field-axis fallthrough and inherit
    # shared-seed pairing (attack x defense cells see identical cohorts)
    if args.aggregator:
        kinds = args.aggregator.split(",")
        spec.axes = dict(spec.axes, aggregator=kinds)
        if any(k in ("krum", "multi_krum") for k in kinds):
            spec.base = dict(spec.base, krum_f=max(
                int(dict(spec.base).get("krum_f", 0)), 1))
    if args.attack:
        spec.axes = dict(spec.axes, attack=args.attack.split(","))
        spec.base = dict(spec.base, attack_frac=args.attack_frac)
    if args.model:
        spec.axes = dict(spec.axes, model=args.model.split(","))
    if args.benchmark:
        base = dict(spec.base, benchmark=args.benchmark)
        # token benchmarks own their data-to-learner mapping (the shard
        # structure); drop a classifier-grid mapping axis value silently
        if args.benchmark in ("tokens", "tokens_skew"):
            base.pop("mapping", None)
        spec.base = base
    cells = spec.expand()
    if args.rounds_per_dispatch != 1:
        cells = [dataclasses.replace(c, config=dataclasses.replace(
            c.config, rounds_per_dispatch=args.rounds_per_dispatch))
            for c in cells]
    if telemetry is not None:
        cells = [dataclasses.replace(c, config=dataclasses.replace(
            c.config, telemetry=2)) for c in cells]
    if args.sharded or args.participant_shards:
        import jax
        axes = (["sweep"] if args.sharded else []) \
            + (["participant"] if args.participant_shards else [])
        print(f"# sharding the {'+'.join(axes)} axis over "
              f"{len(jax.devices())} device(s)")
    print(f"# sweep: {len(cells)} cells "
          f"({' x '.join(f'{a}[{len(v)}]' for a, v in spec.axes.items())}"
          f" x seeds[{len(spec.seeds)}])")

    fault_plan = None
    if args.crash_after is not None:
        from repro.faults import FaultPlan
        fault_plan = FaultPlan(
            n_learners=max(c.config.n_learners for c in cells),
            rounds=max(c.config.rounds for c in cells),
            crash_after=args.crash_after,
            crash_mode="hard" if args.crash_hard else "soft")
    results, batched_wall = run_batched(
        cells, shard=args.sharded,
        shard_participants=args.participant_shards,
        fault_plan=fault_plan,
        checkpoint_path=args.checkpoint,
        checkpoint_every=args.checkpoint_every if args.checkpoint else 0,
        telemetry=telemetry)
    # the serial reference stays at K=1 and telemetry off: an independent
    # ground truth, not the same machinery run twice (level-2 telemetry is
    # bit-transparent, so the parity assert below also proves that)
    serial_cells = ([dataclasses.replace(c, config=dataclasses.replace(
        c.config, rounds_per_dispatch=1, telemetry=0)) for c in cells]
        if args.rounds_per_dispatch != 1 or telemetry is not None else cells)
    serial_summaries, serial_wall = run_serial(serial_cells)
    assert_parity(results, serial_summaries)
    speedup = serial_wall / max(batched_wall, 1e-9)
    print(f"# batched {batched_wall:.2f}s vs serial {serial_wall:.2f}s "
          f"({speedup:.1f}x), per-cell metrics bit-identical\n")
    print(text_table(results))
    if "policy" in spec.axes:
        print()
        print(savings_line(results, {"policy": "relay", "saa": True},
                           {"policy": "random", "saa": False}))

    out = (pathlib.Path(args.out) if args.out else
           pathlib.Path(__file__).resolve().parents[3] / "BENCH_sweeps.json")
    payload = {
        "bench": "sweeps",
        "mode": "smoke" if args.smoke else "demo",
        "sharded": args.sharded,
        "participant_shards": args.participant_shards,
        "rounds_per_dispatch": args.rounds_per_dispatch,
        "cells": len(cells),
        "batched_wall_s": round(batched_wall, 3),
        "serial_wall_s": round(serial_wall, 3),
        "speedup": round(speedup, 2),
        "parity": True,
        "results": results.to_json_dict(),
    }
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"\n# wrote {out}")


if __name__ == "__main__":
    main()
