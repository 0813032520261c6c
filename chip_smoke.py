"""Smoke test of the federated round pipeline on a TPU.

  python chip_smoke.py               # one chip: phases 1-3
  python chip_smoke.py --four-chips  # four chips: the two mesh paths only

Every phase drives the system through its user entry points
(``Simulator``/``SimConfig``, ``SweepRunner``) in this one process, and
prints one line: wall seconds, compile seconds (the union of the
program's ``compile`` spans: tracing, lowering and backend compiles,
persistent-cache loads included), the programs lowered and the cache
hits, and the outcome of its comparison.  A comparison outside its
tolerance raises, and so does any other failure: nothing is caught, and
the exit code is non-zero.

One chip:

1. ``serial[oort]``, ``serial[priority]``: the paper-scale mlp simulation
   (n=1000, SAA on, 20 rounds, eval every 10) through the fused pipeline
   with ``rounds_per_dispatch=1``, compared with the same run at
   ``rounds_per_dispatch=4`` (``k4``), with the SAA Pallas kernel
   (``kernel``), and with the flat per-stage reference (``flat``).
2. ``sweep``: ``SweepRunner`` with the kernel over 4 cells (two
   selectors x SAA off/on), each cell compared with its serial run.
3. ``lm``: the transformer learner on the token benchmark at its
   registered default knobs (D=213,312, padded to 215,040), kernel and
   SAA on, fused pipeline compared with ``fused_rounds=False``.

Four chips (``--four-chips``):

4. ``participant_mesh``: n=10,000 with ``shard_participants=4`` against
   the same run unsharded; the mesh must span 4 devices and the compiled
   round program must hold exactly one all-reduce.
5. ``sweep_mesh``: the phase-2 grid on a 2x2 ``("s", "p")`` mesh
   (``SweepRunner(shard=True, shard_participants=2)``) against the
   unsharded batched sweep.

The last line of standard output is one JSON object naming the device.
Where JAX finds no TPU the script exits non-zero before any phase and
prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import re
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# Where a pair is not bitwise equal, these bound the difference.  On the
# CPU every pair's summary is bit-identical and the final parameters agree
# to a few ulps (a fused program may round one op differently).  On a TPU,
# XLA runs fp32 jnp matmuls at its default precision (bf16 passes; this
# script leaves the global precision alone), and the Pallas kernel's
# jnp.dot and the differently fused per-stage programs need not round the
# same way, so the last bits of each aggregate may differ; twenty server
# steps carry that into the parameters.  A bf16 rounding is 2^-8 ~ 4e-3 on
# one aggregate, and an aggregate moves the parameters by about a percent
# of their norm, so 1e-2 on the final parameters' relative L2 leaves room
# for error growth while still failing a wrong aggregate, a lost update or
# a diverged schedule.  Accuracy: 0.02 is 28 of the 1,400 speech test
# samples.  Eval loss: 1e-2 relative, as for the parameters.
PARAM_RTOL = 1e-2
ACC_ATOL = 0.02
LOSS_RTOL = 1e-2


@dataclasses.dataclass
class Run:
    """What a comparison looks at: the fixed-key summary, the final flat
    parameters (None where the entry point does not expose them) and the
    last evaluated loss."""
    summary: dict
    params: np.ndarray | None
    loss: float


def _last_loss(acct) -> float:
    losses = [r.loss for r in acct.records if r.loss == r.loss]
    return losses[-1] if losses else math.nan


def _flat(params) -> np.ndarray:
    import jax
    return np.concatenate([np.ravel(np.asarray(x, np.float64))
                           for x in jax.tree.leaves(params)])


def _run_sim(cfg, substrate) -> Run:
    from repro.sim import Simulator
    sim = Simulator(cfg, substrate=substrate)
    acct = sim.run()
    return Run(dict(acct.summary()), _flat(sim.params), _last_loss(acct))


def compare(label: str, ref: Run, got: Run) -> str:
    """``label=bitwise`` when summary, loss and parameters are all bit-
    identical; ``summary-bitwise`` when the summary and loss are (the
    repo's own parity criterion) but the parameters are not; ``close``
    otherwise.  The last two give the distances, and raise when they
    exceed the tolerances above."""
    from repro.sweeps.runner import summaries_equal
    summary_same = (summaries_equal(ref.summary, got.summary)
                    and (ref.loss == got.loss
                         or (ref.loss != ref.loss and got.loss != got.loss)))
    if summary_same and (ref.params is None
                         or np.array_equal(ref.params, got.params)):
        return f"{label}=bitwise"
    rel = 0.0
    if ref.params is not None:
        rel = float(np.linalg.norm(got.params - ref.params)
                    / max(np.linalg.norm(ref.params), 1e-30))
    dacc = abs(got.summary["final_accuracy"] - ref.summary["final_accuracy"])
    dloss = abs(got.loss - ref.loss) / max(abs(ref.loss), 1e-30)
    kind = "summary-bitwise" if summary_same else "close"
    out = (f"{label}={kind}(rel_l2={rel!r},dacc={dacc!r},dloss={dloss!r},"
           f"rounds={got.summary['rounds']}/{ref.summary['rounds']})")
    if not (rel <= PARAM_RTOL and dacc <= ACC_ATOL and dloss <= LOSS_RTOL
            and got.summary["rounds"] == ref.summary["rounds"]):
        raise AssertionError(f"{out} exceeds the tolerance "
                             f"(rel_l2 {PARAM_RTOL}, dacc {ACC_ATOL}, "
                             f"dloss {LOSS_RTOL}, equal rounds)")
    return out


# ---------------------------------------------------------------------------
# Phases: each returns the outcome part of its line
# ---------------------------------------------------------------------------


def phase_serial(selector: str, n_learners=1000, rounds=20,
                 eval_every=10) -> str:
    from repro.sim import SimConfig
    from repro.sim.engine import Substrate
    base = SimConfig(n_learners=n_learners, rounds=rounds,
                     eval_every=eval_every, saa=True, selector=selector,
                     seed=0)
    sub = Substrate.build(base)
    ref = _run_sim(base, sub)
    parts = [f"acc={ref.summary['final_accuracy']!r}"]
    for label, over in (("k4", dict(rounds_per_dispatch=4)),
                        ("kernel", dict(use_agg_kernel=True)),
                        ("flat", dict(fused_rounds=False))):
        parts.append(compare(label, ref,
                             _run_sim(dataclasses.replace(base, **over), sub)))
    return " ".join(parts)


def _sweep_cells(n_learners, rounds, eval_every):
    from repro.sweeps import SweepSpec
    return SweepSpec(
        axes={"selector": ["oort", "priority"], "saa": [False, True]},
        base=dict(n_learners=n_learners, rounds=rounds,
                  eval_every=eval_every, use_agg_kernel=True),
        seeds=(0,)).expand()


def phase_sweep(n_learners=1000, rounds=20, eval_every=10) -> str:
    from repro.sim import Simulator
    from repro.sweeps import SweepRunner
    runner = SweepRunner(_sweep_cells(n_learners, rounds, eval_every))
    results = runner.run()
    parts = []
    for res in results:
        cfg = res.cell.config
        acct = Simulator(cfg, substrate=runner.substrate(cfg)).run()
        parts.append(compare(
            res.cell.name,
            Run(dict(acct.summary()), None, _last_loss(acct)),
            Run(dict(res.summary), None, _last_loss(res.acct))))
    return " ".join(parts)


def phase_lm(n_learners=100, rounds=4, eval_every=2, model_params=()) -> str:
    from repro.kernels.staleness_agg.staleness_agg import col_quantum
    from repro.sim import SimConfig
    from repro.sim.engine import Substrate
    base = SimConfig(model="transformer", benchmark="tokens",
                     model_params=model_params, n_learners=n_learners,
                     rounds=rounds, eval_every=eval_every, saa=True,
                     use_agg_kernel=True, seed=0)
    sub = Substrate.build(base)
    fused = _run_sim(base, sub)
    flat = _run_sim(dataclasses.replace(base, fused_rounds=False), sub)
    d = fused.params.size
    return (f"D={d} d_pad={d + (-d) % col_quantum(d)} "
            f"loss={fused.loss!r} " + compare("flat", flat, fused))


def _count_all_reduce(hlo: str) -> int:
    return len(re.findall(r"all-reduce(?:-start)?\(", hlo))


def phase_participant_mesh(n_learners=10000, rounds=6, eval_every=3,
                           n_shards=4) -> str:
    from repro.sim import SimConfig, Simulator
    from repro.sim.engine import Substrate
    from repro.sim.pipeline import RoundPipeline
    cfg = SimConfig(n_learners=n_learners, rounds=rounds,
                    eval_every=eval_every, n_target=64, saa=True,
                    selector="priority", mapping="label_uniform", seed=0)
    sub = Substrate.build(cfg)
    ref = _run_sim(cfg, sub)
    sim = Simulator(dataclasses.replace(cfg, shard_participants=n_shards),
                    substrate=sub)
    pipe = RoundPipeline([sim])
    devices = {d.id for d in pipe.mesh.devices.flat}
    if len(devices) != n_shards:
        raise AssertionError(f"participant mesh spans {len(devices)} "
                             f"devices, expected {n_shards}")
    prog, hlos = pipe._prog, []

    def capture(*args):
        if not hlos:
            hlos.append(prog.lower(*args).compile().as_text())
        return prog(*args)

    pipe._prog = capture
    acct = pipe.run()[0]
    n_ar = _count_all_reduce(hlos[0])
    if n_ar != 1:
        raise AssertionError(f"round program holds {n_ar} all-reduces, "
                             "expected exactly 1")
    got = Run(dict(acct.summary()), _flat(sim.params), _last_loss(acct))
    return (f"mesh={dict(pipe.mesh.shape)} all_reduce={n_ar} "
            + compare("unsharded", ref, got))


def phase_sweep_mesh(n_learners=1000, rounds=20, eval_every=10) -> str:
    from repro.sweeps import SweepRunner
    cells = _sweep_cells(n_learners, rounds, eval_every)
    ref = SweepRunner(cells).run()
    runner = SweepRunner(cells, shard=True, shard_participants=2)
    if dict(runner.mesh.shape) != {"s": 2, "p": 2}:
        raise AssertionError(f"sweep mesh is {dict(runner.mesh.shape)}, "
                             "expected s=2 x p=2")
    got = runner.run()
    return f"mesh={dict(runner.mesh.shape)} " + " ".join(
        compare(a.cell.name,
                Run(dict(a.summary), None, _last_loss(a.acct)),
                Run(dict(b.summary), None, _last_loss(b.acct)))
        for a, b in zip(ref, got))


def run_phase(name: str, tele, fn, *args) -> None:
    """Runs one phase; ``tele`` is an open enabled session, which records
    every compile of the process as ``compile`` spans and counters."""
    from repro.telemetry import compile as compile_spans
    reg = tele.registry
    n0, t0 = len(tele.tracer.events), time.perf_counter()
    lowered0 = reg.value("compile_programs_lowered")
    hits0 = reg.value("compile_cache_hits")
    outcome = fn(*args)
    wall = time.perf_counter() - t0
    compile_s = compile_spans.seconds(tele.tracer.events[n0:])
    print(f"{name}: wall_s={wall!r} compile_s={compile_s!r} "
          f"lowered={reg.value('compile_programs_lowered') - lowered0} "
          f"cache_hits={reg.value('compile_cache_hits') - hits0} "
          f"{outcome}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the two mesh phases, on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this script does not fall back to the CPU", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.kernels import resolve_interpret
    cache_dir = enable_compile_cache()
    if resolve_interpret(None):
        raise AssertionError("Pallas kernels would run in interpret mode")
    print(f"# {len(devices)} x {devices[0].device_kind}; compile cache "
          f"{cache_dir}", flush=True)
    from repro.telemetry import TelemetrySession, Tracer
    tele = TelemetrySession(tracer=Tracer(enabled=True))

    if args.four_chips:
        run_phase("participant_mesh", tele, phase_participant_mesh)
        run_phase("sweep_mesh", tele, phase_sweep_mesh)
    else:
        for selector in ("oort", "priority"):
            run_phase(f"serial[{selector}]", tele, phase_serial, selector)
        run_phase("sweep", tele, phase_sweep)
        run_phase("lm", tele, phase_lm)
    tele.close()

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
