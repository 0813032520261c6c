"""Chip benchmark of the federated simulator: one cell of BENCHMARK.json,
run once.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: the
``SimConfig`` fields, the model and the data of one deployment) and a
traffic mix (``bench/traffic/<mix>.json``: the policy, the rounds of one
simulation and the entry point). The configuration's ``model`` block names
its kind, and the reference model of that kind, with its counts, is
``bench/models/<kind>.py`` (``kinds.py``). Per-layer metrics are readers in
``bench/metrics/<metric>.py``; the limits of the correctness check are in
``bench/limits/<cell>.json``. Everything is found by name, so a new model,
mix, metric or cell enters as files.

Set-up (``setup_s``, from process start): the world is built from the seed
(the program's ``Substrate``, given the data of ``datagen.py`` and the
initial weights of the reference model, made on the device); the compile
cache is placed; one warm-up simulation of the same configuration and seed
compiles every program the window will use.

The window repeats that simulation, a whole ``Simulator.run()`` each time,
until ``--seconds`` have passed, and ends when the last one finishes.
``rounds_per_s`` is the rounds all of them completed over the window's
wall seconds; ``tokens_per_s`` the tokens that learners trained (rows that
trained x local steps x local batch x sequence length; padding rows are
not counted) over the same seconds.

After the window the last simulation's final parameters and eval losses
are compared with the reference's replay of its round log
(``reference.py``, one trained row at a time); the numbers compared and
their limits are printed as the last lines of standard error and under
``checks`` in the result.

With ``--trace 1`` the window runs under the JAX profiler with the
program's host spans on, and the result carries the cell's per-layer
metrics instead of its end-to-end ones.

The last line of standard output is one JSON object. Where JAX finds no
TPU, or fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""
import time

T0 = time.perf_counter()

import os  # noqa: E402

# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

import datagen  # noqa: E402
import reference  # noqa: E402
from kinds import module as _module  # noqa: E402
import tracefile  # noqa: E402
from counts import work  # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
SPAN_NAMES = ("schedule", "pack", "dispatch", "fetch", "eval")
TRACE_DIR = ROOT / ".bench_out" / "trace"


class NoChip(RuntimeError):
    pass


def _json(path: pathlib.Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str) -> dict:
    """The cell's entries and files, found by name."""
    spec = _json(ROOT / "BENCHMARK.json")
    (cell,) = [w for w in spec["workloads"] if w["name"] == name]
    (conf,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return dict(cell=cell, config=_json(ROOT / conf["file"]),
                traffic=_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
                limits=_json(BENCH / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache
    (``jax.monitoring``'s backend-compile event wraps both) and cache hits;
    ``compiled`` is the difference."""

    def __init__(self):
        self.n, self.hits = 0, 0

    def on_duration(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    @property
    def compiled(self) -> int:
        return self.n - self.hits


@functools.lru_cache(maxsize=1)
def recording_simulator():
    """``Simulator`` that keeps its round log: per round, the sample
    indices of every planned learner, which rows arrive fresh, which enter
    the stale cache and which cached updates land (learner, round of
    origin). The log is what the reference replays; recording it changes
    no decision and no compiled program."""
    from repro.sim import Simulator

    class Recording(Simulator):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.round_log = []

        def _schedule_round(self, r, plan):
            sched = super()._schedule_round(r, plan)
            self.round_log.append({
                "round": int(r),
                "bidx": np.asarray(plan.bidx),
                "trained": int(np.sum(~np.isfinite(plan.drop_at))),
                "fresh": [int(i) for i in sched.fresh_rows],
                "new_stale": [(int(i), int(lid))
                              for i, lid, _a, _d in sched.new_stale],
                "landing": [(int(f.learner_id), int(f.origin_round))
                            for f in sched.landing]})
            return sched

    return Recording


@dataclasses.dataclass
class World:
    cfg: object
    substrate: object
    model: object
    params0: object
    arrays: dict
    sim_fields: dict


def build_world(config: dict, traffic: dict, seed: int) -> World:
    import jax
    import jax.numpy as jnp
    from repro.sim import SimConfig
    from repro.sim.engine import Substrate
    from repro.sim.partition import FederatedDataset

    fields = dict(config["sim"])
    clash = set(fields) & set(traffic["sim"])
    if clash:
        raise ValueError(f"configuration and traffic both set {sorted(clash)}")
    fields.update(traffic["sim"])
    fields["model_params"] = tuple(tuple(kv) for kv in fields["model_params"])
    cfg = SimConfig(**fields, seed=int(seed))
    sub = Substrate.build(cfg)
    arrays = datagen.make(config["data"], cfg.n_learners, seed)
    model = reference.Model(config["model"])
    params0 = model.init(seed)
    treedef, shapes = sub.flat_spec[0], list(sub.flat_spec[1])
    if (jax.tree.structure(params0) != treedef
            or [tuple(a.shape) for a in jax.tree.leaves(params0)] != shapes):
        raise ValueError("the reference's parameter tree does not match the "
                         "program's learner")
    flat0 = jax.jit(lambda t: jnp.concatenate(
        [jnp.ravel(a) for a in jax.tree.leaves(t)]))(params0)
    data = FederatedDataset(
        cfg.benchmark, arrays["x_train"], arrays["y_train"],
        arrays["x_test"], arrays["y_test"], arrays["shards"],
        kind=sub.data.kind, vocab=sub.data.vocab)
    sub = dataclasses.replace(sub, data=data, params0=params0,
                              flat_params0=np.asarray(flat0), _warmed=None)
    return World(cfg, sub, model, params0, arrays, fields)


def simulate(world: World, telemetry=None):
    """One whole simulation through the user's entry point. Returns the
    Simulator (final parameters in ``flat_params``) and its Accounting."""
    import jax
    sim = recording_simulator()(world.cfg, substrate=world.substrate)
    acct = sim.run(telemetry=telemetry)
    jax.block_until_ready(sim.flat_params)
    return sim, acct


def eval_losses(acct) -> dict:
    return {int(r.round_idx): float(r.loss) for r in acct.records
            if r.loss == r.loss}


def window(world: World, seconds: float, traced: bool):
    """Simulations back to back until ``seconds`` have passed."""
    tele = None
    if traced:
        from repro.telemetry import TelemetrySession
        from repro.telemetry.trace import Tracer
    sims, rounds, rows, agg_rows = 0, 0, 0, []
    t0 = time.perf_counter()
    while True:
        if traced:
            tele = TelemetrySession(tracer=Tracer(enabled=True,
                                                  jax_profiler=True))
        sim, acct = simulate(world, tele)
        sims += 1
        rounds += len(acct.records)
        rows += sum(e["trained"] for e in sim.round_log)
        agg_rows += [len(e["fresh"]) + len(e["landing"])
                     for e in sim.round_log if e["fresh"] or e["landing"]]
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    return dict(sim=sim, acct=acct, sims=sims, rounds=rounds, rows=rows,
                agg_rows=agg_rows, wall=wall)


def leaves_of(flat: np.ndarray, params0) -> list:
    import jax
    out, off = [], 0
    for a in jax.tree.leaves(params0):
        n = int(np.prod(a.shape))
        out.append(flat[off:off + n].reshape(a.shape))
        off += n
    return out


def leaves_flat(tree) -> np.ndarray:
    import jax
    return np.concatenate([np.asarray(a, np.float32).ravel()
                           for a in jax.tree.leaves(tree)])


def compare(world: World, prog_flat, prog_losses, ref_p, ref_losses) -> dict:
    """The numbers the check compares: the worst relative gap between the
    program's and the reference's eval loss over the eval rounds, and the
    worst leaf's gap between the norms of their parameter changes."""
    import jax
    gaps = [abs(prog_losses[r] - ref_losses[r]) / abs(ref_losses[r])
            for r in prog_losses]
    loss_gap = max(gaps) if gaps else math.inf
    if any(not math.isfinite(x) for x in gaps):
        loss_gap = math.inf
    change_gap = reference.leaf_norm_gap(
        world.params0, leaves_of(np.asarray(prog_flat), world.params0),
        [np.asarray(a) for a in jax.tree.leaves(ref_p)])
    return {"eval_loss_gap": loss_gap, "param_change_gap": change_gap}


def replay(world: World, log, rounds, *, dtype=None, half_batch=False):
    import jax.numpy as jnp
    model = (world.model if dtype is None else
             reference.Model(world.model.spec, dtype=dtype,
                             precision="default"))
    return reference.Replay(model, world.sim_fields, world.arrays,
                            half_batch=half_batch).run(world.params0, log,
                                                       set(rounds))


def check(world: World, prog_flat, prog_losses, log, limits: dict):
    ref_p, ref_losses = replay(world, log, prog_losses)
    numbers = compare(world, prog_flat, prog_losses, ref_p, ref_losses)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def per_layer(spec_metrics, ctx) -> dict:
    out = {}
    for m in spec_metrics:
        v = _module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_cell(c: dict, seed: int, seconds: float, traced: bool,
             require_tpu: bool = True, log=print) -> dict:
    """Runs a cell loaded by ``load_cell``; returns the result object."""
    cell, config, traffic = c["cell"], c["config"], c["traffic"]
    device = device_info(int(cell["chips"]), require_tpu)
    peaks = _json(BENCH / "peaks.json")["devices"]
    if require_tpu and device["kind"] not in peaks:
        raise NoChip(f"no peaks for device kind {device['kind']!r}")
    peak = peaks.get(device["kind"])
    if traffic["entry"] != "serial":
        raise ValueError(f"unknown entry point {traffic['entry']!r}")

    import jax
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter.on_duration)
    jax.monitoring.register_event_listener(counter.on_event)

    world = build_world(config, traffic, seed)
    simulate(world)                                   # warm-up
    warm = (counter.n, counter.compiled)
    setup_s = time.perf_counter() - T0

    counter.n = counter.hits = 0
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(str(TRACE_DIR), profiler_options=opts):
            w = window(world, seconds, traced=True)
    else:
        w = window(world, seconds, traced=False)
    log(f"compiles_in_window: {counter.n} (set-up: {warm[0]} programs, "
        f"{warm[1]} compiled, the rest from the persistent cache)",
        file=sys.stderr)
    mem = max(d.memory_stats().get("peak_bytes_in_use", 0)
              if d.memory_stats() else 0
              for d in jax.local_devices()[:int(cell["chips"])])
    device["memory_peak_bytes"] = int(mem)

    sim, acct = w.pop("sim"), w.pop("acct")
    prog_flat = np.asarray(sim.flat_params)[:world.substrate.flat_params0.size]
    prog_losses, log_r = eval_losses(acct), sim.round_log
    del sim, acct
    gc.collect()

    seq_len = int(config["data"].get("seq_len", 0))
    metrics = {}
    if traced:
        trace = tracefile.load(str(TRACE_DIR), SPAN_NAMES)
        ctx = argparse.Namespace(
            trace=trace, window_s=w["wall"], chips=int(cell["chips"]),
            rounds=w["rounds"], peak=peak, agg_rows=w["agg_rows"],
            d=int(world.substrate.flat_params0.size),
            train_flop=w["rows"] * world.cfg.local_steps
            * world.cfg.local_batch
            * work.train_flop_per_sample(config["model"], seq_len))
        metrics = per_layer(c["per_layer"], ctx)
        busy = tracefile.busy_s(trace)
        device["busy_s"] = busy if busy is not None else 0.0
        device["window_s"] = w["wall"]
        breakdown = {"device_ops": tracefile.top_ops(trace),
                     "idle_gaps": tracefile.idle_gaps(trace)}
    else:
        rates = {"rounds_per_s": w["rounds"] / w["wall"],
                 "tokens_per_s": w["rows"] * world.cfg.local_steps
                 * world.cfg.local_batch * seq_len / w["wall"],
                 "setup_s": setup_s}
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": rates[m["name"]], "unit": m["unit"]}

    t_ref = time.perf_counter()
    ok, checks = check(world, prog_flat, prog_losses, log_r, c["limits"])
    log(f"reference_s: {time.perf_counter() - t_ref:.1f}", file=sys.stderr)
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
            file=sys.stderr)
    result = {"correct": bool(ok), "attempted": w["sims"], "failed": 0,
              "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def place_compile_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed place
    (``JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``),
    every program kept, so that only a cell's first run compiles."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    c = load_cell(a.workload)
    try:
        device_info(int(c["cell"]["chips"]), require_tpu=True)
        place_compile_cache()
        result = run_cell(c, a.seed, a.seconds, bool(a.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
