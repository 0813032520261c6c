"""Event-driven FL round engine reproducing the paper's methodology (§5.1).

Supports the paper's experimental settings:
  OC — over-commit selection by 30% and wait for the first N_t updates;
  DL — fixed reporting deadline, aggregate whatever arrived.
SAFA semantics (select-all + target-ratio round end + bounded-staleness cache)
and RELAY semantics (IPS + APT + SAA with Eq. 2 weights) are both expressible.

Simulated time is decoupled from wall-clock: device durations come from the
heterogeneity profiles, availability from the trace substrate, and every
round's cohort trains in one vmapped JAX call.

Three substrates, same semantics (parity-tested in
tests/test_fastpath_parity.py and tests/test_pipeline_parity.py):

  fused device-resident pipeline (default) — the whole device side of a
  round (cohort training, stale-cache scatter, SAA weights + aggregation,
  server apply) runs as ONE jitted dispatch per round with donated
  parameter/cache buffers (``repro.sim.pipeline``); straggler updates live
  in a device-resident slot cache (``repro.core.stale_cache``), local
  batches are gathered in-program from a device copy of the dataset, and
  the only per-round device->host traffic is the stat-utility vector
  (when a ``needs_feedback`` selector — Oort, UCB, contribution — is
  configured; see ``repro.selection``) plus accuracy/loss every
  ``eval_every`` rounds.  ``SimConfig.shard_participants`` additionally
  splits the packed cohort rows over a participant device-mesh axis
  (``repro.sim.participant_sharding``) for 10k+ learner cohorts — the
  dataset/trace tensors are replicated across the mesh (each shard
  gathers its own rows' batches in-program) and per-round results stay
  bit-identical to the unsharded pipeline;

  flat fast path (``fused_rounds=False``) — the per-stage flat path: flat
  (n, D) fp32 update rows from the compiled cohort program
  (``flat_cohort_step``) through a host-side stale cache to the compiled
  aggregation and flat server step, with one device->host delta copy per
  round; kept as the stage-by-stage parity baseline;

  legacy path (``fast_path=False``) — the original per-learner scalar loops
  and pytree shuffling, kept as the seed-parity/benchmark baseline.

All paths share the struct-of-arrays ``TraceBank``/``ForecasterBank``
availability substrate (fast paths) and the same host-side round logic.

The round loop is decomposed into ``_begin_round`` (host: availability,
selection, batch sampling), ``_schedule_round`` (host: arrival schedule,
fresh/straggler split, stale-cache landings — all decidable *before*
training, which is what lets the fused pipeline dispatch one program per
round), the device stage(s), and ``_record_round`` (host bookkeeping +
optional eval).  ``run()`` chains them for one simulation;
``repro.sweeps.runner`` drives many Simulators through the same methods in
lockstep, batching the device stages across the sweep axis — the host logic
is shared code, so batched cells are bit-identical to serial runs of the
same config/seed.  ``target_accuracy`` arms accuracy-target early stop:
the run ends at the first evaluated round whose accuracy reaches the
target (checked only on ``eval_every`` boundaries, so serial, flat and
batched executions stop at the identical round).

Seed-determined world state (dataset, shards, device profiles, availability
traces, warmed forecasters, initial model) is factored into ``Substrate`` so
a sweep's shared-seed cells build it once and every policy sees identical
traces (matched-condition comparisons, Soltani et al. 2022).
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.core.aggregation import (fedavg_apply, stale_synchronous_aggregate,
                                    stale_synchronous_aggregate_flat,
                                    unflatten_update, yogi_apply,
                                    yogi_apply_flat, yogi_init, yogi_init_flat)
from repro.core.apt import AdaptiveParticipantTarget
from repro.core.availability import AvailabilityForecaster, ForecasterBank
from repro.selection import (SELECTOR_TABLE, build_selector,
                             normalize_selector_params)
from repro.faults.attacks import attack_key
from repro.robust.aggregators import robust_host_aggregate, robust_key
from repro.sim import devices as dev
from repro.sim import learner as ln
from repro.sim import partition as part
from repro.sim import traces as tr
from repro.sim.metrics import Accounting, RoundRecord
from repro.telemetry.trace import capture_span

HOUR = 3600.0


# ---------------------------------------------------------------------------
# Pure flat-update round programs (vmappable: repro.sweeps stacks them)
# ---------------------------------------------------------------------------


def flat_cohort_step(flat_params, bx, by, *, spec, lr, prox_mu,
                     loss=ln._xent):
    """One round of local training as a pure function of the flat model.

    flat_params: (D,) fp32 in ``spec`` leaf order; bx: (m, steps, batch, ...);
    by: (m, steps, batch, ...).  Returns ((m, D) flat deltas, (m,) losses,
    (m,) Oort l2 stats).  ``loss`` is the model's objective from the model
    table (the default is the MLP's).  Rows are independent under vmap, so
    padding rows never perturb real rows, and the whole step can be vmapped
    along a leading sweep axis (or packed as per-row parameters) with
    bit-identical per-row results — the property ``repro.sweeps.runner``
    builds on.
    """
    step = functools.partial(ln.local_train_flat, spec=spec, lr=lr,
                             prox_mu=prox_mu, loss=loss)
    return jax.vmap(step, in_axes=(None, 0, 0))(flat_params, bx, by)


@functools.lru_cache(maxsize=8)
def _cohort_step_fn(spec, lr, prox_mu, loss=ln._xent):
    """Jitted ``flat_cohort_step``, cached per (spec, lr, prox_mu, loss) so
    every Simulator with the same model/hyperparameters shares one
    program (``repro.learners.build_model`` hands out stable function
    objects, so the loss is cache-key-safe)."""
    return jax.jit(functools.partial(flat_cohort_step, spec=spec, lr=lr,
                                     prox_mu=prox_mu, loss=loss))


@functools.lru_cache(maxsize=2)
def _flat_apply_fn():
    """FedAvg server step on the flat vector: x <- x + lr * Delta."""
    return jax.jit(lambda flat, delta, lr: flat + lr * delta)


@functools.lru_cache(maxsize=2)
def _yogi_flat_fn():
    return jax.jit(yogi_apply_flat)


@functools.lru_cache(maxsize=8)
def _flat_eval_fn(spec, evaluate=ln.evaluate):
    return jax.jit(lambda flat, x, y: evaluate(unflatten_update(flat, spec),
                                               x, y))


@functools.lru_cache(maxsize=8)
def _unflatten_fn(spec):
    return jax.jit(lambda flat: unflatten_update(flat, spec))


@dataclasses.dataclass
class SimConfig:
    benchmark: str = "speech"
    mapping: str = "uniform"          # uniform | fedscale | label_{balanced,uniform,zipf}
    n_learners: int = 200
    rounds: int = 200
    selector: str = "random"          # any repro.selection strategy: random |
                                      # oort | priority | safa | flips | ucb |
                                      # contribution (+ registered plugins)
    selector_params: tuple = ()       # ((knob, value), ...) strategy knobs —
                                      # validated against the SelectorSpec,
                                      # folded into selector_key/pipeline_key
    server_opt: str = "fedavg"        # fedavg | yogi server optimizer (named
                                      # `aggregator` before PR 8; old configs
                                      # migrate in __post_init__)
    aggregator: str = "saa"           # robust aggregation strategy: saa |
                                      # coord_median | trimmed_mean | krum |
                                      # multi_krum | norm_median_clip
                                      # (repro.robust; saa = plain weighted
                                      # path, the default and parity baseline)
    trim_k: int = 1                   # trimmed_mean: rows trimmed per tail,
                                      # per coordinate (0 = statically saa)
    krum_f: int = 0                   # krum/multi_krum byzantine allowance f
    multi_krum_m: Optional[int] = None  # multi_krum survivors (None = c - f)
    attack: str = "none"              # coordinated attack: none |
                                      # collude_signflip | collude_same_value
                                      # | alie | adaptive (repro.faults.attacks;
                                      # auto-attaches an AttackSpec to the
                                      # fault plan)
    attack_frac: float = 0.25         # attacker fraction of the population
    attack_scale: float = 10.0        # attack magnitude knob
    attack_z: float = 1.5             # alie sigma multiplier
    scaling_rule: str = "relay"       # equal | dynsgd | adasgd | relay
    beta: float = 0.35                # Eq. 2 averaging weight
    saa: bool = False                 # accept stale updates
    staleness_threshold: Optional[int] = None   # None = unbounded (RELAY default)
    setting: str = "OC"               # OC | DL
    deadline: float = 100.0           # DL reporting deadline (seconds)
    n_target: int = 10
    overcommit: float = 1.3           # OC over-commit factor
    safa_target_ratio: float = 0.1    # SAFA round-end fraction
    apt: bool = False
    dynamic_availability: bool = True
    hardware_scenario: str = "HS1"
    local_steps: int = 5
    local_batch: int = 16
    local_lr: float = 0.05
    prox_mu: float = 0.0              # FedProx proximal term (0 = plain FedAvg)
    server_lr: float = 1.0
    model_mbits: float = 50.0         # update size on the wire
    eval_every: int = 10
    selection_window: float = 5.0
    seed: int = 0
    use_agg_kernel: bool = False      # route aggregation through the Pallas kernel
    fast_path: bool = True            # flat (n, D) updates + TraceBank/ForecasterBank
    fused_rounds: bool = True         # single-dispatch device-resident round pipeline
    target_accuracy: Optional[float] = None   # accuracy-target early stop (eval rounds)
    stale_cache_capacity: int = 64    # initial device stale-cache slots (grows 2x)
    rounds_per_dispatch: int = 1      # K rounds per device dispatch (lax.scan chunk);
                                      # host decisions are prescheduled K ahead, chunks
                                      # break at eval rounds; bit-identical to K=1
    shard_participants: int = 0       # shard the packed cohort rows over a device
                                      # mesh axis "p": 0 = off, N = N shards (clamped
                                      # to the local device count), True = all local
                                      # devices.  Fused pipeline only; bit-identical
                                      # to the unsharded run (one psum per round)
    guard: bool = False               # screen update rows before aggregation
                                      # (non-finite reject + optional norm rules);
                                      # with no faults injected, guarded runs are
                                      # bit-identical to unguarded ones
    guard_clip: Optional[float] = None         # L2 clip for surviving rows
    guard_reject_mult: Optional[float] = None  # reject rows whose sq-norm exceeds
                                               # mult^2 x median surviving sq-norm
    quorum: int = 1                   # min surviving rows for a server apply;
                                      # below it the round's apply is skipped
                                      # (params carried unchanged)
    telemetry: int = 0                # 0 = off (program bit-identical to a
                                      # telemetry-free build), 1 = host-side
                                      # spans + metrics registry, 2 = also
                                      # the in-program round-stats lane +
                                      # per-round JSONL events.  Static in
                                      # pipeline_key (program structure)
    model: str = "mlp"                # learner model: any repro.learners
                                      # strategy — mlp | transformer | moe |
                                      # rwkv6 (+ registered plugins); folded
                                      # into pipeline_key and substrate_key
    model_params: tuple = ()          # ((knob, value), ...) model knobs —
                                      # validated against the ModelSpec

    def __post_init__(self):
        # pre-PR-8 configs (and their snapshots) used `aggregator` for the
        # server optimizer; migrate so old dicts keep working
        if self.aggregator in ("fedavg", "yogi"):
            self.server_opt = self.aggregator
            self.aggregator = "saa"
        from repro.faults.attacks import ATTACK_KINDS
        from repro.robust import ROBUST_AGGREGATORS
        if self.selector not in SELECTOR_TABLE:
            raise ValueError(f"unknown selector {self.selector!r} "
                             f"(choose from {tuple(SELECTOR_TABLE)})")
        # canonical sorted-tuple form: hashable (pipeline_key), picklable
        # (checkpoints), and knob-validated at config time
        self.selector_params = normalize_selector_params(
            self.selector, self.selector_params)
        if self.aggregator not in ROBUST_AGGREGATORS:
            raise ValueError(f"unknown aggregator {self.aggregator!r} "
                             f"(choose from {ROBUST_AGGREGATORS})")
        if self.attack not in ATTACK_KINDS:
            raise ValueError(f"unknown attack {self.attack!r} "
                             f"(choose from {ATTACK_KINDS})")
        # model-table validation (lazy import: repro.learners imports the
        # sim package for the MLP wrapper, so engine must not import it at
        # module level)
        from repro.learners import MODEL_TABLE, normalize_model_params
        if self.model not in MODEL_TABLE:
            raise ValueError(f"unknown model {self.model!r} "
                             f"(choose from {tuple(MODEL_TABLE)})")
        self.model_params = normalize_model_params(self.model,
                                                   self.model_params)
        if self.model != "mlp" and not self.fast_path:
            raise ValueError(
                f"model {self.model!r} requires the flat fast path "
                "(fast_path=True) — the legacy pytree round loop is "
                "MLP-only")


def substrate_key(cfg: SimConfig) -> tuple:
    """The config fields that determine the seed-built world state."""
    return (cfg.benchmark, cfg.mapping, cfg.n_learners, cfg.seed,
            cfg.dynamic_availability, cfg.model,
            tuple(cfg.model_params or ()))


@dataclasses.dataclass
class Substrate:
    """Everything the config seed determines before the first round.

    Built with the exact RNG draw order of the original Simulator
    constructor (dataset, partition, profiles, traces), then the generator
    state is captured so a Simulator resuming from a cached Substrate
    consumes the identical stream the uncached constructor would — sweep
    cells sharing a substrate are bit-identical to standalone runs.

    Device profiles are stored as the HS1 base population; hardware
    scenarios are pure transforms applied per Simulator
    (``devices.apply_hardware_scenario``), so the hardware axis of a sweep
    shares one substrate too.
    """
    key: tuple
    data: part.FederatedDataset
    base_profiles: list
    traces: list
    trace_bank: tr.TraceBank
    rng_state: dict
    params0: dict                      # initial model pytree (read-only, shared)
    flat_params0: np.ndarray           # same model, flat fp32 (D,)
    flat_spec: tuple
    meta: object = None                # repro.learners.DataMeta of the dataset
    model_fns: object = None           # repro.learners.ModelFns (init/loss/eval)
    _warmed: Optional[tuple] = None    # lazily-built fast-path forecaster warmup

    @staticmethod
    def build(cfg: SimConfig) -> "Substrate":
        from repro.learners import DataMeta, build_model
        rng = np.random.default_rng(cfg.seed)
        if part.benchmark_kind(cfg.benchmark) == "tokens":
            # token benchmarks carry their own shard structure; profiles and
            # traces still consume this generator, in the same order the
            # classifier branch draws them
            data = part.make_token_dataset(cfg.benchmark, cfg.n_learners,
                                           cfg.seed)
            meta = DataMeta(kind="tokens", vocab=data.vocab,
                            seq_len=int(data.x_train.shape[1]))
        else:
            x_tr, y_tr, x_te, y_te = part.make_dataset(cfg.benchmark, rng)
            shards = part.partition(y_tr, cfg.n_learners, cfg.mapping, rng)
            data = part.FederatedDataset(cfg.benchmark, x_tr, y_tr, x_te,
                                         y_te, shards)
            meta = DataMeta(kind="classifier",
                            feature_dim=int(x_tr.shape[1]),
                            n_classes=data.n_classes)
        base_profiles = dev.sample_profiles(cfg.n_learners, rng)   # HS1 base
        traces = tr.make_traces(cfg.n_learners, rng,
                                dynamic=cfg.dynamic_availability)
        model_fns = build_model(cfg.model, tuple(cfg.model_params), meta)
        params0 = model_fns.init(jax.random.PRNGKey(cfg.seed))
        flat_spec = agg.make_flat_spec(params0)
        flat0, _ = agg.flatten_update(params0)
        return Substrate(key=substrate_key(cfg), data=data,
                         base_profiles=base_profiles, traces=traces,
                         trace_bank=tr.TraceBank(traces),
                         rng_state=rng.bit_generator.state,
                         params0=params0, flat_params0=np.asarray(flat0),
                         flat_spec=flat_spec, meta=meta, model_fns=model_fns)

    def warmed_fbank(self) -> tuple:
        """Pre-deployment forecaster history (paper App. A step 2), computed
        once per substrate; returns (counts, avail_counts, recent) arrays
        that each Simulator copies into its own ForecasterBank."""
        if self._warmed is None:
            fb = ForecasterBank(len(self.traces))
            for tt in np.arange(0, 3 * 24 * HOUR, 1800.0):
                fb.observe_all(tt, self.trace_bank.available_all(tt))
            self._warmed = (fb.counts, fb.avail_counts, fb.recent)
        return self._warmed


@dataclasses.dataclass
class _InFlight:
    learner_id: int
    origin_round: int
    arrival: float
    duration: float
    delta: object                     # device-cache slot id (fused), flat (D,)
    stat_util: float                  # fp32 row (flat) or pytree (legacy)


@dataclasses.dataclass
class RoundPlan:
    """Host-side output of ``_begin_round``: everything the device stage
    needs for one round's cohort training.  The fused pipeline carries only
    sample *indices* (``bidx``) and gathers the batches in-program; the
    per-stage paths materialize ``bx``/``by`` on host.  Both consume the
    identical RNG draws, so the sampled batches match bit-for-bit."""
    t_now: float
    chosen: list
    n_t: int
    k: int                            # cohort size
    bx: Optional[np.ndarray]          # (k, steps, batch, dim) local batches
    by: Optional[np.ndarray]          # (k, steps, batch)
    durs: np.ndarray                  # (k,)
    drop_at: np.ndarray               # (k,) mid-round dropout offsets (inf = none)
    bidx: Optional[np.ndarray] = None  # (k, steps*batch) sample indices (fused)


@dataclasses.dataclass
class RoundSchedule:
    """Host-side round outcome, decided *before* the device dispatch.

    Everything here depends only on the plan (durations, dropouts, arrival
    order) and the stale-cache metadata — never on the update values — so
    the fused pipeline can build its gather/scatter index arrays and launch
    one program for train + cache + aggregate + apply.  Entries removed from
    ``Simulator.stale_cache`` (``landing``/``expired``) are returned so the
    caller can free their device slots or collect their host rows."""
    t_end: float
    fresh_rows: list                  # plan-row indices aggregated fresh, arrival order
    new_stale: list                   # (row, lid, arrival, duration) entering the cache
    landing: list                     # _InFlight entries landing this round, cache order
    landing_taus: list                # their staleness (rounds)
    expired: list                     # over-threshold entries (removed, marked wasted)
    feedback: list                    # (lid, row, duration) selector feedback, arrival order
    slots: list = dataclasses.field(default_factory=list)  # set by the pipeline


class Simulator:
    def __init__(self, cfg: SimConfig, substrate: Optional[Substrate] = None,
                 fault_plan=None):
        # no telemetry session exists yet: the span's bounds wait for the
        # run's session (``_hand_over_build``), and a running profiler
        # capture is annotated directly
        t0 = time.perf_counter_ns()
        with capture_span("build"):
            self._build(cfg, substrate, fault_plan)
        self._build_ns = (t0, time.perf_counter_ns())

    def _hand_over_build(self, telemetry) -> None:
        """Record this Simulator's construction as a ``build`` span of the
        session that runs it (once)."""
        if self._build_ns is not None:
            telemetry.complete("build", *self._build_ns)
            self._build_ns = None

    def _build(self, cfg: SimConfig, substrate: Optional[Substrate],
               fault_plan) -> None:
        self.cfg = cfg
        self.fault_plan = fault_plan  # repro.faults.FaultPlan or None
        if cfg.attack != "none" and cfg.attack_frac > 0:
            # auto-attach the coordinated attack to the fault plan; a
            # restored plan already carries one (resume-safe), and the
            # attacker stream is independent of the fault draws, so two
            # cells differing only in aggregator share identical attacks
            from repro.faults import AttackSpec, FaultPlan
            plan = self.fault_plan
            if plan is None:
                plan = FaultPlan(cfg.n_learners, cfg.rounds, specs=(),
                                 seed=cfg.seed)
            if getattr(plan, "attack", None) is None:
                plan = plan.with_attack(AttackSpec(
                    cfg.attack, cfg.attack_frac, cfg.attack_scale,
                    cfg.attack_z))
            self.fault_plan = plan
        if substrate is None:
            substrate = Substrate.build(cfg)
        else:
            assert substrate.key == substrate_key(cfg), \
                "substrate built for a different config family"
        self.substrate = substrate
        self.rng = np.random.default_rng(cfg.seed)
        self.rng.bit_generator.state = substrate.rng_state
        self.data = substrate.data
        self.profiles = dev.apply_hardware_scenario(substrate.base_profiles,
                                                    cfg.hardware_scenario)
        self.traces = substrate.traces
        # per-learner round duration is config-determined: compute it once
        self.durations = np.array([
            p.round_duration(cfg.local_steps * cfg.local_batch, 1, cfg.model_mbits)
            for p in self.profiles])
        if cfg.fast_path:
            self.trace_bank = substrate.trace_bank
            self.fbank = ForecasterBank(cfg.n_learners)
            self.forecasters = None
        else:
            self.trace_bank = None
            self.fbank = None
            self.forecasters = [AvailabilityForecaster() for _ in range(cfg.n_learners)]
        self._warmup_forecasters()
        # strategy-table build: the spec's static flags drive the engine's
        # scheduling rules, the factory gets the build-time world state
        # (FLIPS clusters the substrate's label shards here)
        self._sel_spec = SELECTOR_TABLE[cfg.selector]
        self.selector = build_selector(cfg, substrate=substrate,
                                       durations=self.durations)
        self.apt = AdaptiveParticipantTarget(n0=cfg.n_target) if cfg.apt else None
        self.params = substrate.params0
        self._flat_spec = substrate.flat_spec
        self._model_fns = substrate.model_fns  # ModelFns(init, loss, evaluate)
        if cfg.fast_path:
            self.flat_params = jnp.asarray(substrate.flat_params0)
            self.flat_opt_state = (yogi_init_flat(len(substrate.flat_params0))
                                   if cfg.server_opt == "yogi" else None)
            self.opt_state = None
        else:
            self.flat_params = None
            self.flat_opt_state = None
            self.opt_state = yogi_init(self.params) if cfg.server_opt == "yogi" else None
        self.acct = Accounting()
        self.stale_cache: list[_InFlight] = []
        self.busy_until = np.zeros(cfg.n_learners)  # device busy training/uploading
        self.mu = cfg.deadline  # initial round-duration estimate
        self._t_now = 0.0
        self.n_checked_in = 0   # learners checked in at the last _begin_round

    # ------------------------------------------------------------------
    def _warmup_forecasters(self):
        """Learners have pre-deployment local history (paper App. A step 2)."""
        if self.cfg.fast_path:
            counts, avail_counts, recent = self.substrate.warmed_fbank()
            self.fbank.counts = counts.copy()
            self.fbank.avail_counts = avail_counts.copy()
            self.fbank.recent = recent.copy()
            return
        ts = np.arange(0, 3 * 24 * HOUR, 1800.0)
        for lid, (f, t) in enumerate(zip(self.forecasters, self.traces)):
            for tt in ts:
                f.observe(tt, t.available(tt))

    def _available_now(self, t_now: float):
        """Idle + available learner ids (ascending), forecasters updated."""
        if self.cfg.fast_path:
            mask = self.trace_bank.available_all(t_now) & (self.busy_until <= t_now)
            available = np.nonzero(mask)[0]
            if len(available):                  # devices log their own state
                self.fbank.observe_batch(available, t_now, 1.0)
            return available
        available = [lid for lid in range(self.cfg.n_learners)
                     if self.traces[lid].available(t_now)
                     and self.busy_until[lid] <= t_now]
        for lid in available:
            self.forecasters[lid].observe(t_now, True)
        return available

    def _views(self, t_now: float, available_ids):
        """The check-in as a view selector reads it: ids (ascending),
        forecast P(available in [t+mu, t+2mu]) and round durations."""
        ids = np.asarray(available_ids, np.int64)
        t0, t1 = t_now + self.mu, t_now + 2 * self.mu
        if self.cfg.fast_path:
            probs = self.fbank.predict_window_batch(ids, t0, t1)
        else:
            probs = np.array([self.forecasters[lid].predict_window(t0, t1)
                              for lid in available_ids], np.float64)
        return ids, probs, self.durations[ids]

    # ------------------------------------------------------------------
    # Round stages (run() chains them; repro.sweeps.runner drives them in
    # lockstep across many Simulators with batched device stages)
    # ------------------------------------------------------------------

    def eval_due(self, r: int) -> bool:
        return (r + 1) % self.cfg.eval_every == 0 or r == self.cfg.rounds - 1

    def _begin_round(self, r: int) -> Optional[RoundPlan]:
        """Host pre-step: advance time, census availability, pick the cohort,
        sample its local batches.  Returns None when the round is skipped
        (nobody available / nobody selected)."""
        cfg = self.cfg
        self._t_now += cfg.selection_window
        t_now = self._t_now
        available = self._available_now(t_now)
        self.n_checked_in = len(available)     # the census handed to selection
        if not len(available):
            self._t_now += 60.0
            return None

        n_t = cfg.n_target
        if self.apt is not None:
            rts = [f.arrival - t_now for f in self.stale_cache
                   if f.arrival > t_now]
            n_t = self.apt.target(rts)
        n_sel = (int(np.ceil(n_t * cfg.overcommit))
                 if cfg.setting == "OC" else n_t)
        if self.selector.needs_views:
            chosen = self.selector.select_arrays(
                r, *self._views(t_now, available), n_sel, self.rng)
        else:
            # view-free selectors (random, safa) skip the forecaster window
            # queries — pure reads, so state and RNG streams are untouched
            chosen = self.selector.select_ids(r, available, n_sel, self.rng)
        if not chosen:
            self._t_now += 60.0
            return None
        return self._build_plan(chosen, t_now, n_t)

    def _build_plan(self, chosen, t_now, n_t) -> RoundPlan:
        cfg = self.cfg
        fused = cfg.fast_path and cfg.fused_rounds
        takes, xs, ys = [], [], []
        for lid in chosen:
            if fused:
                # indices only; the pipeline gathers the rows in-program
                takes.append(ln.sample_batch_indices(
                    self.data.shards[lid], cfg.local_steps, cfg.local_batch,
                    self.rng))
            else:
                bx, by = ln.sample_local_batches(
                    self.data.shards[lid], self.data.x_train,
                    self.data.y_train, cfg.local_steps, cfg.local_batch,
                    self.rng)
                xs.append(bx)
                ys.append(by)
        durs = self.durations[np.asarray(chosen)]
        k = len(chosen)
        if cfg.fast_path:
            nus = self.trace_bank.next_unavailable_after_batch(chosen, t_now)
            rel = nus - t_now
            drop_at = np.where(rel < durs, rel, np.inf)
        else:
            drop_at = []
            for lid, d in zip(chosen, durs):
                nu = self.traces[lid].next_unavailable_after(t_now)
                drop_at.append(nu - t_now if nu - t_now < d else np.inf)
            drop_at = np.array(drop_at)
        if fused:
            return RoundPlan(t_now, list(chosen), n_t, k, None, None, durs,
                             drop_at, bidx=np.asarray(takes, np.int32))
        return RoundPlan(t_now, list(chosen), n_t, k, np.stack(xs),
                         np.stack(ys), durs, drop_at)

    def _train(self, plan: RoundPlan):
        """Device stage: the cohort's local training (simulated durations,
        real gradients).  Fast path returns flat (k, D) fp32 host rows."""
        cfg = self.cfg
        if cfg.fast_path:
            # pad the cohort to a power-of-two bucket: one compiled program per
            # bucket instead of per distinct cohort size (rows independent
            # under vmap, so real rows are bit-identical; padding discarded).
            # Serial-only: the sweep runner packs unpadded plan rows itself.
            k, m = plan.k, agg.bucket_pow2(plan.k)
            bx = np.concatenate([plan.bx,
                                 np.broadcast_to(plan.bx[:1],
                                                 (m - k,) + plan.bx.shape[1:])])
            by = np.concatenate([plan.by,
                                 np.broadcast_to(plan.by[:1],
                                                 (m - k,) + plan.by.shape[1:])])
            step = _cohort_step_fn(self._flat_spec, cfg.local_lr, cfg.prox_mu,
                                   self._model_fns.loss)
            deltas, losses, l2s = step(self.flat_params, bx, by)
            # one device->host copy per round
            return np.asarray(deltas)[:k], np.asarray(losses)[:k], np.asarray(l2s)[:k]
        deltas, losses, l2s = ln.local_train_cohort(
            self.params, plan.bx, plan.by, cfg.local_lr, cfg.prox_mu)
        return deltas, np.asarray(losses), np.asarray(l2s)

    def _schedule_round(self, r: int, plan: RoundPlan) -> RoundSchedule:
        """Host post-plan step, decided *before* training: arrival schedule,
        round end time, fresh/straggler split, stale-cache landings, resource
        accounting.  None of it reads the update values, so the fused
        pipeline runs it first and dispatches one device program for the
        whole round.  Accounting/bookkeeping mutations happen here in the
        same order the pre-refactor ``_collect_updates`` performed them
        (float accumulation order is part of the parity contract)."""
        cfg = self.cfg
        t_now, chosen, durs, drop_at = plan.t_now, plan.chosen, plan.durs, plan.drop_at
        n_t = plan.n_t

        fp = self.fault_plan
        arrivals = []   # (arrival_time, idx into chosen) for non-dropouts
        for i, lid in enumerate(chosen):
            if np.isfinite(drop_at[i]):
                # device went away mid-round: partial work, always wasted
                self.acct.charge(float(drop_at[i]), wasted=True)
                self.busy_until[lid] = t_now + float(drop_at[i])
            elif fp is not None and fp.post_drop(r, lid):
                # injected fault: the learner finishes training but the
                # result is lost before upload — full duration charged and
                # wasted (paper §3), no arrival, no selector feedback
                self.acct.charge(float(durs[i]), wasted=True)
                self.busy_until[lid] = t_now + float(durs[i])
            else:
                arrivals.append((t_now + durs[i], i))
                self.acct.charge(float(durs[i]), wasted=False)
                self.busy_until[lid] = t_now + float(durs[i])
        arrivals.sort()

        # --- round end time ---------------------------------------
        if self._sel_spec.select_all:
            need = max(1, int(np.ceil(cfg.safa_target_ratio * len(chosen))))
            t_end = (arrivals[need - 1][0] if len(arrivals) >= need
                     else t_now + cfg.deadline)
            t_end = min(t_end, t_now + cfg.deadline)
        elif cfg.setting == "OC":
            t_end = (arrivals[n_t - 1][0] if len(arrivals) >= n_t
                     else (arrivals[-1][0] if arrivals else t_now + cfg.deadline))
        else:  # DL
            t_end = t_now + cfg.deadline

        # --- split fresh / straggler ------------------------------
        fresh_rows, new_stale, feedback = [], [], []
        for (arr, i) in arrivals:
            lid = chosen[i]
            feedback.append((lid, i, durs[i]))
            if arr <= t_end and (cfg.setting == "DL"
                                 or self._sel_spec.select_all
                                 or len(fresh_rows) < n_t):
                fresh_rows.append(i)
                self.acct.unique.add(lid)
            elif cfg.saa:
                new_stale.append((i, lid, arr, durs[i]))
            else:
                # already charged as used at dispatch; never aggregated
                self.acct.mark_wasted(float(durs[i]))

        # --- stale updates landing this round ---------------------
        landing, landing_taus, expired = [], [], []
        still_waiting = []
        for f in self.stale_cache:
            if f.arrival <= t_end:
                tau = r - f.origin_round
                if (cfg.staleness_threshold is None
                        or tau <= cfg.staleness_threshold):
                    landing.append(f)
                    landing_taus.append(tau)
                    self.acct.unique.add(f.learner_id)
                    if fp is not None and fp.replay(r, f.learner_id):
                        # injected fault: the same stale delivery lands
                        # twice — a duplicate row in the aggregation operand
                        landing.append(f)
                        landing_taus.append(tau)
                else:
                    expired.append(f)
                    self.acct.mark_wasted(f.duration)
            else:
                still_waiting.append(f)
        self.stale_cache = still_waiting
        return RoundSchedule(t_end, fresh_rows, new_stale, landing,
                             landing_taus, expired, feedback)

    def _apply_feedback(self, r: int, sched: RoundSchedule, l2s) -> None:
        """Selector feedback for every arrival, in arrival order.  ``l2s``
        holds the per-row loss stats consumed by ``needs_feedback``
        selectors (Oort, UCB, contribution); None when the fused pipeline
        skipped the fetch, in which case stat_util is reported as 0."""
        cfg = self.cfg
        for (lid, i, dur) in sched.feedback:
            stat_util = (float(cfg.local_steps * cfg.local_batch * l2s[i])
                         if l2s is not None else 0.0)
            self.selector.update_feedback(lid, stat_util=stat_util,
                                          duration=dur, round_idx=r)

    def _stat_util(self, row: int, l2s) -> float:
        return (float(self.cfg.local_steps * self.cfg.local_batch * l2s[row])
                if l2s is not None else 0.0)

    def _collect_updates(self, r: int, plan: RoundPlan, deltas, losses, l2s):
        """Host post-step for the per-stage paths: schedule the round, apply
        selector feedback, then materialize the scheduled rows from the
        round's update values.  Returns (t_end, fresh_updates, stale_updates,
        stale_taus, agg_lids) where ``agg_lids`` are the learner ids behind
        each aggregation-operand row, fresh first then landing stale (the
        attack paths map them to the round's attacker set)."""
        cfg = self.cfg
        sched = self._schedule_round(r, plan)
        self._apply_feedback(r, sched, l2s)

        def row(i):
            return (deltas[i] if cfg.fast_path
                    else jax.tree.map(lambda d: d[i], deltas))

        fresh_updates = [row(i) for i in sched.fresh_rows]
        for (i, lid, arr, dur) in sched.new_stale:
            delta_i = row(i)
            if cfg.fast_path:
                # copy: delta_i is a view into the round's padded (m, D)
                # cohort buffer; caching the view would pin the whole
                # buffer for the straggler's lifetime
                delta_i = np.array(delta_i)
            self.stale_cache.append(_InFlight(lid, r, arr, dur, delta_i,
                                              self._stat_util(i, l2s)))
        stale_updates = [f.delta for f in sched.landing]
        agg_lids = ([int(plan.chosen[i]) for i in sched.fresh_rows]
                    + [f.learner_id for f in sched.landing])
        return (sched.t_end, fresh_updates, stale_updates,
                sched.landing_taus, agg_lids)

    def _corrupt_deltas(self, r: int, plan: RoundPlan, deltas):
        """Apply the fault plan's per-row update corruption (chaos harness).

        A pure fp32 multiply after local training and before caching /
        aggregation — the identical IEEE operation the fused pipeline folds
        into its round program, so faulted runs stay parity-comparable
        across substrates.  Losses and Oort stats are computed pre-fault
        everywhere (corruption models the uplink, not the training)."""
        fp = self.fault_plan
        if fp is None or not fp.has_corruption:
            return deltas
        fscale = fp.scale_for(r, plan.chosen)
        if self.cfg.fast_path:
            return np.asarray(deltas) * fscale[:, None]
        k = len(plan.chosen)
        return jax.tree.map(
            lambda d: d * jnp.asarray(fscale).reshape((k,) + (1,) * (d.ndim - 1)),
            deltas)

    def _aggregate(self, r, agg_lids, fresh_updates, stale_updates,
                   stale_taus):
        """Returns the aggregated delta, or None when the guard's quorum
        check rejects the round (caller carries params unchanged)."""
        cfg = self.cfg
        fresh_mask = [True] * len(fresh_updates) + [False] * len(stale_updates)
        taus = [0] * len(fresh_updates) + stale_taus
        atk = attack_key(cfg)
        rob = robust_key(cfg)
        if atk is not None or rob is not None:
            # attacked / robust route: one shared composition program
            # (attack -> guard screen -> robust strategy -> SAA weights),
            # the same per-cell numerics the fused pipeline and the batched
            # sweep executor run.  Legacy trees flatten exactly as the
            # guarded path does.
            if cfg.fast_path:
                stacked = np.stack(fresh_updates + stale_updates)
                spec = None
            else:
                flats, spec = [], None
                for t in fresh_updates + stale_updates:
                    f, spec = agg.flatten_update(t)
                    flats.append(f)
                stacked = jnp.stack(flats)
            att = (self.fault_plan.attack_flags(r, agg_lids)
                   if atk is not None else np.zeros(len(fresh_mask), bool))
            guard_desc = ((cfg.guard_clip, cfg.guard_reject_mult)
                          if cfg.guard else None)
            agg_out, info = robust_host_aggregate(
                stacked, fresh_mask, taus, att, attack=atk, guard=guard_desc,
                robust=rob, use_kernel=cfg.use_agg_kernel, beta=cfg.beta,
                rule=cfg.scaling_rule, quorum=cfg.quorum,
                bucketed=cfg.fast_path)
            if cfg.guard:
                self.acct.note_guard(info["nonfinite"], info["norm"],
                                     info["applied"])
            if rob is not None:
                self.acct.note_robust(info["robust_rejected"],
                                      info["robust_trimmed"])
            if not info["applied"]:
                return None
            return agg_out if spec is None else unflatten_update(agg_out,
                                                                 spec)
        if not cfg.guard:
            if cfg.fast_path:
                stacked = np.stack(fresh_updates + stale_updates)
                agg_flat, _ = stale_synchronous_aggregate_flat(
                    stacked, fresh_mask, taus, rule=cfg.scaling_rule,
                    beta=cfg.beta, use_kernel=cfg.use_agg_kernel)
                return agg_flat
            agg_tree, _ = stale_synchronous_aggregate(
                fresh_updates + stale_updates, fresh_mask, taus,
                rule=cfg.scaling_rule, beta=cfg.beta,
                use_kernel=cfg.use_agg_kernel,
                compiled=False)  # seed-exact eager baseline
            return agg_tree
        # guarded route: one shared screening + masked-aggregation program.
        # Legacy trees are flattened exactly as the unguarded tree path
        # does, so the clean (nothing-rejected) case routes through the
        # identical unguarded computation bit-for-bit.
        if cfg.fast_path:
            stacked = np.stack(fresh_updates + stale_updates)
            spec = None
        else:
            flats, spec = [], None
            for t in fresh_updates + stale_updates:
                f, spec = agg.flatten_update(t)
                flats.append(f)
            stacked = jnp.stack(flats)
        agg_out, _, info = agg.guarded_aggregate_flat(
            stacked, fresh_mask, taus, rule=cfg.scaling_rule, beta=cfg.beta,
            use_kernel=cfg.use_agg_kernel, compiled=cfg.fast_path,
            clip=cfg.guard_clip, reject_mult=cfg.guard_reject_mult,
            quorum=cfg.quorum)
        self.acct.note_guard(info["nonfinite"], info["norm"], info["applied"])
        if not info["applied"]:
            return None
        return agg_out if spec is None else unflatten_update(agg_out, spec)

    def _apply_update(self, agg_out):
        """Server optimizer step on the aggregated delta."""
        cfg = self.cfg
        if cfg.fast_path:
            if cfg.server_opt == "yogi":
                self.flat_params, self.flat_opt_state = _yogi_flat_fn()(
                    self.flat_params, agg_out, self.flat_opt_state)
            else:
                self.flat_params = _flat_apply_fn()(self.flat_params, agg_out,
                                                    cfg.server_lr)
        elif cfg.server_opt == "yogi":
            self.params, self.opt_state = yogi_apply(self.params, agg_out,
                                                     self.opt_state)
        else:
            self.params = fedavg_apply(self.params, agg_out, cfg.server_lr)

    def _evaluate(self):
        if self.cfg.fast_path:
            return _flat_eval_fn(self._flat_spec,
                                 self._model_fns.evaluate)(self.flat_params,
                                                           self.data.x_test,
                                                           self.data.y_test)
        return ln.evaluate(self.params, self.data.x_test, self.data.y_test)

    def _advance_round_state(self, r: int, t_start: float, t_end: float,
                             n_selected: int, n_fresh: int, n_stale: int):
        """The host part of ``_record_round`` that the *next* round's
        ``_begin_round`` depends on: round-duration estimate, the appended
        RoundRecord (accuracy NaN until an evaluation fills it), and the
        clock.  The chunked pipeline calls this during prescheduling — K
        rounds ahead of the device dispatch — and fills the eval fields
        afterwards via ``_fill_round_eval``; values are identical to the
        unchunked sequence because nothing here reads update values."""
        duration = t_end - t_start
        self.mu = (self.apt.update_round_duration(duration)
                   if self.apt is not None else
                   0.75 * duration + 0.25 * self.mu)
        rec = RoundRecord(r, t_end, n_selected, n_fresh, n_stale,
                          self.acct.resource_used, self.acct.resource_wasted,
                          len(self.acct.unique))
        self.acct.records.append(rec)
        self._t_now = t_end
        return rec

    def _fill_round_eval(self, rec, acc, loss, progress: bool = False):
        """Write an evaluation's metrics into an already-appended record."""
        rec.accuracy, rec.loss = float(acc), float(loss)
        if progress:
            print(f"  round {rec.round_idx:4d} t={rec.sim_time/60:7.1f}min "
                  f"acc={rec.accuracy:.3f} "
                  f"used={self.acct.resource_used/60:.0f}min "
                  f"wasted={100*self.acct.resource_wasted/max(self.acct.resource_used,1e-9):.0f}%")

    def _record_round(self, r: int, t_start: float, t_end: float,
                      n_selected: int, n_fresh: int, n_stale: int,
                      acc_loss=None, progress: bool = False):
        """Bookkeeping tail of a round: round-duration estimate, RoundRecord,
        optional evaluation (``acc_loss`` supplies precomputed metrics when a
        sweep batch evaluated all cells in one call)."""
        rec = self._advance_round_state(r, t_start, t_end, n_selected,
                                        n_fresh, n_stale)
        if self.eval_due(r):
            acc, loss = self._evaluate() if acc_loss is None else acc_loss
            self._fill_round_eval(rec, acc, loss, progress=progress)
        return rec

    def _target_reached(self) -> bool:
        """Accuracy-target early stop: True once the latest recorded round's
        evaluation reached ``target_accuracy``.  Only eval rounds carry an
        accuracy (NaN otherwise), so every execution mode — serial, flat,
        batched sweep — tests the identical round boundaries and stops at
        the identical round."""
        target = self.cfg.target_accuracy
        if target is None or not self.acct.records:
            return False
        acc = self.acct.records[-1].accuracy
        return acc == acc and acc >= target

    def _finalize(self) -> Accounting:
        # updates still in flight at the end of training are wasted work
        for f in self.stale_cache:
            self.acct.mark_wasted(f.duration)
        if self.cfg.fast_path:
            self.params = _unflatten_fn(self._flat_spec)(self.flat_params)
        return self.acct

    # ------------------------------------------------------------------
    # Snapshot support (chaos harness: crash-safe bit-exact resume)
    # ------------------------------------------------------------------

    def capture_state(self, stale_rows=None):
        """Everything mutable the round loop reads, as plain host objects.

        ``stale_rows`` optionally supplies the stale-cache update rows
        (aligned with ``self.stale_cache``) — the fused pipeline passes the
        gathered device rows, since there ``_InFlight.delta`` is only a
        cache slot id.  The result round-trips through pickle; restoring it
        into a Simulator rebuilt from the same config + substrate resumes
        the identical RNG/selector/accounting streams."""
        cfg = self.cfg
        st = {
            "rng": self.rng.bit_generator.state,
            "selector": copy.deepcopy(self.selector),
            "apt": copy.deepcopy(self.apt),
            "busy_until": self.busy_until.copy(),
            "mu": self.mu,
            "t_now": self._t_now,
            "acct": copy.deepcopy(self.acct),
        }
        if cfg.fast_path:
            st["fbank"] = (self.fbank.counts.copy(),
                           self.fbank.avail_counts.copy(),
                           self.fbank.recent.copy())
        else:
            st["forecasters"] = copy.deepcopy(self.forecasters)
        entries = []
        for idx, f in enumerate(self.stale_cache):
            if stale_rows is not None:
                row = np.asarray(stale_rows[idx])
            elif cfg.fast_path:
                row = np.asarray(f.delta)
            else:
                row = jax.tree.map(np.asarray, f.delta)
            entries.append((f.learner_id, f.origin_round, f.arrival,
                            f.duration, f.stat_util, row))
        st["stale"] = entries
        return st

    def restore_state(self, st):
        """Inverse of ``capture_state``.  Stale entries come back with their
        host rows as ``delta``; a fused-pipeline resume re-seats them into
        the device cache afterwards (``repro.checkpoint.state``)."""
        self.rng.bit_generator.state = st["rng"]
        self.selector = copy.deepcopy(st["selector"])
        self.apt = copy.deepcopy(st["apt"])
        self.busy_until = np.array(st["busy_until"])
        self.mu = st["mu"]
        self._t_now = st["t_now"]
        self.acct = copy.deepcopy(st["acct"])
        if self.cfg.fast_path:
            counts, avail_counts, recent = st["fbank"]
            self.fbank.counts = np.array(counts)
            self.fbank.avail_counts = np.array(avail_counts)
            self.fbank.recent = np.array(recent)
        else:
            self.forecasters = copy.deepcopy(st["forecasters"])
        self.stale_cache = [
            _InFlight(lid, orig, arr, dur, row, su)
            for (lid, orig, arr, dur, su, row) in st["stale"]]

    # ------------------------------------------------------------------
    def run(self, progress: bool = False, *,
            checkpoint_path: Optional[str] = None, checkpoint_every: int = 0,
            telemetry=None):
        if self.cfg.shard_participants and not (self.cfg.fast_path
                                                and self.cfg.fused_rounds):
            raise ValueError(
                "shard_participants requires the fused fast path "
                "(fast_path=True, fused_rounds=True) — the per-stage and "
                "legacy substrates have no device-sharded round program")
        if self.cfg.fast_path and self.cfg.fused_rounds:
            from repro.sim.pipeline import RoundPipeline
            return RoundPipeline([self], progress=progress,
                                 checkpoint_path=checkpoint_path,
                                 checkpoint_every=checkpoint_every,
                                 telemetry=telemetry).run()[0]
        self._t_now = 0.0
        return self._run_loop(0, progress, checkpoint_path, checkpoint_every,
                              telemetry=telemetry)

    def _run_loop(self, start_round: int, progress: bool,
                  checkpoint_path: Optional[str], checkpoint_every: int,
                  telemetry=None):
        """The per-stage/legacy round loop from ``start_round`` — resume
        entry point: a restored Simulator continues here without resetting
        the clock."""
        cfg = self.cfg
        fp = self.fault_plan
        if telemetry is None:
            from repro.telemetry import TelemetrySession
            telemetry = TelemetrySession()
        self._hand_over_build(telemetry)
        for r in range(start_round, cfg.rounds):
            with telemetry.span("schedule", round=r):
                plan = self._begin_round(r)
            if plan is not None:
                with telemetry.span("dispatch", round=r):
                    deltas, losses, l2s = self._train(plan)
                    deltas = self._corrupt_deltas(r, plan, deltas)
                with telemetry.span("fetch", round=r):
                    t_end, fresh_updates, stale_updates, stale_taus, \
                        agg_lids = \
                        self._collect_updates(r, plan, deltas, losses, l2s)
                    if fresh_updates or stale_updates:
                        agg_out = self._aggregate(r, agg_lids, fresh_updates,
                                                  stale_updates, stale_taus)
                        if agg_out is not None:
                            self._apply_update(agg_out)
                with telemetry.span("eval", round=r):
                    self._record_round(r, plan.t_now, t_end,
                                       len(plan.chosen), len(fresh_updates),
                                       len(stale_updates), progress=progress)
                if self._target_reached():
                    self.acct.stopped_early = True
                    break
            if checkpoint_path and checkpoint_every and \
                    (r + 1) % checkpoint_every == 0 and r + 1 < cfg.rounds:
                from repro.checkpoint.state import save_engine_snapshot
                with telemetry.span("checkpoint", round=r + 1):
                    save_engine_snapshot(checkpoint_path, self, r + 1)
            if fp is not None and fp.crash_due(r):
                telemetry.event("crash", round=int(r), mode=fp.crash_mode)
                telemetry.flush()
                fp.trigger_crash(r)
        with telemetry.span("finalize", cells=1):
            return self._finalize()
