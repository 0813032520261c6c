"""Device-resident round pipeline: ONE jitted dispatch per simulation round
(or per K-round chunk), optionally sharded over a sweep-axis device mesh.

``RoundPipeline`` drives S >= 1 Simulators (the serial engine passes
``[self]``; ``repro.sweeps.runner`` passes a compatibility batch) through a
round loop whose entire device side — cohort local training, straggler
scatter into the device stale cache, SAA weights + aggregation, and the
server apply — is one compiled program with **donated** parameter / cache /
optimizer buffers.  Host<->device traffic per round:

  host -> device: the round's index arrays (sample indices, row->cell
      ownership, cache scatter slots, aggregation gather/mask arrays) via
      explicit ``jax.device_put`` — a few KB of int32/bool, never update
      rows or batch data (the dataset lives on device for the whole run);
  device -> host: nothing, unless a ``needs_feedback`` selector (Oort,
      UCB, contribution — see ``repro.selection``) needs its per-row
      stat-utility feedback (a (R,) fp32 vector), plus accuracy/loss on
      ``eval_every`` boundaries.

Because every *decision* of a round (arrival order, round end, fresh vs
straggler split, cache landings) depends only on durations/dropouts — never
on update values — ``Simulator._schedule_round`` runs before the dispatch
and the whole round becomes data-independent index plumbing around one
launch.  All heavy intermediates (the (R, D) delta rows, the stale rows,
the (G, n, D) aggregation operand) exist only inside the program.

Multi-round chunking (``SimConfig.rounds_per_dispatch`` = K > 1): the host
state machine is *prescheduled* K rounds ahead — legal because nothing it
decides reads update values — and the K rounds run as one ``lax.scan`` over
the round body with the donated params/cache/optimizer buffers threaded
through the scan carry.  Chunks always break at ``eval_every`` boundaries,
so evaluation, accuracy-target early stop and the stat-utility feedback
keep their exact round semantics; per-cell results are bit-identical to
K=1 (asserted by tests/test_chunked_sharded.py).  A ``needs_feedback``
selector (``repro.selection``: Oort, UCB, contribution) needs its
per-round device feedback before the *next* round's selection, so it
forces K=1 — and because ``selector_key`` is part of ``pipeline_key``,
only *its own* batch: a feedback cell no longer caps prescheduling for
feedback-free cells sharing a sweep.

Device sharding (``mesh=``): the round program runs under ``shard_map``
over a 2-D ``("s", "p")`` mesh (``repro.sim.participant_sharding``; a
legacy 1-D "s" mesh from ``repro.sweeps.sharding`` is normalized, either
axis may be size 1):

  sweep axis "s" — cells are placed in balanced contiguous blocks of a
  ``(n_shards, s_loc + 1, D)`` params tensor (one scratch row per shard);
  each shard executes the identical round body on its own cells' packed
  rows, with no cross-cell communication.  Early-stop repacking is
  shard-aware: when the live set shrinks enough that the bucketed
  per-shard capacity drops, live cells are compacted across shard
  boundaries (stopped cells vacate whole per-shard bucket steps) and the
  state tensors are rebuilt by a resharding gather — pure data movement,
  bit-identical per cell to the unsharded run;

  participant axis "p" — each round's packed cohort rows are split into
  balanced contiguous blocks over the p-shards
  (``participant_sharding.split_balanced``), so the local-training
  matmuls — the CPU-bound hot path — run in parallel across devices and
  cohorts of tens of thousands of learners fit the round budget.  Cell
  params/optimizer rows are **replicated** along "p" (every p-shard
  applies the identical server step, so replicas stay bitwise equal with
  no communication); the stale cache is partitioned per (s, p) shard — a
  straggler's slot lives on the p-shard that trained it, wherever its
  cell's rows land in later rounds.  The only cross-shard data dependency
  is the SAA aggregation operand (a cell's fresh rows and landing slots
  live on whichever p-shards trained them): each shard zero-masks the
  columns it does not own and ONE ``psum`` over "p" reconstructs the full
  operand — bit-identical to the unsharded gather because every element
  has exactly one non-zero contributor, and the single collective in the
  hot loop (tests/test_participant_sharding.py asserts both).

Parity: gathers/scatters are pure data movement, padding rows are masked to
exact zeros before aggregation (``bucket_pad``'s layout, bit-for-bit), the
weights+aggregate unit is the same ``weights_and_aggregate_by_id`` the
batched sweep path has always vmapped, and the server apply is the same
formula — so per-cell metrics are bit-identical to the per-stage flat path
and to serial runs (asserted by tests/test_pipeline_parity.py and the
benchmarks), for every (mesh, K) combination.

Donation invariants: the stacked params tensor, the cache rows and the
optimizer state are donated into every round/chunk program — after a
dispatch the previous buffers are dead and must not be touched; the
pipeline is their only owner and always replaces its references with the
returned arrays.  Inside a chunk the same invariant holds step-to-step:
the scan carry owns the buffers, and host code never observes the
intermediate rounds' states.  ``Simulator.flat_params`` is stale while a
pipeline run is in flight and is rewritten at ``finalize``.  Dataset/test
tensors are *not* donated (read-only, reused every round; replicated
across the mesh when sharded).

Early stop: cells whose latest evaluation reached ``target_accuracy`` leave
the lockstep batch entirely — no host round logic, no packed rows, no
aggregation group, no eval slot — so a sweep's per-round cost tracks the
*live* cells (bucket-padded repacking shrinks every axis), not S x rounds.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import aggregation as agg
from repro.core.aggregation import (aggregate_updates,
                                    counted_weights_and_aggregate_by_id,
                                    unflatten_update,
                                    weights_and_aggregate_by_id,
                                    yogi_apply_flat)
from repro.core.stale_cache import DeviceStaleCache, ShardedSlotAccounts
from repro.core.staleness import EPS, RULE_ID
from repro.faults.attacks import apply_attack, attack_key
from repro.learners import model_key
from repro.robust.aggregators import (COORD_KINDS, krum_select, robust_key,
                                      trimmed_weighted_aggregate,
                                      weighted_rows)
from repro.selection import SELECTOR_TABLE, selector_key
from repro.sim import learner as ln
from repro.sim.participant_sharding import PART_AXIS, split_balanced
from repro.telemetry import TelemetrySession
from repro.telemetry.registry import CounterView, MetricsRegistry
from repro.telemetry.schema import (DISPATCH_KINDS, GUARD_COUNTERS,
                                    LANE_WIDTH, N_LANE_HOST,
                                    PIPELINE_COUNTERS)

ROW_BLOCK = 128   # packed participant-row padding bucket (bucket_block)
UPD_BLOCK = 32    # per-cell aggregation-row padding bucket (sweep_bucket_pad's)

# float32 rows of width D that the vmapped round holds: per packed row its
# parameter copy, gradient, updated parameters and delta; besides them the
# parameters with their scratch row, the stale cache and the operand
VMAP_ROW_COPIES = 4
VMAP_FIXED_COPIES = 6


@functools.lru_cache(maxsize=1)
def device_bytes_limit() -> Optional[int]:
    """The first local device's memory, where its backend reports it (read
    once: it does not change while the process lives)."""
    stats = jax.local_devices()[0].memory_stats()
    return (stats or {}).get("bytes_limit")


def stream_cohort(d: int, rows: int, bytes_limit: Optional[int]) -> bool:
    """Whether a round trains its ``rows`` packed rows of width ``d`` one
    at a time (``_stream_rows``) instead of under ``vmap``: where the
    vmapped cohort would not fit the device's ``bytes_limit``.  A backend
    that reports no limit keeps ``vmap``."""
    if not bytes_limit:
        return False
    return 4 * d * (VMAP_ROW_COPIES * rows + VMAP_FIXED_COPIES) > bytes_limit


def pipeline_key(cfg) -> tuple:
    """Config fields every Simulator in one pipeline must share: they fix
    the compiled round program's static structure or the lockstep cadence.
    ``repro.sweeps.runner.compat_key`` groups cells by (a superset of) this."""
    return (cfg.benchmark, cfg.local_steps, cfg.local_batch, cfg.local_lr,
            cfg.prox_mu, cfg.rounds, cfg.eval_every, cfg.server_opt,
            robust_key(cfg), attack_key(cfg), selector_key(cfg),
            cfg.use_agg_kernel,
            cfg.scaling_rule if cfg.use_agg_kernel else None,
            cfg.rounds_per_dispatch, cfg.shard_participants,
            cfg.guard, cfg.guard_clip, cfg.guard_reject_mult, cfg.quorum,
            cfg.telemetry, model_key(cfg))


class PipelineStats:
    """Dispatch / transfer accounting for the hot loop (``--profile``).

    Backed by a telemetry ``MetricsRegistry`` — the registry is the single
    storage for every counter (including the guard counters, written once
    by ``TelemetrySession.note_guard``); this class is an attribute-style
    view over it, so the ``--profile`` JSON, the Prometheus snapshot and
    per-sim guard accounting can never disagree.  The attribute API is
    unchanged: ``stats.rounds += k``, ``stats.dispatches["eval"] += 1``,
    ``stats.as_dict()``.  When pipelines share one session (a sweep), the
    counters accumulate across batches and ``as_dict()`` is already the
    sweep-wide total.
    """

    # derived from the telemetry schema so a counter added there (e.g. the
    # robust-aggregator rejections) can never be silently dropped here
    GUARD_KEYS = tuple(k[len("guard_"):] for k in GUARD_COUNTERS)

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 n_shards: int = 1, n_pshards: int = 1):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.n_shards = n_shards
        self.n_pshards = n_pshards
        self.rounds_per_dispatch = 1
        for name in PIPELINE_COUNTERS:
            self.registry.counter(name)
        self.dispatches = CounterView(self.registry, "pipeline_dispatches_",
                                      DISPATCH_KINDS)
        self.guard = CounterView(self.registry, "guard_", self.GUARD_KEYS)

    def _counter(self, name):
        return self.registry.counter("pipeline_" + name)

    # per-round index arrays (explicit device_put) / stat-util + eval +
    # repack-eviction + lane fetches / one-time dataset uploads — all
    # plain registry counters behind attribute accessors
    rounds = property(lambda s: s._counter("rounds").value,
                      lambda s, v: setattr(s._counter("rounds"), "value", v))
    h2d_bytes = property(
        lambda s: s._counter("h2d_bytes").value,
        lambda s, v: setattr(s._counter("h2d_bytes"), "value", v))
    d2h_bytes = property(
        lambda s: s._counter("d2h_bytes").value,
        lambda s, v: setattr(s._counter("d2h_bytes"), "value", v))
    init_h2d_bytes = property(
        lambda s: s._counter("init_h2d_bytes").value,
        lambda s, v: setattr(s._counter("init_h2d_bytes"), "value", v))
    cross_shard_landings = property(
        lambda s: s._counter("cross_shard_landings").value,
        lambda s, v: setattr(s._counter("cross_shard_landings"), "value", v))
    feedback_fetches = property(
        lambda s: s._counter("feedback_fetches").value,
        lambda s, v: setattr(s._counter("feedback_fetches"), "value", v))
    # packed participant rows that train vs the padding of their bucket
    trained_rows = property(
        lambda s: s._counter("trained_rows").value,
        lambda s, v: setattr(s._counter("trained_rows"), "value", v))
    pad_rows = property(
        lambda s: s._counter("pad_rows").value,
        lambda s, v: setattr(s._counter("pad_rows"), "value", v))
    # rows trained one at a time by a streamed round program
    streamed_rows = property(
        lambda s: s._counter("streamed_rows").value,
        lambda s, v: setattr(s._counter("streamed_rows"), "value", v))
    # the census each round hands to selection (its per-round work)
    checked_in = property(
        lambda s: s._counter("checked_in").value,
        lambda s, v: setattr(s._counter("checked_in"), "value", v))

    def as_dict(self) -> dict:
        per_round = max(self.rounds, 1)
        return {
            "rounds": self.rounds,
            "dispatches": dict(self.dispatches),
            "dispatches_per_round": round(
                sum(self.dispatches.values()) / per_round, 3),
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "h2d_bytes_per_round": round(self.h2d_bytes / per_round),
            "d2h_bytes_per_round": round(self.d2h_bytes / per_round),
            "init_h2d_bytes": self.init_h2d_bytes,
            "n_shards": self.n_shards,
            "n_pshards": self.n_pshards,
            "rounds_per_dispatch": self.rounds_per_dispatch,
            "cross_shard_landings": self.cross_shard_landings,
            "feedback_fetches": self.feedback_fetches,
            "trained_rows": self.trained_rows,
            "pad_rows": self.pad_rows,
            "streamed_rows": self.streamed_rows,
            "checked_in": self.checked_in,
            "guard": dict(self.guard),
        }


# ---------------------------------------------------------------------------
# The fused round body (shared by the unsharded and sharded chunk programs
# — one set of numerics, two launch wrappers)
# ---------------------------------------------------------------------------


def _round_body(params, cache, opt_state, x_tr, y_tr, ints, floats, shapes,
                *, train_unit, steps, batch, yogi, use_kernel, kernel_rule,
                single, p_axis=None, guard=None, faulty=False, lane=False,
                attack=None, robust=None, norm_d=None, stream_unit=None):
    """One round's device work on one (local) params/cache block.

    params: (rows, D) — cell rows plus one scratch row; cache: (C + 1, D)
    slot rows plus the trash row; ints/floats: the round's packed index
    arrays whose layout is described by the static ``shapes`` tuple.
    ``single`` broadcasts the parameters instead of gathering them (the
    serial engine's S == 1 case; bit-identical either way).

    ``p_axis`` names the participant mesh axis when the body runs as one
    p-shard of a sharded round: the packed rows are this shard's block of
    the cohort, the cache is this shard's slot partition, and the
    aggregation operand is reconstructed from the per-shard ownership-
    masked partials with ONE ``psum`` — the hot loop's only collective.
    Everything after the psum (weights, aggregate, server apply) is
    computed identically on every p-shard, which is what keeps the
    p-replicated params/optimizer rows bitwise in sync.

    ``guard`` (static) is ``(clip, reject_mult, quorum)`` when guarded
    aggregation is on: the operand is screened in-program
    (``aggregation.screen_rows`` — the same formula every host path runs),
    the survivor mask replaces ``agg_valid``, and the server apply is
    gated on ``survivors >= quorum``.  ``faulty`` (static) appends a
    per-row fp32 corruption multiplier to the floats buffer, applied to
    the delta rows between training and the cache scatter — fault
    injection without any extra transfer or collective.  The last two
    outputs are a (G, 6) int32 stats block [rejected_nonfinite,
    rejected_norm, survivors, applied, robust_rejected, robust_trimmed]
    (zeros when unguarded/non-robust) and the telemetry round-stats lane;
    both are p-replicated like everything after the psum.

    ``attack`` (static, ``repro.faults.attacks.attack_key``) appends a
    per-group attacker mask to the ints buffer and rewrites the attacker
    rows of the post-psum operand *before* the lane stats and the guard
    screen (``apply_attack`` — the same formula every host path runs); a
    round with no scheduled attackers passes through bit-exactly.
    ``robust`` (static, ``repro.robust.aggregators.robust_key``) runs the
    robust aggregator: mask-style kinds shrink ``agg_valid`` before the
    SAA weights pass, coordinate-wise kinds replace it with the trimmed
    mean of the SAA-weighted rows (robust-of-weighted; the numerics of
    ``repro.robust.aggregators._robust_cell``, vmapped over groups).
    When either is active the staleness-agg Pallas kernel is bypassed —
    ``use_kernel`` then only routes the coordinate-wise statistic through
    the ``trimmed_agg`` kernel.  Both default to None, leaving the
    compiled program untouched (the static bit-parity half).

    ``lane`` (static, ``SimConfig.telemetry >= 2``) emits a per-group
    fp32 stats row (``telemetry.schema.LANE_FIELDS``): the host-known
    head fields ride through the floats buffer and are echoed back, the
    update-row L2-norm min/mean/max and non-finite count are computed on
    the *post-psum, pre-screen* operand (so corruption the guard later
    rejects is still visible), and the guard tail mirrors ``gstats``.
    Computed after the psum → no extra collective; lane off returns a
    zero-width block, so the program's outputs and numerics are untouched.

    ``streamed`` (static, the last entry of ``shapes``; ``stream_cohort``)
    trains the round's real rows one at a time with ``stream_unit``
    (``learner.local_train_leaves``; ``_stream_rows``): a fresh row is
    added into its group's fresh sum, a straggler row is written to its
    cache slot, and the aggregation operand is the fresh mean and the
    landing stale rows, ``(G, 1 + ns, D)``, weighed by Eq. 2 with the mean
    counting ``n_F`` fresh updates — so no ``(R, D)`` delta stack exists.
    The row count rides as the last int of ``ints``.  Guarded, robust,
    attacked, lane-emitting and participant-sharded programs need the full
    operand: ``RoundPipeline`` refuses to stream them.

    Named scopes ``train`` / ``cache`` (scatter and operand gather) /
    ``fresh_sum`` (the streamed round's sum and mean of its fresh rows) /
    ``aggregate`` (screens, SAA weights and aggregate; the fused kernel
    also applies) / ``apply`` (server step) name the device ops in a
    profile; they add metadata only, no op.
    """
    r_b, tb, g_b, nf_b, ns_b, all_valid, streamed = shapes
    n_b = nf_b + ns_b
    o = [0]

    def take(n, shape=None, dtype=None):
        a = ints[o[0]:o[0] + n]
        o[0] += n
        if dtype is not None:
            a = a.astype(dtype)
        return a.reshape(shape) if shape is not None else a

    batch_idx = take(r_b * tb, (r_b, tb))
    row_cell = take(r_b)
    row_sub = take(r_b)
    scat_slot = take(r_b)
    agg_cell = take(g_b)
    fr_idx = take(g_b * nf_b, (g_b, nf_b))
    sl_idx = take(g_b * ns_b, (g_b, ns_b))
    agg_tau = take(g_b * n_b, (g_b, n_b))
    rule_id = take(g_b)
    agg_fresh = take(g_b * n_b, (g_b, n_b), bool)
    agg_valid = take(g_b * n_b, (g_b, n_b), bool)
    agg_mask = take(g_b * n_b, (g_b, n_b), bool)
    has_g = take(g_b, None, bool)
    agg_att = (take(g_b * n_b, (g_b, n_b), bool) if attack is not None
               else None)
    beta_g, lr_g = floats[:g_b], floats[g_b:2 * g_b]
    fscale = floats[2 * g_b:2 * g_b + r_b] if faulty else None

    # --- train: gather batches + per-row params, one vmapped call ---
    with jax.named_scope("train"):
        # trailing sample dims ride along untouched: (dim,) features for
        # the classifier benchmarks, (S,) token sequences (x AND y) for the
        # LM ones
        bx = x_tr[row_sub[:, None], batch_idx]        # (R, steps*batch, ...)
        bx = bx.reshape((r_b, steps, batch) + bx.shape[2:])
        by = y_tr[row_sub[:, None], batch_idx]
        by = by.reshape((r_b, steps, batch) + by.shape[2:])
    if streamed:
        n_rows = take(1)[0]
        cache, fsum, losses, l2s = _stream_rows(
            stream_unit, params, cache, bx, by, row_cell, scat_slot, fr_idx,
            agg_valid[:, :nf_b], fscale, n_rows, single=single)
        return _streamed_aggregate(
            params, cache, opt_state, fsum, losses, l2s, sl_idx, agg_cell,
            agg_tau, agg_fresh, agg_valid, has_g, beta_g, lr_g, rule_id,
            nf_b=nf_b, yogi=yogi, use_kernel=use_kernel,
            kernel_rule=kernel_rule)
    with jax.named_scope("train"):
        if single:
            deltas, losses, l2s = jax.vmap(
                train_unit, in_axes=(None, 0, 0))(params[0], bx, by)
        else:
            deltas, losses, l2s = jax.vmap(train_unit)(params[row_cell],
                                                       bx, by)

    # --- straggler scatter into the cache, then gather ---------------
    with jax.named_scope("cache"):
        if faulty:
            # injected corruption: one IEEE fp32 multiply per delta row —
            # before the scatter, so cached straggler rows carry the fault too
            deltas = deltas * fscale[:, None]
        # scatter FIRST so the donated cache updates in place (a gather
        # before the scatter would force XLA to copy the whole buffer);
        # this round's scatter slots are disjoint from this round's landing
        # slots because the pipeline quarantines freed slots for one round
        cache = cache.at[scat_slot].set(deltas)

        # fresh columns from this round's delta rows, stale columns from
        # the cache slots; same per-cell row multiset as the per-stage
        # path's (fresh + stale, zero-padded) stack
        uf, us = deltas[fr_idx], cache[sl_idx]
        if p_axis is not None:
            # every operand column is owned by exactly one p-shard (the one
            # holding its delta row / cache slot): zero the rest and let one
            # psum reconstruct the full operand — bit-identical to the
            # unsharded gather, since each element sums one non-zero
            # contributor with exact zeros
            uf = jnp.where(agg_mask[:, :nf_b, None], uf, 0.0)
            us = jnp.where(agg_mask[:, nf_b:, None], us, 0.0)
            u = jax.lax.psum(jnp.concatenate([uf, us], axis=1), p_axis)
        else:
            if not all_valid:
                # bucket_pad's exact zeros in the padding columns
                uf = jnp.where(agg_valid[:, :nf_b, None], uf, 0.0)
                us = jnp.where(agg_valid[:, nf_b:, None], us, 0.0)
            u = jnp.concatenate([uf, us], axis=1)

    with jax.named_scope("aggregate"):
        if attack is not None:
            # coordinated attack: rewrite the attacker rows of the post-psum
            # operand (pre-lane, pre-screen — the lane and the guard both see
            # what the server would see)
            atk_kind, atk_scale, atk_z = attack
            u = apply_attack(u, agg_att, agg_valid, kind=atk_kind,
                             scale=atk_scale, z=atk_z)

        if lane:
            # telemetry lane, device half: row-norm stats over the
            # *pre-screen* operand, post-psum (p-replicated, no extra
            # collective).  Finite rows are selected with where() — never
            # multiplied — so one NaN row cannot poison the finite rows'
            # stats.  Under the persistent D-blocked layout (``norm_d``) the
            # stats reduce over the true-D slice: slice-then-reduce is
            # bit-identical to the unpadded layout, whereas reducing across
            # appended zero columns is not (the SIMD lane partition of the
            # reduction changes).
            u_t = u if norm_d is None else u[..., :norm_d]
            row_fin = jnp.isfinite(u_t).all(axis=-1)
            norms = jnp.sqrt(jnp.sum(u_t * u_t, axis=-1))
            ok = agg_valid & row_fin
            cnt = ok.sum(axis=-1)
            nonzero = cnt > 0
            l2_min = jnp.where(
                nonzero, jnp.min(jnp.where(ok, norms, jnp.inf), axis=-1), 0.0)
            l2_max = jnp.where(
                nonzero, jnp.max(jnp.where(ok, norms, -jnp.inf), axis=-1),
                0.0)
            l2_mean = jnp.where(
                nonzero,
                jnp.sum(jnp.where(ok, norms, 0.0), axis=-1)
                / jnp.maximum(cnt, 1).astype(jnp.float32), 0.0)
            lane_nonfin = (agg_valid & ~row_fin).sum(axis=-1)

        # --- guard screening + robust mask step (static: plain programs
        # are untouched) --------------------------------------------------
        zeros_g = jnp.zeros(g_b, jnp.int32)
        n_nf = n_out = rrej = rtrim = zeros_g
        if guard is not None:
            clip_g, mult_g, quorum_g = guard
            u, v2, n_nf, n_out, _ = agg.screen_rows(u, agg_valid, clip=clip_g,
                                                    reject_mult=mult_g,
                                                    norm_d=norm_d)
            agg_valid = v2
        robust_coord = robust is not None and robust[0] in COORD_KINDS
        if robust is not None and not robust_coord:
            # mask-style robust kinds shrink the survivor mask before the
            # SAA weights pass (repro.robust.aggregators._robust_cell order:
            # attack -> guard screen -> robust mask -> weights)
            if robust[0] in ("krum", "multi_krum"):
                sel = jax.vmap(functools.partial(
                    krum_select, f=robust[1], m=robust[2]))(u, agg_valid)
                rrej = (agg_valid & ~sel).sum(axis=-1).astype(jnp.int32)
                agg_valid = sel
            else:                                        # norm_median_clip
                _, clip_r, mult_r = robust
                u, v2, nf2, out2, ncl2 = agg.screen_rows(
                    u, agg_valid, clip=clip_r, reject_mult=mult_r)
                rrej, rtrim, agg_valid = nf2 + out2, ncl2, v2
        survivors = agg_valid.sum(axis=-1).astype(jnp.int32)
        has_eff = (has_g & (survivors >= quorum_g) if guard is not None
                   else has_g)

        # --- SAA weights + aggregate + server apply ----------------------
        rows_old = params[agg_cell]                       # (G, D)
        # robust/attacked programs always take the jnp weights path for the
        # SAA part; use_kernel then only routes the coordinate-wise trim
        # through the trimmed_agg kernel (one cross-substrate story)
        saa_kernel = use_kernel and attack is None and robust is None
        if saa_kernel:
            from repro.kernels.staleness_agg.staleness_agg import (
                D_BLK, sweep_fused_staleness_apply,
                sweep_fused_staleness_aggregate)
            d = u.shape[-1]
            pad = (-d) % D_BLK
            up = jnp.pad(u, ((0, 0), (0, 0), (0, pad)))
            if yogi:
                agg_out, _ = sweep_fused_staleness_aggregate(
                    up, agg_fresh, agg_tau, beta_g, agg_valid,
                    rule=kernel_rule)
                agg_out = agg_out[:, :d]
            else:
                scal = jnp.stack([beta_g, lr_g], axis=1)
                new_rows, _ = sweep_fused_staleness_apply(
                    jnp.pad(rows_old, ((0, 0), (0, pad))), up, agg_fresh,
                    agg_tau, agg_valid, scal, rule=kernel_rule)
                new_rows = new_rows[:, :d]
        elif robust_coord:
            # robust-of-weighted: per-coordinate trimmed mean of the SAA-
            # weighted rows (trimmed_weighted_aggregate's formula, vmapped)
            median = robust[0] == "coord_median"
            tk = 0 if median else robust[1]
            if use_kernel:
                from repro.kernels.trimmed_agg import ops as tops
                y, cc = jax.vmap(weighted_rows)(u, agg_fresh, agg_tau,
                                                agg_valid, beta_g, rule_id)
                k_half = jnp.maximum((cc - 1) // 2, 0)
                k_eff = (k_half if median
                         else jnp.minimum(jnp.int32(tk), k_half))
                agg_out = tops.sweep_trimmed_aggregate(y, k_eff, cc)
                agg_out = jnp.where((cc > 0)[:, None], agg_out, 0.0)
                rtrim = jnp.where(cc > 0, 2 * k_eff, 0)
            else:
                agg_out, rtrim = jax.vmap(functools.partial(
                    trimmed_weighted_aggregate, trim_k=tk, median=median))(
                    u, agg_fresh, agg_tau, agg_valid, beta_g, rule_id)
        elif ns_b == 0:
            # no stale rows anywhere this round: Eq. 2 degenerates to the
            # fresh average, so skip the deviation pass entirely.  The
            # weight vector is bit-identical to the general path's (fresh
            # rows weigh 1, padding weighs 0, same normalization).  Under a
            # guard or a mask-style robust kind, rejected fresh rows must
            # weigh 0 too (agg_valid is the post-screen survivor mask;
            # without faults it covers every fresh column, so the bits are
            # unchanged).
            w = ((agg_fresh & agg_valid).astype(jnp.float32)
                 if guard is not None or robust is not None
                 else agg_fresh.astype(jnp.float32))
            w = w / jnp.maximum(w.sum(axis=1, keepdims=True), EPS)
            agg_out = jax.vmap(aggregate_updates)(u, w)
        else:
            agg_out, _ = jax.vmap(weights_and_aggregate_by_id)(
                u, agg_fresh, agg_tau, agg_valid, beta_g, rule_id)

    # --- stats block + lane assembly ---------------------------------
    if guard is not None or robust is not None:
        gstats = jnp.stack([n_nf, n_out, survivors,
                            has_eff.astype(jnp.int32), rrej, rtrim], axis=1)
    else:
        gstats = jnp.zeros((g_b, 6), jnp.int32)
    if lane:
        # assemble the lane row: host pass-through head (echoed from the
        # floats buffer), device norm stats, guard + robust tail
        # (agg_valid is the post-screen/post-mask survivor mask here;
        # plain programs leave it unchanged)
        host_off = 2 * g_b + (r_b if faulty else 0)
        lane_host = floats[host_off:host_off + g_b * N_LANE_HOST] \
            .reshape(g_b, N_LANE_HOST)
        lanes = jnp.concatenate([
            lane_host,
            jnp.stack([l2_min, l2_mean, l2_max,
                       lane_nonfin.astype(jnp.float32)], axis=1),
            jnp.stack([n_nf, n_out, rrej, rtrim],
                      axis=1).astype(jnp.float32),
            jnp.stack([agg_valid.sum(axis=-1).astype(jnp.float32),
                       has_eff.astype(jnp.float32)], axis=1),
        ], axis=1)
    else:
        # zero-width block keeps the program signature uniform at no cost
        lanes = jnp.zeros((g_b, 0), jnp.float32)
    with jax.named_scope("apply"):
        if yogi:
            state_rows = jax.tree.map(lambda s: s[agg_cell], opt_state)
            new_rows, new_state = jax.vmap(yogi_apply_flat)(
                rows_old, agg_out, state_rows)
            keep = lambda new, old: jnp.where(
                has_eff.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)
            opt_state = jax.tree.map(
                lambda s, ns, os: s.at[agg_cell].set(keep(ns, os)),
                opt_state, new_state, state_rows)
        elif not saa_kernel:
            new_rows = rows_old + lr_g[:, None] * agg_out
        # quorum failures (has_eff < has_g) carry the old rows unchanged
        new_rows = jnp.where(has_eff[:, None], new_rows, rows_old)
        params = params.at[agg_cell].set(new_rows)
    return params, cache, opt_state, losses, l2s, gstats, lanes


def _stream_rows(stream_unit, params, cache, bx, by, row_cell, scat_slot,
                 fr_idx, fr_valid, fscale, n_rows, *, single):
    """The streamed round's training: its first ``n_rows`` packed rows (the
    real ones; the bucket's pad rows are not trained), one after another.
    Each fresh row's delta is added into its aggregation group's fresh
    sum, kept as the parameter leaves (so the add needs no relayout), and
    each straggler row's delta is flattened into its cache slot; a row
    that is neither (a late row without SAA) only reports its loss.
    Returns (cache, fresh-sum leaves (G, *leaf), losses (R,), l2s (R,))."""
    r_b = bx.shape[0]
    g_b, d = fr_idx.shape[0], cache.shape[-1]
    trash = cache.shape[0] - 1
    # each packed row's aggregation group if it is fresh, else -1 (padding
    # columns point at row 0 with -1, and max keeps the real entries)
    row_g = jnp.full((r_b,), -1, jnp.int32).at[fr_idx].max(
        jnp.where(fr_valid, jnp.arange(g_b, dtype=jnp.int32)[:, None], -1))

    def deltas(before, after, j):
        out = [a - b for b, a in zip(before, after)]
        return out if fscale is None else [u * fscale[j] for u in out]

    def add(fsum, g, before, after, j):
        return [jax.lax.dynamic_update_index_in_dim(
            f, jax.lax.dynamic_index_in_dim(f, g, keepdims=False) + u, g, 0)
            for f, u in zip(fsum, deltas(before, after, j))]

    def put(cache, slot, before, after, j):
        flat = [u.ravel() for u in deltas(before, after, j)]
        pad = d - sum(u.size for u in flat)
        row = jnp.concatenate(flat + [jnp.zeros((pad,), jnp.float32)])
        return jax.lax.dynamic_update_index_in_dim(cache, row, slot, 0)

    def row(j, carry):
        cache, fsum, losses, l2s = carry
        p = params[0] if single else params[row_cell[j]]
        with jax.named_scope("train"):
            before, after, loss, l2 = stream_unit(p, bx[j], by[j])
        g, slot = row_g[j], scat_slot[j]
        with jax.named_scope("fresh_sum"):
            fsum = jax.lax.cond(g >= 0,
                                lambda f: add(f, g, before, after, j),
                                lambda f: f, fsum)
        with jax.named_scope("cache"):
            cache = jax.lax.cond(slot != trash,
                                 lambda c: put(c, slot, before, after, j),
                                 lambda c: c, cache)
        return (cache, fsum, losses.at[j].set(loss), l2s.at[j].set(l2))

    p_shape = jax.eval_shape(stream_unit, params[0], bx[0], by[0])[0]
    with jax.named_scope("fresh_sum"):
        fsum = [jnp.zeros((g_b,) + b.shape, jnp.float32) for b in p_shape]
    zeros = jnp.zeros((r_b,), jnp.float32)
    return jax.lax.fori_loop(0, n_rows, row, (cache, fsum, zeros, zeros))


def _streamed_aggregate(params, cache, opt_state, fsum, losses, l2s, sl_idx,
                        agg_cell, agg_tau, agg_fresh, agg_valid, has_g,
                        beta_g, lr_g, rule_id, *, nf_b, yogi, use_kernel,
                        kernel_rule):
    """Eq. 2 over the streamed round's operand, ``(G, 1 + ns, D)``: each
    group's fresh mean (its fresh-sum leaves flattened, over ``n_F``),
    counting ``n_F`` fresh updates, and its landing stale rows, through the
    SAA kernel or the jnp path; then the server step.  Returns the round
    body's outputs."""
    g_b, ns_b = sl_idx.shape
    n_f = agg_fresh[:, :nf_b].sum(axis=-1).astype(jnp.float32)     # (G,)
    d = cache.shape[-1]
    with jax.named_scope("fresh_sum"):
        # the fresh mean as a flat row of the cache's width (zero tail)
        flat = [f.reshape(g_b, -1) for f in fsum]
        pad = d - sum(f.shape[1] for f in flat)
        mean = jnp.concatenate(flat + [jnp.zeros((g_b, pad), jnp.float32)],
                               axis=1) / jnp.maximum(n_f, 1.0)[:, None]
    with jax.named_scope("cache"):
        if ns_b:
            us = jnp.where(agg_valid[:, nf_b:, None], cache[sl_idx], 0.0)
            u = jnp.concatenate([mean[:, None], us], axis=1)
        else:
            u = mean[:, None]
    fresh_n = jnp.concatenate([n_f[:, None],
                               jnp.zeros((g_b, ns_b), jnp.float32)], axis=1)
    valid = jnp.concatenate([(n_f > 0)[:, None], agg_valid[:, nf_b:]], axis=1)
    tau = jnp.concatenate([jnp.zeros((g_b, 1), agg_tau.dtype),
                           agg_tau[:, nf_b:]], axis=1)
    rows_old = params[agg_cell]                                   # (G, D)
    with jax.named_scope("aggregate"):
        if use_kernel:
            from repro.kernels.staleness_agg.staleness_agg import (
                sweep_fused_staleness_apply, sweep_fused_staleness_aggregate)
            if yogi:
                agg_out, _ = sweep_fused_staleness_aggregate(
                    u, fresh_n, tau, beta_g, valid, rule=kernel_rule)
            else:
                new_rows, _ = sweep_fused_staleness_apply(
                    rows_old, u, fresh_n, tau, valid,
                    jnp.stack([beta_g, lr_g], axis=1), rule=kernel_rule)
        elif ns_b == 0:
            # the fresh mean alone: its normalized weight is exactly 1
            agg_out = mean
        else:
            agg_out, _ = jax.vmap(counted_weights_and_aggregate_by_id)(
                u, fresh_n, tau, valid, beta_g, rule_id)
    with jax.named_scope("apply"):
        if yogi:
            state_rows = jax.tree.map(lambda s: s[agg_cell], opt_state)
            new_rows, new_state = jax.vmap(yogi_apply_flat)(
                rows_old, agg_out, state_rows)
            opt_state = jax.tree.map(
                lambda s, ns, os: s.at[agg_cell].set(
                    jnp.where(has_g.reshape((-1,) + (1,) * (ns.ndim - 1)),
                              ns, os)),
                opt_state, new_state, state_rows)
        elif not use_kernel:
            new_rows = rows_old + lr_g[:, None] * agg_out
        new_rows = jnp.where(has_g[:, None], new_rows, rows_old)
        params = params.at[agg_cell].set(new_rows)
    return (params, cache, opt_state, losses, l2s,
            jnp.zeros((g_b, 6), jnp.int32), jnp.zeros((g_b, 0), jnp.float32))


@functools.lru_cache(maxsize=16)
def _chunk_program(spec, lr, prox_mu, steps, batch, yogi, use_kernel,
                   kernel_rule, guard, faulty, lane, attack, robust,
                   loss, norm_d, out_dim, single):
    """K-round chunk program (unsharded): ``lax.scan`` of the round body
    with the donated params/cache/optimizer buffers as the scan carry and
    the K prescheduled rounds' index arrays as the scanned inputs.  One
    dispatch covers K rounds; the per-step math is the op-for-op round
    body, so results are bitwise those of K single dispatches — K=1 (the
    default) is simply a scan of length one, the only round driver.

    Static over (model spec, local hyperparameters, server optimizer,
    kernel routing, S==1); the round-varying index arrays arrive packed in
    TWO device buffers (one int32, one fp32) whose layout is described by
    the static ``shapes`` tuple — so one explicit ``jax.device_put`` pair
    covers a chunk, and XLA recompiles only when a padding bucket first
    appears.

    ``loss`` is the model's objective (``MODEL_TABLE``; stable per
    ``build_model``'s cache, so it is a sound lru key), ``norm_d`` /
    ``out_dim`` the persistent D-blocked layout's true and padded row
    widths (both ``None`` on the unpadded layout — the HEAD program).
    """
    train_unit = functools.partial(ln.local_train_flat, spec=spec, lr=lr,
                                   prox_mu=prox_mu, loss=loss,
                                   out_dim=out_dim)
    stream_unit = functools.partial(ln.local_train_leaves, spec=spec, lr=lr,
                                    prox_mu=prox_mu, loss=loss)
    body = functools.partial(_round_body, train_unit=train_unit, steps=steps,
                             batch=batch, yogi=yogi, use_kernel=use_kernel,
                             kernel_rule=kernel_rule, guard=guard,
                             faulty=faulty, lane=lane, attack=attack,
                             robust=robust, single=single, norm_d=norm_d,
                             stream_unit=stream_unit)

    def prog(params, cache, opt_state, x_tr, y_tr, ints_k, floats_k, shapes):
        def step(carry, xs):
            p, c, o = carry
            p, c, o, losses, l2s, gst, lns = body(p, c, o, x_tr, y_tr,
                                                  xs[0], xs[1], shapes)
            return (p, c, o), (losses, l2s, gst, lns)

        (params, cache, opt_state), (losses, l2s, gst, lns) = jax.lax.scan(
            step, (params, cache, opt_state), (ints_k, floats_k))
        return params, cache, opt_state, losses, l2s, gst, lns

    return jax.jit(prog, donate_argnums=(0, 1, 2), static_argnums=(7,))


@functools.lru_cache(maxsize=16)
def _sharded_chunk_program(spec, lr, prox_mu, steps, batch, yogi, use_kernel,
                           kernel_rule, guard, faulty, lane, attack, robust,
                           loss, norm_d, out_dim, mesh):
    """K-round chunk program sharded over the 2-D ``("s", "p")`` round
    mesh: ``shard_map`` with the chunk scan inside.  Each (s, p) device
    owns its s-block's ``(s_loc + 1, D)`` params rows (replicated along
    "p"), a ``(c_loc + 1, D)`` block of the flat per-(s, p)-shard cache,
    and its own packed index arrays covering the cohort rows it trains.
    The round body is shard-local except for the single aggregation-
    operand ``psum`` over "p" (a no-op reduction when ``n_p == 1``, the
    PR-4 sweep-only case) — every cell's math is op-for-op the unsharded
    body's and the sweep-axis Pallas kernels simply see a grid over the
    local S.  Datasets are replicated; losses/l2s come back concatenated
    along the row axis (flat shard ``f = j * n_p + q`` owns rows
    ``[f * r_b, (f+1) * r_b)``)."""
    train_unit = functools.partial(ln.local_train_flat, spec=spec, lr=lr,
                                   prox_mu=prox_mu, loss=loss,
                                   out_dim=out_dim)
    body = functools.partial(_round_body, train_unit=train_unit, steps=steps,
                             batch=batch, yogi=yogi, use_kernel=use_kernel,
                             kernel_rule=kernel_rule, guard=guard,
                             faulty=faulty, lane=lane, attack=attack,
                             robust=robust, single=False, p_axis=PART_AXIS,
                             norm_d=norm_d)
    opt_spec = ({"m": P("s"), "v": P("s"), "t": P("s")} if yogi else None)

    def prog(params3, cache3, opt_state, x_tr, y_tr, ints3, floats3, shapes):
        def per_shard(p3, c3, o3, x_tr, y_tr, i3, f3):
            p, c = p3[0], c3[0]
            o = jax.tree.map(lambda a: a[0], o3)

            def step(carry, xs):
                p, c, o = carry
                p, c, o, losses, l2s, gst, lns = body(p, c, o, x_tr, y_tr,
                                                      xs[0], xs[1], shapes)
                return (p, c, o), (losses, l2s, gst, lns)

            (p, c, o), (losses, l2s, gst, lns) = jax.lax.scan(
                step, (p, c, o), (i3[:, 0], f3[:, 0]))
            return (p[None], c[None], jax.tree.map(lambda a: a[None], o),
                    losses, l2s, gst, lns)

        return jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(P("s"), P(("s", "p")), opt_spec, P(), P(),
                      P(None, ("s", "p")), P(None, ("s", "p"))),
            out_specs=(P("s"), P(("s", "p")), opt_spec,
                       P(None, ("s", "p")), P(None, ("s", "p")),
                       P(None, ("s", "p")), P(None, ("s", "p"))),
            check_vma=False,
        )(params3, cache3, opt_state, x_tr, y_tr, ints3, floats3)

    return jax.jit(prog, donate_argnums=(0, 1, 2), static_argnums=(7,))


@functools.lru_cache(maxsize=2)
def _row_fetch_program():
    """Jitted row gather from a (n_shards, rows_loc, ...) tensor's flattened
    row space — eager advanced indexing would sneak implicit scalar uploads
    past the transfer guard; inside jit the constants live in the program."""
    @jax.jit
    def f(arr, idx):
        return arr.reshape((-1,) + arr.shape[2:])[idx]
    return f


@functools.lru_cache(maxsize=8)
def _eval_program(spec, evaluate=ln.evaluate):
    """Batched eval over the live cells: gather their parameter rows and
    each cell's (possibly shared) test set.  ``evaluate`` is the model's
    metric fn (``MODEL_TABLE``); a block-padded parameter row is accepted
    as-is — ``unflatten_update`` consumes exactly D leading elements."""
    def ev(flat, ti, x_u, y_u):
        return evaluate(unflatten_update(flat, spec), x_u[ti], y_u[ti])

    def f(params, packed, x_u, y_u):
        l_b = packed.shape[0] // 2
        eval_idx, te_idx = packed[:l_b], packed[l_b:]
        with jax.named_scope("eval"):
            return jax.vmap(ev, in_axes=(0, 0, None, None))(
                params[eval_idx], te_idx, x_u, y_u)

    return jax.jit(f)


# ---------------------------------------------------------------------------
# Pipeline driver
# ---------------------------------------------------------------------------


def _quarantine_frees(order, scheds) -> list:
    """Slots released by this round's landings/expiries, deduplicated by
    in-flight entry: a replay fault lands the same entry twice, but its
    slot must be freed exactly once."""
    out, seen = [], set()
    for i in order:
        for f in scheds[i].landing + scheds[i].expired:
            if id(f) not in seen:
                seen.add(id(f))
                out.append(f.delta)
    return out


@dataclasses.dataclass
class _RoundWork:
    """One prescheduled round of a chunk: the host state machine has already
    advanced past it (plans drawn, schedules fixed, slots allocated,
    records appended); only the device dispatch and the eval fill remain."""
    r: int
    order: list
    plans: dict
    scheds: dict
    surv: dict
    recs: dict
    rowq: dict      # (cell, plan row) -> (p-shard, local slot) row placement
    occ: dict       # cell -> stale-cache occupancy after this round's
                    # scheduling (captured at preschedule time — the cache
                    # mutates across a chunk's later rounds)


class RoundPipeline:
    def __init__(self, sims: Sequence, progress: bool = False, mesh=None,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every: int = 0, checkpoint_wrap=None,
                 start_round: int = 0, telemetry=None,
                 labels: Optional[Sequence[str]] = None):
        assert len(sims) >= 1
        # every pipeline has a telemetry session; the directory-less
        # default costs ~nothing (null spans, no writers) but still backs
        # PipelineStats with a live registry
        self.telemetry = (telemetry if telemetry is not None
                          else TelemetrySession())
        for sim in sims:
            sim._hand_over_build(self.telemetry)
        with self.telemetry.span("upload", cells=len(sims)):
            self._setup(sims, progress, mesh, checkpoint_path,
                        checkpoint_every, checkpoint_wrap, start_round,
                        labels)

    def _setup(self, sims, progress, mesh, checkpoint_path, checkpoint_every,
               checkpoint_wrap, start_round, labels) -> None:
        """Host state, device buffers (parameters, stale cache, optimizer
        state, datasets) and the compiled programs of a batch."""
        self.sims = list(sims)
        self.progress = progress
        cfg0 = sims[0].cfg
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = int(checkpoint_every or 0)
        self.checkpoint_wrap = checkpoint_wrap  # envelope hook (sweep resume)
        self._start_round = int(start_round)
        self._next_ckpt = self._start_round + self.checkpoint_every
        for sim in sims:
            assert sim.cfg.fast_path and sim.cfg.fused_rounds, \
                "RoundPipeline drives the fused fast path only"
            assert pipeline_key(sim.cfg) == pipeline_key(cfg0), \
                "incompatible Simulators in one pipeline batch"
        self.cfg0 = cfg0
        self._labels = (list(labels) if labels is not None
                        else [f"sim{i}" for i in range(len(sims))])
        # level >= 2 turns on the in-program round-stats lane (static in
        # pipeline_key, so every sim of a batch agrees)
        self._lane = int(cfg0.telemetry) >= 2
        self.spec = sims[0]._flat_spec
        self.d = agg.flat_dim(self.spec)
        # model objective/metric come off the MODEL_TABLE build (stable
        # objects: build_model caches, and model_key(cfg) ∈ pipeline_key
        # keeps the batch model-uniform, so sims[0]'s fns serve every cell)
        self._model_fns = sims[0]._model_fns
        # persistent D-blocked layout: when every round runs the staleness-
        # agg Pallas kernel (no attack/robust rewrite bypassing it), the
        # params/cache/opt buffers are allocated ONCE at the kernel's
        # D_BLK-padded width instead of jnp.pad-ing the operand each round.
        # For paper-scale D the per-round pad was cheap; for model-zoo D
        # (1e5+) it is an O(G·N·D) copy in the hot loop.  Pad columns hold
        # exact zeros for the life of the run (train deltas are zero-padded
        # at the source, every server op is columnwise), and every true-D
        # reduction (lane norms, guard screen) slices before reducing, so
        # results are bit-identical to the per-round-pad layout.
        saa = (cfg0.use_agg_kernel and attack_key(cfg0) is None
               and robust_key(cfg0) is None)
        if saa:
            from repro.kernels.staleness_agg.staleness_agg import D_BLK
            self.d_pad = self.d + ((-self.d) % D_BLK)
        else:
            self.d_pad = self.d
        d, pad_w = self.d, self.d_pad - self.d

        def _pad_rows(a):
            # widen the trailing D axis with zero columns (jnp/np alike);
            # identity on the unpadded layout and on non-D leaves (yogi "t").
            # It closes over locals, not ``self``: a pipeline that held a
            # closure over itself would live until the next garbage
            # collection, with its parameter and cache buffers
            if pad_w and np.ndim(a) and np.shape(a)[-1] == d:
                width = [(0, 0)] * (np.ndim(a) - 1) + [(0, pad_w)]
                return (np.pad(a, width) if isinstance(a, np.ndarray)
                        else jnp.pad(a, width))
            return a

        self._pad_rows = _pad_rows
        self.yogi = cfg0.server_opt == "yogi"
        if mesh is None and cfg0.shard_participants:
            from repro.sim.participant_sharding import participant_mesh
            mesh = participant_mesh(cfg0.shard_participants)
        elif mesh is not None:
            if cfg0.shard_participants:
                raise ValueError(
                    "ambiguous participant sharding: an explicit mesh was "
                    "passed while SimConfig.shard_participants is set — "
                    "configure one or the other (SweepRunner callers: use "
                    "SweepRunner(shard_participants=))")
            from repro.sim.participant_sharding import as_round_mesh
            mesh = as_round_mesh(mesh)
        self.mesh = mesh
        self.n_shards = int(mesh.shape["s"]) if mesh is not None else 1
        self.n_pshards = int(mesh.shape["p"]) if mesh is not None else 1
        self.stats = PipelineStats(registry=self.telemetry.registry,
                                   n_shards=self.n_shards,
                                   n_pshards=self.n_pshards)

        s = len(sims)
        # ``needs_feedback`` selectors (Oort, UCB, contribution, ...)
        # consume the per-row stat-utility feedback; without one the round
        # loop fetches nothing per round.  selector_key is part of
        # pipeline_key, so the batch is selector-uniform and one spec lookup
        # decides for every cell.
        sel_spec = SELECTOR_TABLE[cfg0.selector]
        self._fetch_l2s = sel_spec.needs_feedback
        # A feedback selector's signal is device data needed before the
        # next round's host decisions, so it caps prescheduling at one round
        self.k_rounds = (1 if self._fetch_l2s
                         else max(1, int(cfg0.rounds_per_dispatch)))
        self.stats.rounds_per_dispatch = self.k_rounds

        if self.mesh is None:
            # stacked (S+1, D) params; the extra row is scratch that padding
            # aggregation groups read and write (never a real cell)
            self.placement = None
            self.params = jnp.concatenate(
                [_pad_rows(jnp.stack([sim.flat_params for sim in sims])),
                 jnp.zeros((1, self.d_pad), jnp.float32)])
            if self.yogi:
                self.opt_state = jax.tree.map(
                    lambda *xs: _pad_rows(
                        jnp.stack(xs + (jnp.zeros_like(xs[0]),))),
                    *[sim.flat_opt_state for sim in sims])
            else:
                self.opt_state = None
            self.cache = DeviceStaleCache(
                self.d_pad,
                capacity=max(c.cfg.stale_cache_capacity for c in sims),
                grow=True)
            self.accounts = None
        else:
            from repro.sim.participant_sharding import (cache_spec,
                                                        chunk_spec,
                                                        param_spec,
                                                        replicated_spec)
            from repro.sweeps.sharding import Placement
            self.placement = Placement.build(range(s), self.n_shards)
            self._shard_spec = param_spec(mesh)
            self._cache_spec = cache_spec(mesh)
            self._rep_spec = replicated_spec(mesh)
            self._chunk_spec = chunk_spec(mesh)
            self.params = jax.device_put(
                self._stack_rows([_pad_rows(np.asarray(sim.flat_params))
                                  for sim in sims], (self.d_pad,), np.float32),
                self._shard_spec)
            if self.yogi:
                leaves = [sim.flat_opt_state for sim in sims]
                self.opt_state = jax.tree.map(
                    lambda *xs: jax.device_put(
                        self._stack_rows(
                            [_pad_rows(np.asarray(x)) for x in xs],
                            np.shape(_pad_rows(np.asarray(xs[0]))),
                            np.asarray(xs[0]).dtype),
                        self._shard_spec),
                    *leaves)
            else:
                self.opt_state = None
            self.cache = None
            # one slot space per (s, p) shard, flat s-major — a straggler's
            # slot lives on the p-shard that trained its row
            nflat = self.n_shards * self.n_pshards
            self.accounts = ShardedSlotAccounts(
                nflat, capacity=max(c.cfg.stale_cache_capacity for c in sims))
            self.cache_rows = jax.device_put(
                jnp.zeros((nflat, self.accounts.capacity + 1, self.d_pad),
                          jnp.float32), self._cache_spec)
            self._saved = {}      # evicted done cells' final rows (host)

        # one device copy of each distinct substrate's dataset (replicated
        # across the mesh when sharded: shard-local batch gathers)
        subs = []
        self.sub_idx = np.zeros(s, np.int32)
        for i, sim in enumerate(sims):
            if not any(sim.substrate is sb for sb in subs):
                subs.append(sim.substrate)
            self.sub_idx[i] = next(j for j, sb in enumerate(subs)
                                   if sb is sim.substrate)
        host = (np.stack([sb.data.x_train for sb in subs]),
                np.stack([sb.data.y_train for sb in subs]),
                np.stack([sb.data.x_test for sb in subs]),
                np.stack([sb.data.y_test for sb in subs]))
        if self.mesh is None:
            self.x_tr, self.y_tr, self.x_te, self.y_te = jax.device_put(host)
        else:
            self.x_tr, self.y_tr, self.x_te, self.y_te = (
                jax.device_put(a, self._rep_spec) for a in host)
        self.stats.init_h2d_bytes += (sum(a.nbytes for a in host)
                                      + (s + self.n_shards) * self.d_pad * 4)
        # guard/fault routing is static program structure: all cells of a
        # batch share the guard config (pipeline_key) and the floats-buffer
        # layout (any faulted cell widens it for the whole batch)
        self._guard = ((cfg0.guard_clip, cfg0.guard_reject_mult,
                        max(int(cfg0.quorum), 1)) if cfg0.guard else None)
        self._faulty = any(
            sim.fault_plan is not None and sim.fault_plan.has_corruption
            for sim in sims)
        # robust aggregation / coordinated attacks are static program
        # structure like the guard (pipeline_key keeps batches uniform)
        self._attack = attack_key(cfg0)
        self._robust = robust_key(cfg0)
        norm_d = self.d if pad_w else None
        out_dim = self.d_pad if pad_w else None
        prog_args = (self.spec, cfg0.local_lr, cfg0.prox_mu, cfg0.local_steps,
                     cfg0.local_batch, self.yogi, cfg0.use_agg_kernel,
                     cfg0.scaling_rule if cfg0.use_agg_kernel else None,
                     self._guard, self._faulty, self._lane,
                     self._attack, self._robust,
                     self._model_fns.loss, norm_d, out_dim)
        if self.mesh is not None:
            self._prog = _sharded_chunk_program(*prog_args, mesh)
        else:
            self._prog = _chunk_program(*prog_args, len(sims) == 1)
        # single-sim non-SAFA cohorts have a near-constant size, so exact
        # (unpadded) shapes cost at most a handful of compiles and remove
        # the pow2 bucket's up-to-2x wasted training rows — but only long
        # runs amortize those compiles; short runs, SAFA cohorts (sizes all
        # over the place), sweep batches and chunked/sharded dispatches
        # keep the shared padding buckets.  Padding is masked/discarded
        # everywhere, so the choice never affects results (bucket_block's
        # contract).
        self._exact = (self.mesh is None and self.k_rounds == 1
                       and len(sims) == 1 and not sel_spec.select_all
                       and cfg0.rounds >= 24)
        self._eval = _eval_program(self.spec, self._model_fns.evaluate)
        # the device memory ``stream_cohort`` weighs the vmapped cohort by
        self._bytes_limit = device_bytes_limit()
        self.done = [False] * s
        self._pending_free = []   # freed slots quarantined for one round

    def _stack_rows(self, rows: list, trailing: tuple, dtype) -> np.ndarray:
        """Place per-cell host rows into the (n_shards, s_loc + 1, ...)
        layout of the current placement (scratch/padding rows zero)."""
        pl = self.placement
        out = np.zeros((pl.n_shards, pl.s_loc + 1) + tuple(trailing), dtype)
        for i, row in enumerate(rows):
            out[pl.shard_of[i], pl.slot_of[i]] = row
        return out

    def _unpad_leaf(self, a):
        """Slice a D-blocked leaf back to the engine's true-D width
        (identity on the unpadded layout and on non-D leaves like the
        yogi step counter)."""
        if (self.d_pad != self.d and np.ndim(a)
                and np.shape(a)[-1] == self.d_pad):
            return a[..., :self.d]
        return a

    # ------------------------------------------------------------------
    def run(self, transfer_guard: bool = False):
        """Drive every round, then finalize.  ``transfer_guard=True`` wraps
        the round loop in ``jax.transfer_guard("disallow")``: every upload
        the pipeline performs is an explicit ``device_put``, so any
        *implicit* host transfer sneaking into the hot path raises — the
        CI smoke (and ``--profile`` benches) run in this mode."""
        if self._start_round == 0:
            for sim in self.sims:
                sim._t_now = 0.0
        if transfer_guard:
            with jax.transfer_guard("disallow"):
                self._run_rounds()
        else:
            self._run_rounds()
        return self.finalize()

    def _run_rounds(self):
        r = self._start_round
        fps = [sim.fault_plan for sim in self.sims
               if sim.fault_plan is not None]
        while r < self.cfg0.rounds and not all(self.done):
            # a chunk is K prescheduled rounds, broken early at eval
            # boundaries so evaluation / early stop / Oort feedback keep
            # their exact round semantics
            rounds = []
            while len(rounds) < self.k_rounds:
                rounds.append(r)
                if self.sims[0].eval_due(r):
                    break
                r += 1
            r = rounds[-1] + 1
            self._run_chunk(rounds)
            # checkpoint / crash hooks at chunk boundaries only, so a
            # resumed run re-enters at a boundary of the same chunk
            # sequence the uninterrupted run walks
            r_done = rounds[-1]
            if (self.checkpoint_path and self.checkpoint_every
                    and r_done + 1 >= self._next_ckpt
                    and r_done + 1 < self.cfg0.rounds):
                with self.telemetry.span("checkpoint", round=r_done + 1):
                    self.checkpoint(r_done + 1)
                self._next_ckpt = r_done + 1 + self.checkpoint_every
            for fp in fps:
                if fp.crash_due(r_done):
                    # log + flush before the crash fires: a hard crash is a
                    # SIGKILL, so anything unflushed would be lost
                    self.telemetry.event("crash", round=int(r_done),
                                         mode=fp.crash_mode)
                    self.telemetry.flush()
                    fp.trigger_crash(r_done)

    # ------------------------------------------------------------------
    # The round driver: preschedule a K-round chunk (K=1 by default),
    # dispatch it as one program, run the post-dispatch tail
    # ------------------------------------------------------------------
    def _shard_of(self, i: int) -> int:
        return self.placement.shard_of[i] if self.mesh is not None else 0

    def _preschedule(self, r: int) -> Optional[_RoundWork]:
        """Run one round's host state machine to completion — plans,
        schedules, slot allocation, selector feedback, record append — so
        the next round's decisions can be taken before this round's device
        work has run.  (Oort feedback is deferred to post-dispatch; its
        presence forces K=1, so no later round preschedules before it.)"""
        sims = self.sims
        plans = {}
        for i, sim in enumerate(sims):
            if self.done[i]:
                continue
            p = sim._begin_round(r)
            self.stats.checked_in += sim.n_checked_in
            if p is not None:
                plans[i] = p
        if not plans:
            return None
        order = list(plans)
        scheds = {i: sims[i]._schedule_round(r, plans[i]) for i in order}
        surv = {i: np.nonzero(~np.isfinite(plans[i].drop_at))[0]
                for i in order}

        # participant-row placement: each s-shard's packed survivor rows
        # (cells in order, rows in plan order — the exact row packing
        # _materialize emits) split into balanced contiguous blocks over
        # the p-shards.  The trivial 1x1 placement doubles as the
        # unsharded path's row->packed-position map.
        rowq = {}
        for j in range(self.n_shards):
            rows_j = [(i, int(ri)) for i in order
                      if self._shard_of(i) == j for ri in surv[i]]
            off = 0
            for q, size in enumerate(split_balanced(len(rows_j),
                                                    self.n_pshards)):
                for loc in range(size):
                    rowq[rows_j[off + loc]] = (q, loc)
                off += size

        # slot management: release the previous round's quarantined slots,
        # then this round's allocs — a slot gathered this round is never a
        # scatter target this round, so the in-program scatter-then-gather
        # stays collision-free (see the cache comment in _round_body)
        if self.mesh is None:
            grow0 = self.cache.grow_events
            if self._pending_free:
                self.cache.free(self._pending_free)
            self._pending_free = _quarantine_frees(order, scheds)
            for i in order:
                sc = scheds[i]
                if sc.new_stale:
                    sc.slots, _ = self.cache.alloc(len(sc.new_stale))
            self.stats.dispatches["cache_grow"] += \
                self.cache.grow_events - grow0
        else:
            grow0 = self.accounts.grow_events
            for shard, slot in self._pending_free:
                self.accounts.free(shard, [slot])
            self._pending_free = _quarantine_frees(order, scheds)
            for i in order:
                sc = scheds[i]
                if sc.new_stale:
                    # a straggler caches on the (s, p) shard that trains
                    # its row this round — later rounds read it from there
                    # via the aggregation psum, wherever the cell's rows
                    # land by then
                    j = self.placement.shard_of[i]
                    slots = []
                    for (ri, _l, _a, _d) in sc.new_stale:
                        flat = j * self.n_pshards + rowq[(i, int(ri))][0]
                        s_ids, _ = self.accounts.alloc(flat, 1)
                        slots.append((flat, s_ids[0]))
                    sc.slots = slots
            self.stats.dispatches["cache_grow"] += \
                self.accounts.grow_events - grow0

        if not self._fetch_l2s:
            from repro.sim.engine import _InFlight
            for i in order:
                sim, sc = sims[i], scheds[i]
                sim._apply_feedback(r, sc, None)
                for (row_i, lid, arr, dur), slot in zip(sc.new_stale,
                                                        sc.slots):
                    sim.stale_cache.append(
                        _InFlight(lid, r, arr, dur, slot, 0.0))

        recs = {i: sims[i]._advance_round_state(
            r, plans[i].t_now, scheds[i].t_end, len(plans[i].chosen),
            len(scheds[i].fresh_rows), len(scheds[i].landing))
            for i in order}
        # telemetry: stale-cache occupancy must be read NOW — later rounds
        # of the same chunk mutate it before the dispatch runs.  (A feedback
        # selector's new stragglers are appended post-dispatch, so count
        # them in.)
        occ = {}
        if self._lane:
            for i in order:
                occ[i] = len(sims[i].stale_cache) + (
                    len(scheds[i].new_stale) if self._fetch_l2s else 0)
        return _RoundWork(r, order, plans, scheds, surv, recs, rowq, occ)

    def _materialize(self, works):
        """Build the chunk's packed index arrays: per round and per flat
        (s, p) shard, the same layout the single-round driver packs,
        padded to one chunk-global bucket set so the scan's inputs are
        rectangular.  Returns (ints (K, n_s * n_p, L), floats
        (K, n_s * n_p, F), shapes, offs) where ``offs[(k, i)]`` holds cell
        ``i``'s survivor rows' positions (aligned with ``surv[i]``) in the
        round-k loss/l2s vector flattened over (flat shard, local row).

        Aggregation-group metadata (cells, taus, fresh/valid masks, rules,
        betas) is replicated across a cell's p-shards — the post-psum
        weights pass must compute identically on all of them — while the
        gather columns (``fr_idx``/``sl_idx``) and the ownership mask
        (``agg_mask``) are per p-shard: a shard contributes exactly the
        operand columns whose delta row or cache slot it owns."""
        cfg0 = self.cfg0
        sims = self.sims
        tb = cfg0.local_steps * cfg0.local_batch
        n_p = self.n_pshards
        nflat = self.n_shards * n_p
        mesh = self.mesh
        if mesh is None:
            scratch = len(sims)
            trash = self.cache.trash_slot
            slot_of = lambda i: i
        else:
            scratch = self.placement.scratch_slot
            trash = self.accounts.trash_slot
            slot_of = self.placement.slot_of.__getitem__

        # chunk-global padding buckets (uniform scan/shard shapes)
        max_rows, max_g, nf_max, ns_max = 1, 1, 1, 0
        for w in works:
            rows_f, g_js = [0] * nflat, [0] * self.n_shards
            for (i, _ri), (q, _loc) in w.rowq.items():
                rows_f[self._shard_of(i) * n_p + q] += 1
            for i in w.order:
                sc = w.scheds[i]
                if sc.fresh_rows or sc.landing:
                    g_js[self._shard_of(i)] += 1
                    nf_max = max(nf_max, len(sc.fresh_rows))
                    ns_max = max(ns_max, len(sc.landing))
            max_rows = max(max_rows, *rows_f)
            max_g = max(max_g, *g_js)
        if self._exact:     # long serial runs: unpadded shapes (see __init__)
            r_b, g_b, nf_b = max_rows, max_g, nf_max
            ns_b = ns_max if ns_max else 0
        else:
            r_b = agg.bucket_block(max_rows, ROW_BLOCK)
            g_b = agg.bucket_pow2(max_g)
            nf_b = agg.bucket_block(nf_max, UPD_BLOCK)
            ns_b = agg.bucket_pow2(ns_max) if ns_max else 0
        n_b = nf_b + ns_b
        # a fully-populated single-round unsharded dispatch skips the
        # in-program padding masks entirely (they would be identities)
        all_valid = False
        if mesh is None and len(works) == 1:
            w0 = works[0]
            groups0 = [i for i in w0.order
                       if w0.scheds[i].fresh_rows or w0.scheds[i].landing]
            all_valid = bool(
                groups0 and g_b == len(groups0)
                and all(len(w0.scheds[i].fresh_rows) == nf_b
                        and len(w0.scheds[i].landing) == ns_b
                        for i in groups0))
        streamed = stream_cohort(self.d_pad, r_b, self._bytes_limit)
        if streamed:
            self._check_streamable(r_b)
            # rows this wide barely fit: free each Simulator's own starting
            # row, which the stacked parameters replaced and finalize
            # rewrites (``flat_params`` is stale during a run anyway)
            for sim in sims:
                sim.flat_params = None
        shapes = (r_b, tb, g_b, nf_b, ns_b, all_valid, streamed)
        trained = sum(len(w.rowq) for w in works)
        self.stats.trained_rows += trained
        self.stats.pad_rows += len(works) * nflat * r_b - trained
        if streamed:
            self.stats.streamed_rows += trained

        # a faulted batch appends the per-row corruption multipliers to the
        # floats buffer (static layout — pipeline_key keeps faulted and
        # clean cells in separate batches only via the guard config, so the
        # widening applies to the whole batch); the telemetry lane appends
        # its host-known per-group head fields after those
        nf_len = (2 * g_b + (r_b if self._faulty else 0)
                  + (N_LANE_HOST * g_b if self._lane else 0))
        floats_all = np.zeros((len(works), nflat, nf_len), np.float32)
        chunks = []
        offs = {}
        gmaps = {}      # (k_idx, shard j) -> that shard's group cell list
        for k_idx, w in enumerate(works):
            per_shard = []
            for j in range(self.n_shards):
                cells_j = [i for i in w.order if self._shard_of(i) == j]
                groups = [i for i in cells_j
                          if w.scheds[i].fresh_rows or w.scheds[i].landing]
                gmaps[(k_idx, j)] = groups
                # p-replicated aggregation-group metadata
                agg_cell = np.full(g_b, scratch, np.int32)
                agg_fresh = np.zeros((g_b, n_b), np.int32)
                agg_tau = np.zeros((g_b, n_b), np.int32)
                agg_valid = np.zeros((g_b, n_b), np.int32)
                rule_id = np.zeros(g_b, np.int32)
                has_g = np.zeros(g_b, np.int32)
                beta_g = np.zeros(g_b, np.float32)
                lr_g = np.zeros(g_b, np.float32)
                agg_att = (np.zeros((g_b, n_b), np.int32)
                           if self._attack is not None else None)
                for g, i in enumerate(groups):
                    sc, cfg = w.scheds[i], sims[i].cfg
                    for col in range(len(sc.fresh_rows)):
                        agg_fresh[g, col] = 1
                        agg_valid[g, col] = 1
                    for col, tau in enumerate(sc.landing_taus):
                        agg_tau[g, nf_b + col] = tau
                        agg_valid[g, nf_b + col] = 1
                    if agg_att is not None and sims[i].fault_plan is not None:
                        # per-column attacker flags by learner id: a stale
                        # column is flagged for the round the update LANDS
                        # (the server can only ever see landed rows)
                        n_fr = len(sc.fresh_rows)
                        lids = ([int(w.plans[i].chosen[ri])
                                 for ri in sc.fresh_rows]
                                + [f.learner_id for f in sc.landing])
                        fl = sims[i].fault_plan.attack_flags(w.r, lids)
                        agg_att[g, :n_fr] = fl[:n_fr]
                        agg_att[g, nf_b:nf_b + len(sc.landing)] = fl[n_fr:]
                    agg_cell[g] = slot_of(i)
                    rule_id[g] = RULE_ID[cfg.scaling_rule]
                    beta_g[g] = cfg.beta
                    lr_g[g] = cfg.server_lr
                    has_g[g] = 1
                    if mesh is not None and sc.landing:
                        # diagnostic: landings whose slot shard differs from
                        # some other column of the same group — operand rows
                        # the psum genuinely merges across shards
                        col_q = ([w.rowq[(i, int(ri))][0]
                                  for ri in sc.fresh_rows]
                                 + [f.delta[0] - j * n_p for f in sc.landing])
                        self.stats.cross_shard_landings += sum(
                            1 for f in sc.landing
                            if any(qc != f.delta[0] - j * n_p
                                   for qc in col_q))
                floats_j = np.concatenate([beta_g, lr_g])
                if self._lane:
                    # host half of the lane, p-replicated like the rest of
                    # the group metadata; the device echoes it back so the
                    # fetched lane row is self-contained
                    tele_j = np.zeros((g_b, N_LANE_HOST), np.float32)
                    for g, i in enumerate(groups):
                        sc = w.scheds[i]
                        tele_j[g] = (w.r, sc.t_end, len(w.plans[i].chosen),
                                     len(sc.fresh_rows), len(sc.landing),
                                     w.occ[i])

                # per-q buffers, filled in ONE pass over rows and columns
                # (a scan per shard would scale host packing with n_p)
                batch_q = [np.zeros((r_b, tb), np.int32) for _ in range(n_p)]
                rcell_q = [np.full(r_b, scratch, np.int32)
                           for _ in range(n_p)]
                rsub_q = [np.zeros(r_b, np.int32) for _ in range(n_p)]
                scat_q = [np.full(r_b, trash, np.int32) for _ in range(n_p)]
                fr_q = [np.zeros((g_b, nf_b), np.int32) for _ in range(n_p)]
                sl_q = [np.zeros((g_b, ns_b), np.int32) for _ in range(n_p)]
                mask_q = [np.zeros((g_b, n_b), np.int32) for _ in range(n_p)]
                fscale_q = ([np.ones(r_b, np.float32) for _ in range(n_p)]
                            if self._faulty else None)
                nloc_q = [0] * n_p
                for i in cells_j:
                    p, sc, sv = w.plans[i], w.scheds[i], w.surv[i]
                    fp_i = sims[i].fault_plan
                    fsc_i = (fp_i.scale_for(w.r, p.chosen)
                             if self._faulty and fp_i is not None
                             and fp_i.has_corruption else None)
                    if fsc_i is not None:
                        # surviving corrupt rows (NaN/Inf/scaled) this cell
                        # injects this round — logged to events.jsonl
                        bad = int(np.count_nonzero(fsc_i[sv] != 1.0))
                        if bad:
                            self.telemetry.event(
                                "fault", cell=self._labels[i],
                                round=int(w.r), corrupt_rows=bad)
                    cell_offs = offs.setdefault(
                        (k_idx, i), np.zeros(len(sv), np.int64))
                    for k_row, ri in enumerate(sv):
                        q, loc = w.rowq[(i, int(ri))]
                        batch_q[q][loc] = p.bidx[ri]
                        rcell_q[q][loc] = slot_of(i)
                        rsub_q[q][loc] = self.sub_idx[i]
                        if fsc_i is not None:
                            fscale_q[q][loc] = fsc_i[ri]
                        cell_offs[k_row] = (j * n_p + q) * r_b + loc
                        nloc_q[q] = max(nloc_q[q], loc + 1)
                    for (ri, _l, _a, _d), slot in zip(sc.new_stale,
                                                      sc.slots):
                        q, loc = w.rowq[(i, int(ri))]
                        scat_q[q][loc] = slot if mesh is None else slot[1]
                # operand gather columns land on their owner shard's arrays
                # (the ownership mask the psum reconstruction relies on)
                for g, i in enumerate(groups):
                    sc = w.scheds[i]
                    for col, ri in enumerate(sc.fresh_rows):
                        q, loc = w.rowq[(i, int(ri))]
                        fr_q[q][g, col] = loc
                        mask_q[q][g, col] = 1
                    for col, f in enumerate(sc.landing):
                        q = 0 if mesh is None else f.delta[0] - j * n_p
                        sl_q[q][g, col] = (f.delta if mesh is None
                                           else f.delta[1])
                        mask_q[q][g, nf_b + col] = 1
                for q in range(n_p):
                    if 0 < nloc_q[q] < r_b:   # padding replicates row 0
                        batch_q[q][nloc_q[q]:] = batch_q[q][0]
                        rcell_q[q][nloc_q[q]:] = rcell_q[q][0]
                        rsub_q[q][nloc_q[q]:] = rsub_q[q][0]
                    ints_parts = [batch_q[q].ravel(), rcell_q[q], rsub_q[q],
                                  scat_q[q], agg_cell, fr_q[q].ravel(),
                                  sl_q[q].ravel(), agg_tau.ravel(), rule_id,
                                  agg_fresh.ravel(), agg_valid.ravel(),
                                  mask_q[q].ravel(), has_g]
                    if agg_att is not None:
                        # attacker flags ride the ints buffer, p-replicated
                        # like the rest of the group metadata
                        ints_parts.append(agg_att.ravel())
                    if streamed:
                        # the streamed loop's trip count: the real rows
                        ints_parts.append(np.asarray([nloc_q[q]], np.int32))
                    per_shard.append(np.concatenate(ints_parts))
                    parts = [floats_j]
                    if self._faulty:
                        parts.append(fscale_q[q])
                    if self._lane:
                        parts.append(tele_j.ravel())
                    floats_all[k_idx, j * n_p + q] = (
                        np.concatenate(parts) if len(parts) > 1
                        else floats_j)
            chunks.append(np.stack(per_shard))
        ints_all = np.stack(chunks)        # already int32 throughout
        return ints_all, floats_all, shapes, offs, gmaps

    def _check_streamable(self, r_b: int) -> None:
        """A streamed round has no full aggregation operand, which these
        programs need; refuse them rather than materialize it."""
        bad = [name for name, on in (
            ("guarded aggregation", self._guard is not None),
            ("a robust aggregator", self._robust is not None),
            ("a coordinated attack", self._attack is not None),
            ("a device mesh (sweep or participant sharding)",
             self.mesh is not None),
            ("the telemetry lane (telemetry >= 2)", self._lane)) if on]
        if bad:
            raise NotImplementedError(
                f"rows of D = {self.d} do not fit the device as a vmapped "
                f"cohort of {r_b}, so the round streams its participants, "
                f"which cannot be combined with {', '.join(bad)}")

    def _put(self, ints, floats):
        """Upload a chunk's packed buffers; returns them on the device with
        the cache rows the dispatch takes."""
        if self.mesh is None:
            dev_ints, dev_floats = jax.device_put(
                (ints[:, 0], floats[:, 0]))
            cache_rows = self.cache.rows
        else:
            # the host accounting may have grown mid-chunk; bring the
            # device tensor to the final capacity before the dispatch
            # (appended slots only — existing local slot ids stay valid)
            if self.cache_rows.shape[1] != self.accounts.capacity + 1:
                from repro.sweeps.sharding import reshard_rows
                old_rows = self.cache_rows.shape[1]
                nflat = self.n_shards * self.n_pshards
                cmap = np.full(nflat * (self.accounts.capacity + 1),
                               old_rows - 1, np.int32)   # any defined row
                for j in range(nflat):
                    base_new = j * (self.accounts.capacity + 1)
                    base_old = j * old_rows
                    for sl in range(old_rows - 1):
                        cmap[base_new + sl] = base_old + sl
                self.cache_rows = reshard_rows(
                    self.cache_rows, cmap,
                    (nflat, self.accounts.capacity + 1),
                    self._cache_spec)
            dev_ints = jax.device_put(ints, self._chunk_spec)
            dev_floats = jax.device_put(floats, self._chunk_spec)
            cache_rows = self.cache_rows
        return dev_ints, dev_floats, cache_rows

    def _run_chunk(self, rounds) -> None:
        """Preschedule up to K rounds, dispatch them as one scan program,
        then run the post-dispatch tail (Oort feedback, eval fill, early
        stop, shard repack) for the chunk."""
        works = []
        with self.telemetry.span("schedule", rounds=len(rounds)):
            for r in rounds:
                w = self._preschedule(r)
                if w is not None:
                    works.append(w)
        if not works:
            return
        sims = self.sims
        with self.telemetry.span("pack", rounds=len(works)):
            ints, floats, shapes, offs, gmaps = self._materialize(works)

        with self.telemetry.span("put", rounds=len(works)):
            dev_ints, dev_floats, cache_rows = self._put(ints, floats)
            self.stats.h2d_bytes += ints.nbytes + floats.nbytes
            self.stats.dispatches["round"] += 1
            self.stats.rounds += len(works)
        with self.telemetry.span("dispatch", rounds=len(works)), \
                self.telemetry.tracer.step("round", works[0].r):
            (params, cache_rows, self.opt_state, _losses, l2s, gstats,
             lanes) = self._prog(self.params, cache_rows, self.opt_state,
                                 self.x_tr, self.y_tr, dev_ints, dev_floats,
                                 shapes)
        self.params = params
        if self.mesh is None:
            self.cache.rows = cache_rows
        else:
            self.cache_rows = cache_rows

        # --- guard/robust-stats attribution (active programs only) -------
        lane_np = None
        with self.telemetry.span("fetch"):
            if self._guard is not None or self._robust is not None:
                g_np = np.asarray(jax.device_get(gstats))
                self.stats.d2h_bytes += g_np.nbytes
                g_b = shapes[2]
                for k_idx, w in enumerate(works):
                    # unsharded: (g_b, 6); sharded: (nflat * g_b, 6) with
                    # flat shard f = j * n_p + q owning [f*g_b, (f+1)*g_b)
                    # — gstats are p-replicated: read each group's q=0 copy
                    flat = g_np[k_idx].reshape(-1, 6)
                    for j in range(self.n_shards):
                        for g, i in enumerate(gmaps[(k_idx, j)]):
                            nf, nnorm, _surv, applied, rrej, rtrim = (
                                int(x) for x in
                                flat[(j * self.n_pshards) * g_b + g])
                            # single writer for guard/robust accounting:
                            # the session increments the registry counters
                            # (stats.guard is a view) and forwards to the
                            # per-sim Accounting
                            if self._guard is not None:
                                self.telemetry.note_guard(
                                    sims[i].acct, nf, nnorm, bool(applied))
                            if self._robust is not None:
                                self.telemetry.note_robust(
                                    sims[i].acct, rrej, rtrim)

            if self._lane:
                lane_np = np.asarray(jax.device_get(lanes))
                self.stats.d2h_bytes += lane_np.nbytes

            # --- deferred Oort feedback (K forced to 1) -------------------
            if self._fetch_l2s:
                from repro.sim.engine import _InFlight
                l2s_np = np.asarray(jax.device_get(l2s))
                self.stats.d2h_bytes += l2s_np.nbytes
                self.stats.feedback_fetches += 1
                (w,) = works
                l2s_flat = l2s_np[0].ravel()  # (flat shard, local row) order
                for i in w.order:
                    sim, sc = sims[i], w.scheds[i]
                    l2s_i = np.zeros(w.plans[i].k, np.float32)
                    l2s_i[w.surv[i]] = l2s_flat[offs[(0, i)]]
                    sim._apply_feedback(w.r, sc, l2s_i)
                    for (row_i, lid, arr, dur), slot in zip(sc.new_stale,
                                                            sc.slots):
                        sim.stale_cache.append(_InFlight(
                            lid, w.r, arr, dur, slot,
                            sim._stat_util(row_i, l2s_i)))

        # --- eval fill + early stop at the chunk's eval boundary ----------
        wl = works[-1]
        if sims[wl.order[0]].eval_due(wl.r):
            with self.telemetry.span("eval", round=wl.r):
                self._eval_fill(wl)

        # --- per-round telemetry events (after eval, so the chunk's eval
        # round carries its accuracy/loss) ---------------------------------
        if self._lane:
            g_b = shapes[2]
            for k_idx, w in enumerate(works):
                flat = lane_np[k_idx].reshape(-1, LANE_WIDTH)
                rows = {}
                for j in range(self.n_shards):
                    for g, i in enumerate(gmaps[(k_idx, j)]):
                        rows[i] = flat[(j * self.n_pshards) * g_b + g]
                for i in w.order:
                    row = rows.get(i)
                    if row is None:
                        # nothing aggregated for this cell this round (no
                        # fresh rows, no landings): the host half is still
                        # known, the device stats are genuinely zero
                        sc = w.scheds[i]
                        row = np.zeros(LANE_WIDTH, np.float32)
                        row[:N_LANE_HOST] = (w.r, sc.t_end,
                                             len(w.plans[i].chosen),
                                             len(sc.fresh_rows),
                                             len(sc.landing), w.occ[i])
                    ev = self.telemetry.round_event(self._labels[i], row,
                                                    w.recs[i])
                    sims[i].acct.round_events.append(ev)
            self.telemetry.flush()
        if self.mesh is not None:
            self._maybe_repack()

    def _eval_fill(self, wl) -> None:
        """Deferred eval at the chunk's eval boundary: batched accuracy/loss
        for the live cells, round-record fill, accuracy-target early stop."""
        sims = self.sims
        l_b = agg.bucket_pow2(len(wl.order))
        cells = wl.order + [wl.order[0]] * (l_b - len(wl.order))
        if self.mesh is None:
            rows = np.asarray(cells, np.int32)
            eval_params = self.params
        else:
            rows = np.asarray([self.placement.flat_row(i)
                               for i in cells], np.int32)
            eval_params = self.params.reshape(-1, self.d_pad)
        packed = np.concatenate([rows,
                                 self.sub_idx[np.asarray(cells)]])
        packed = (jax.device_put(packed) if self.mesh is None
                  else jax.device_put(packed, self._rep_spec))
        self.stats.dispatches["eval"] += 1
        a, lo = self._eval(eval_params, packed, self.x_te, self.y_te)
        acc = np.asarray(jax.device_get(a))
        loss = np.asarray(jax.device_get(lo))
        self.stats.h2d_bytes += 2 * rows.nbytes
        self.stats.d2h_bytes += acc.nbytes + loss.nbytes
        for ei, i in enumerate(wl.order):
            sims[i]._fill_round_eval(wl.recs[i], acc[ei], loss[ei],
                                     progress=self.progress)
            if sims[i]._target_reached():
                sims[i].acct.stopped_early = True
                self.done[i] = True

    # ------------------------------------------------------------------
    # Crash-safe snapshots (chaos harness): the full batch state at a
    # chunk boundary, as plain host objects — resumable bit-exactly
    # ------------------------------------------------------------------
    def snapshot(self, r_next: int) -> dict:
        """Host snapshot of every sim's state with ``r_next`` the first
        round a resume will run.  Taken only at chunk boundaries, so a
        resumed pipeline re-enters the identical chunk sequence; stale
        rows are gathered off the device cache and re-seated on resume
        (slot ids never affect values, only placement)."""
        sims = self.sims
        # parameter / optimizer rows leave at the engine's true-D width
        # (the padded tail is derivable zero); stale rows stay at the
        # cache width — a resume rebuilds the pipeline from the same cfg,
        # so the re-seating cache has the identical d_pad
        unpad = lambda a: a[..., :self.d]
        if self.mesh is None:
            params_np = np.asarray(jax.device_get(self.params))
            cache_np = np.asarray(jax.device_get(self.cache.rows))
            opt_np = (jax.tree.map(lambda a: np.asarray(jax.device_get(a)),
                                   self.opt_state) if self.yogi else None)
            row_of = lambda i: unpad(params_np[i])
            opt_of = ((lambda i: jax.tree.map(
                lambda a: self._unpad_leaf(a[i]), opt_np))
                if self.yogi else (lambda i: None))
            slot_row = lambda slot: cache_np[slot]
        else:
            flat = unpad(np.asarray(
                jax.device_get(self.params)).reshape(-1, self.d_pad))
            cache_np = np.asarray(
                jax.device_get(self.cache_rows)).reshape(-1, self.d_pad)
            rows_loc = self.accounts.capacity + 1
            opt_np = (jax.tree.map(lambda a: np.asarray(jax.device_get(a)),
                                   self.opt_state) if self.yogi else None)

            def row_of(i):
                if i in self._saved:
                    return unpad(np.asarray(self._saved[i][0]))
                return flat[self.placement.flat_row(i)]

            def opt_of(i):
                if not self.yogi:
                    return None
                if i in self._saved:
                    return jax.tree.map(
                        lambda a: self._unpad_leaf(np.asarray(a)),
                        self._saved[i][1])
                fr = self.placement.flat_row(i)
                return jax.tree.map(
                    lambda a: self._unpad_leaf(
                        a.reshape((-1,) + a.shape[2:])[fr]), opt_np)

            slot_row = lambda sl: cache_np[sl[0] * rows_loc + sl[1]]
        payload_sims = []
        for i, sim in enumerate(sims):
            rows = [np.asarray(slot_row(f.delta)) for f in sim.stale_cache]
            payload_sims.append({
                "cfg": dataclasses.asdict(sim.cfg),
                "state": sim.capture_state(stale_rows=rows),
                "flat_params": np.asarray(row_of(i)),
                "flat_opt_state": opt_of(i),
                "fault_plan": sim.fault_plan,
            })
        return {"version": 1, "kind": "pipeline", "next_round": int(r_next),
                "done": list(self.done), "sims": payload_sims,
                "labels": list(self._labels),
                # rounds.jsonl byte offset at this boundary: a resume into
                # the same telemetry dir truncates back to it, keeping the
                # round log inside the bitwise-resume contract
                "telemetry": self.telemetry.state()}

    def checkpoint(self, r_next: int) -> None:
        from repro.checkpoint.state import save_snapshot
        payload = self.snapshot(r_next)
        if self.checkpoint_wrap is not None:
            payload = self.checkpoint_wrap(payload)
        save_snapshot(self.checkpoint_path, payload)

    # ------------------------------------------------------------------
    # Shard-aware repacking (early-stopped cells vacate whole shard
    # bucket steps; live cells compact across shard boundaries)
    # ------------------------------------------------------------------
    def _maybe_repack(self) -> None:
        from repro.sweeps.sharding import Placement
        live = [i for i in range(len(self.sims)) if not self.done[i]]
        if not live:
            return
        new_pl = Placement.build(live, self.n_shards)
        if new_pl.s_loc >= self.placement.s_loc:
            return
        with self.telemetry.span("repack", live=len(live)):
            self._repack(new_pl, live)

    def _repack(self, new_pl, live) -> None:
        from repro.sweeps.sharding import reshard_rows
        old_pl = self.placement
        self.stats.dispatches["repack"] += 1

        # 1. save the evicted (done) cells' final rows to host — their
        #    device rows disappear with the shrink; finalize reads these
        evict = [i for i in old_pl.shard_of
                 if self.done[i] and i not in self._saved]
        if evict:
            # replicated: the jitted gather reads sharded operands, so a
            # single-device index array would force an implicit reshard
            idx = jax.device_put(
                np.asarray([old_pl.flat_row(i) for i in evict], np.int32),
                self._rep_spec)
            fetch = _row_fetch_program()
            rows = np.asarray(jax.device_get(fetch(self.params, idx)))
            opt_rows = None
            if self.yogi:
                opt_rows = jax.tree.map(
                    lambda a: np.asarray(jax.device_get(fetch(a, idx))),
                    self.opt_state)
            self.stats.d2h_bytes += rows.nbytes
            for k, i in enumerate(evict):
                self._saved[i] = (
                    rows[k],
                    jax.tree.map(lambda a: a[k], opt_rows)
                    if self.yogi else None)

        # 2. migrate params / optimizer rows into the compacted layout
        head = (self.n_shards, new_pl.s_loc + 1)
        pmap = np.full(new_pl.total_rows, old_pl.scratch_flat(0), np.int32)
        for i in live:
            pmap[new_pl.flat_row(i)] = old_pl.flat_row(i)
        self.params = reshard_rows(self.params, pmap, head, self._shard_spec)
        if self.yogi:
            self.opt_state = jax.tree.map(
                lambda a: reshard_rows(a, pmap, head, self._shard_spec),
                self.opt_state)

        # 3. rebuild the sharded cache: every live in-flight entry gets a
        #    slot on its cell's new s-shard — staying on its p-shard, so
        #    the participant partition survives the compaction —
        #    (allocation may grow capacity), then one gather moves the rows
        n_p = self.n_pshards
        nflat = self.n_shards * n_p
        new_acc = ShardedSlotAccounts(nflat, capacity=self.accounts.capacity)
        moves = []                        # (in-flight entry, old flat row)
        old_rows_loc = self.accounts.capacity + 1
        for i in live:
            shard = new_pl.shard_of[i]
            for f in self.sims[i].stale_cache:
                old_flat, old_slot = f.delta
                new_flat = shard * n_p + old_flat % n_p
                slots, _ = new_acc.alloc(new_flat, 1)
                f.delta = (new_flat, slots[0])
                moves.append((f, old_flat * old_rows_loc + old_slot))
        new_rows_loc = new_acc.capacity + 1
        # default: shard 0's old trash row — any defined row does (padding
        # slots are always scatter-written before they are ever gathered)
        cmap = np.full(nflat * new_rows_loc, old_rows_loc - 1,
                       np.int32)
        for f, old_flat_row in moves:
            shard, slot = f.delta
            cmap[shard * new_rows_loc + slot] = old_flat_row
        self.cache_rows = reshard_rows(
            self.cache_rows, cmap, (nflat, new_rows_loc),
            self._cache_spec)
        self.accounts = new_acc
        self._pending_free = []   # old slot ids are meaningless now
        self.placement = new_pl

    # ------------------------------------------------------------------
    def finalize(self):
        """Write the device state back to the Simulators and finalize each.
        After this the pipeline's donated-buffer chain ends; the returned
        Accountings are the same objects ``Simulator.run`` yields."""
        with self.telemetry.span("finalize", cells=len(self.sims)):
            return self._write_back()

    def _write_back(self):
        accts = []
        if self.mesh is None:
            for i, sim in enumerate(self.sims):
                sim.flat_params = self.params[i, :self.d]
                if self.yogi:
                    sim.flat_opt_state = jax.tree.map(
                        lambda x: self._unpad_leaf(x[i]), self.opt_state)
                accts.append(sim._finalize())
            return accts
        flat = self.params.reshape(-1, self.d_pad)
        for i, sim in enumerate(self.sims):
            if i in self._saved:
                row, opt_row = self._saved[i]
                sim.flat_params = jnp.asarray(row)[:self.d]
                if self.yogi:
                    sim.flat_opt_state = jax.tree.map(
                        lambda a: self._unpad_leaf(jnp.asarray(a)), opt_row)
            else:
                fr = self.placement.flat_row(i)
                sim.flat_params = flat[fr, :self.d]
                if self.yogi:
                    sim.flat_opt_state = jax.tree.map(
                        lambda a: self._unpad_leaf(
                            a.reshape((-1,) + a.shape[2:])[fr]),
                        self.opt_state)
            accts.append(sim._finalize())
        return accts
