"""Pinned telemetry schemas.

Every exported artifact (the in-program round-stats lane, the per-round
JSONL event log, the guard counters) has its field order pinned here so
downstream consumers — ``benchmarks/figures.py``, the CI smoke
validators, external dashboards — can rely on it.  Changing any tuple is
a schema break and must update ``tests/test_telemetry.py`` deliberately.
"""
from __future__ import annotations

# ---------------------------------------------------------------------------
# In-program round-stats lane (fused pipeline, ``SimConfig.telemetry >= 2``).
#
# One fp32 row per aggregation group per round, emitted as an extra
# ``lax.scan`` output alongside ``gstats`` and fetched only at chunk
# boundaries.  The first ``N_LANE_HOST`` fields are known on the host at
# pack time and ride through the floats buffer (the device echoes them so
# the lane is self-contained); the rest are computed in-program.
LANE_FIELDS = (
    # host pass-through (packed into the dispatch floats buffer)
    "round",                # simulated round index
    "sim_time",             # simulated clock at round end (hours)
    "cohort",               # learners selected this round
    "fresh",                # fresh (in-round) update rows aggregated
    "stale_landed",         # straggler rows landing this round (incl. replays)
    "cache_occupancy",      # stale-cache entries pending after scheduling
    # computed in-program, post-psum (no extra collective)
    "l2_min",               # update-row L2 norm, min over finite valid rows
    "l2_mean",              # ... mean
    "l2_max",               # ... max
    "nonfinite_rows",       # valid rows containing any non-finite entry
    # guard/robust columns (mirror gstats; zeros-but-survivors when
    # unguarded and non-robust)
    "rejected_nonfinite",   # rows rejected by the non-finite screen
    "rejected_norm",        # rows rejected by the norm-outlier screen
    "robust_rejected",      # rows the robust aggregator rejected (krum
                            # losers, norm_median_clip rejects)
    "robust_trimmed",       # rows trimmed per coordinate band (2*k_eff)
                            # or clipped by norm_median_clip
    "survivors",            # rows that entered the aggregate
    "applied",              # 1 if the update was applied (quorum met)
)
LANE_WIDTH = len(LANE_FIELDS)
# leading fields packed on the host into the widened floats buffer
N_LANE_HOST = 6

# lane fields serialized as ints in round events (the rest stay floats)
LANE_INT_FIELDS = frozenset((
    "round", "cohort", "fresh", "stale_landed", "cache_occupancy",
    "nonfinite_rows", "rejected_nonfinite", "rejected_norm",
    "robust_rejected", "robust_trimmed", "survivors", "applied",
))

# ---------------------------------------------------------------------------
# Per-round JSONL event log (``<telemetry-dir>/rounds.jsonl``).
#
# One event per (cell, recorded round), keys exactly in this order.  Only
# deterministic fields — no wall-clock — so the log joins the bitwise
# crash→resume contract: uninterrupted and crash→resume runs produce
# byte-identical files.  NaN accuracy/loss serialize as null.
ROUND_EVENT_KEYS = (
    "event",                # always "round"
    "cell",                 # cell / run label
    *LANE_FIELDS,
    # host-side accounting joined from the RoundRecord
    "resource_used",
    "resource_wasted",
    "unique_participants",
    "accuracy",             # null on non-eval rounds
    "loss",
)

# ---------------------------------------------------------------------------
# Registry counter names (single source of truth for guard accounting and
# the dispatch/transfer profile; ``PipelineStats`` is a view over these).
GUARD_COUNTERS = (
    "guard_rejected_nonfinite",
    "guard_rejected_norm",
    "guard_quorum_skips",
    "guard_robust_rejected",
    "guard_robust_trimmed",
)
PIPELINE_COUNTERS = (
    "pipeline_rounds",
    "pipeline_h2d_bytes",
    "pipeline_d2h_bytes",
    "pipeline_init_h2d_bytes",
    "pipeline_cross_shard_landings",
    "pipeline_feedback_fetches",
    "pipeline_trained_rows",    # packed rows that train (survivors)
    "pipeline_pad_rows",        # padding rows of the chosen shape bucket
    "pipeline_streamed_rows",   # rows trained one at a time (streamed round)
    "pipeline_checked_in",      # checked-in learners handed to selection
    "pipeline_saa_kernel_steps",  # SAA kernel grid steps launched
)
# Compile work seen while an enabled session is open
# (``repro.telemetry.compile``).
COMPILE_COUNTERS = (
    "compile_programs_lowered",     # jaxpr -> MLIR module lowerings
    "compile_backend_compiles",     # backend compiles, cache loads included
    "compile_cache_hits",           # persistent compilation cache hits
    "compile_cache_misses",         # ... and writes after a miss
)
DISPATCH_KINDS = ("round", "eval", "cache_grow", "repack")

# ---------------------------------------------------------------------------
# ``jax.named_scope`` names of the device round program (``sim/pipeline.py``)
# and the eval program; a profile's device ops carry them in their op path.
DEVICE_SCOPES = (
    "train",        # local training (gather of batches, SGD steps, delta)
    "fresh_sum",    # streamed round: fresh rows summed, then their mean
    "cache",        # straggler rows to their slots, operand gather
    "aggregate",    # guard/robust screens, Eq. 2 weights and aggregate
    "apply",        # server step
    "eval",         # held-out accuracy and loss
)

# ---------------------------------------------------------------------------
# Host-side tracer span names (Chrome trace-event JSON, Perfetto-loadable).
SPAN_NAMES = (
    "build",        # Simulator construction (recorded after the fact)
    "upload",       # RoundPipeline construction: device_puts of state+data
    "schedule",     # host prescheduling of a chunk of rounds
    "pack",         # packing dispatch int32/fp32 buffers
    "put",          # device_put of the packed buffers
    "dispatch",     # the fused round program (holds the profiler's
                    # StepTraceAnnotation "round", step = first round)
    "fetch",        # device_get of gstats / lane / l2s + attribution
    "eval",         # deferred eval fill + early-stop bookkeeping
    "repack",       # early-stop sweep-bucket repacking
    "checkpoint",   # snapshot write
    "finalize",     # device state written back, Simulator._finalize
    "compile",      # trace / lower / backend compile (jax.monitoring)
)
