"""Fused staleness-aware aggregation (SAA, Eq. 2) Pallas TPU kernels.

Aggregating n participant updates of D parameters (D ~ 1e8+) is the server-side
hot-spot RELAY adds: a naive implementation materializes the mixed update
``(u_s + n_F u_hat)/(n_F+1)`` per straggler (n x D extra bytes).  The kernels
here never materialize the mixed tensor; three entry points:

  - ``deviation_partials`` / ``weighted_aggregate``: the original two-launch
    pair (deviation partials, then host-side weights, then a weighted matvec);
  - ``fused_staleness_aggregate``: ONE kernel launch, one grid traversal over a
    ``(phase, D-block)`` grid.  Phase 0 accumulates each update's deviation
    numerator and the ||u_hat||^2 denominator into resident VMEM accumulators;
    at the phase boundary the Eq. 2 weights are computed *in-kernel* (no host
    round-trip, O(n) work on the (n,1) accumulators); phase 1 streams U again
    for the weighted matvec ``w @ U``;
  - ``fused_staleness_apply``: same traversal, but phase 1 emits
    ``params + lr * (w @ U)`` with the params buffer aliased input->output, so
    the server step is a single in-place kernel.

All passes are grid-sequential with accumulator outputs (constant index maps
keep the (n,1)/(1,1) accumulators VMEM-resident across the whole grid), the
TPU-idiomatic replacement for the GPU's atomics-based reductions.

``interpret=None`` on every entry point auto-detects the backend: compiled on
TPU, interpreter elsewhere (CPU tests / CI).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.staleness import EPS, SCALING_RULES
from repro.kernels import resolve_interpret

D_BLK = 2048  # lane-aligned (16 x 128); (n<=64) x 2048 fp32 = 512 KB per operand


def _deviation_increments(u, fresh):
    """Eq. 2 deviation partials for one (n, D_BLK) tile.

    u: (n, D_BLK) fp32; fresh: (n, 1) fp32 fresh counts (1 a fresh row, n_F
    a fresh mean, 0 a stale row).  Returns the tile's
    contribution (num (n, 1), den (1, 1)) — the single implementation of the
    partials math shared by every kernel variant.
    """
    n_f = jnp.maximum(fresh.sum(), 1.0)
    u_hat = (u * fresh).sum(axis=0, keepdims=True) / n_f      # (1, D_BLK)
    mixed = (u + n_f * u_hat) / (n_f + 1.0)
    num = ((u_hat - mixed) ** 2).sum(axis=1, keepdims=True)   # (n, 1)
    den = (u_hat ** 2).sum().reshape(1, 1)
    return num, den


def _deviation_kernel(u_ref, fresh_ref, num_ref, den_ref):
    """Accumulate per-update deviation partials over D blocks.

    u_ref: (n, D_BLK) fp32; fresh_ref: (n, 1) fp32 {0,1}
    num_ref: (n, 1) accumulator; den_ref: (1, 1) accumulator.
    """
    i = pl.program_id(0)
    num, den = _deviation_increments(u_ref[...], fresh_ref[...])

    @pl.when(i == 0)
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    num_ref[...] += num
    den_ref[...] += den


def _aggregate_kernel(w_ref, u_ref, out_ref):
    """out[D_BLK] = w (1, n) @ U (n, D_BLK)."""
    out_ref[...] = jnp.dot(w_ref[...], u_ref[...],
                           preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Single-traversal fused kernel
# ---------------------------------------------------------------------------


def _accumulate_partials(u, fresh, num_ref, den_ref):
    """Deviation partials for one (n, D_BLK) tile into the accumulators."""
    num, den = _deviation_increments(u, fresh)
    num_ref[...] += num
    den_ref[...] += den


def _compute_weights(rule, fresh, tau, beta, num, den, valid):
    """Eq. 2 normalized weights from the accumulated partials — all (n, 1).

    ``valid`` masks bucket-padding rows (zero weight, excluded from the
    stale max), mirroring ``core.staleness.staleness_weights``'s mask.
    A fresh row weighs ``fresh``: 1 for one update, ``n_F`` for a row that
    is the mean of ``n_F`` fresh updates (the deviation partials then take
    ``n_F`` as the fresh count too), as in
    ``core.staleness.counted_staleness_weights_by_id``.
    """
    lam = jnp.where(fresh > 0, 0.0, num / (den + EPS))
    stale = (fresh <= 0) & (valid > 0)
    lam_max = jnp.max(jnp.where(stale, lam, 0.0))
    w_stale = SCALING_RULES[rule](tau, lam, lam_max, beta)
    w = jnp.where(fresh > 0, fresh, w_stale)
    w = jnp.where(valid > 0, w, 0.0)
    return w / jnp.maximum(w.sum(), EPS)


def _make_fused_kernel(rule: str):
    def kernel(u_ref, fresh_ref, tau_ref, valid_ref, beta_ref,
               num_ref, den_ref, w_ref, out_ref):
        p = pl.program_id(0)      # phase: 0 = partials, 1 = aggregate
        i = pl.program_id(1)      # D block
        fresh = fresh_ref[...]    # (n, 1) fp32 {0, 1}

        @pl.when((p == 0) & (i == 0))
        def _init():
            num_ref[...] = jnp.zeros_like(num_ref)
            den_ref[...] = jnp.zeros_like(den_ref)
            w_ref[...] = jnp.zeros_like(w_ref)

        @pl.when(p == 0)
        def _partials():
            _accumulate_partials(u_ref[...], fresh, num_ref, den_ref)
            # keep the revisited output block defined on every grid step
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when((p == 1) & (i == 0))
        def _weights():
            w = _compute_weights(rule, fresh, tau_ref[...], beta_ref[0, 0],
                                 num_ref[...], den_ref[...], valid_ref[...])
            w_ref[...] = w.reshape(w_ref.shape)

        @pl.when(p == 1)
        def _agg():
            out_ref[...] = jnp.dot(w_ref[...], u_ref[...],
                                   preferred_element_type=jnp.float32)

    return kernel


def _make_fused_apply_kernel(rule: str):
    def kernel(params_ref, u_ref, fresh_ref, tau_ref, valid_ref, scal_ref,
               out_ref, num_ref, den_ref, w_ref):
        p = pl.program_id(0)
        i = pl.program_id(1)
        fresh = fresh_ref[...]

        @pl.when((p == 0) & (i == 0))
        def _init():
            num_ref[...] = jnp.zeros_like(num_ref)
            den_ref[...] = jnp.zeros_like(den_ref)
            w_ref[...] = jnp.zeros_like(w_ref)

        @pl.when(p == 0)
        def _partials():
            _accumulate_partials(u_ref[...], fresh, num_ref, den_ref)
            # copy-through: the output buffer aliases params, so phase 0's
            # write-back must preserve the values phase 1 re-reads
            out_ref[...] = params_ref[...]

        @pl.when((p == 1) & (i == 0))
        def _weights():
            w = _compute_weights(rule, fresh, tau_ref[...], scal_ref[0, 0],
                                 num_ref[...], den_ref[...], valid_ref[...])
            w_ref[...] = w.reshape(w_ref.shape)

        @pl.when(p == 1)
        def _apply():
            agg = jnp.dot(w_ref[...], u_ref[...],
                          preferred_element_type=jnp.float32)
            out_ref[...] = params_ref[...] + scal_ref[0, 1] * agg

    return kernel


def _make_sweep_fused_kernel(rule: str):
    """Fused SAA kernel with a leading sweep-grid axis: grid (S, phase, D
    blocks).  Each simulation ``s`` owns its own accumulator blocks (index
    maps select row ``s``), re-initialized at its (phase 0, block 0) step, so
    one launch aggregates a whole sweep's round with per-cell Eq. 2 weights
    and per-cell beta."""
    def kernel(u_ref, fresh_ref, tau_ref, valid_ref, beta_ref,
               num_ref, den_ref, w_ref, out_ref):
        p = pl.program_id(1)      # phase: 0 = partials, 1 = aggregate
        i = pl.program_id(2)      # D block
        fresh = fresh_ref[0]      # (n, 1) fp32 {0, 1}

        @pl.when((p == 0) & (i == 0))
        def _init():
            num_ref[...] = jnp.zeros_like(num_ref)
            den_ref[...] = jnp.zeros_like(den_ref)
            w_ref[...] = jnp.zeros_like(w_ref)

        @pl.when(p == 0)
        def _partials():
            num, den = _deviation_increments(u_ref[0], fresh)
            num_ref[0] += num
            den_ref[0] += den
            # keep the revisited output block defined on every grid step
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when((p == 1) & (i == 0))
        def _weights():
            w = _compute_weights(rule, fresh, tau_ref[0], beta_ref[0, 0, 0],
                                 num_ref[0], den_ref[0], valid_ref[0])
            w_ref[...] = w.reshape(w_ref.shape)

        @pl.when(p == 1)
        def _agg():
            out_ref[0] = jnp.dot(w_ref[0], u_ref[0],
                                 preferred_element_type=jnp.float32)

    return kernel


def _make_sweep_fused_apply_kernel(rule: str):
    """Sweep-axis fused SAA **server step**: grid (S, phase, D blocks), the
    params buffer aliased input->output.  Phase 0 accumulates each cell's
    deviation partials (copying params through to the aliased output so the
    revisited blocks stay defined); phase 1 computes the per-cell Eq. 2
    weights in-kernel and emits ``params + lr_s * (w_s @ U_s)`` — the whole
    sweep's aggregation *and* batched server apply in one launch."""
    def kernel(params_ref, u_ref, fresh_ref, tau_ref, valid_ref, scal_ref,
               out_ref, num_ref, den_ref, w_ref):
        p = pl.program_id(1)      # phase: 0 = partials, 1 = apply
        i = pl.program_id(2)      # D block
        fresh = fresh_ref[0]      # (n, 1) fp32 {0, 1}

        @pl.when((p == 0) & (i == 0))
        def _init():
            num_ref[...] = jnp.zeros_like(num_ref)
            den_ref[...] = jnp.zeros_like(den_ref)
            w_ref[...] = jnp.zeros_like(w_ref)

        @pl.when(p == 0)
        def _partials():
            num, den = _deviation_increments(u_ref[0], fresh)
            num_ref[0] += num
            den_ref[0] += den
            # copy-through: the output aliases params, so phase 0's
            # write-back must preserve the values phase 1 re-reads
            out_ref[...] = params_ref[...]

        @pl.when((p == 1) & (i == 0))
        def _weights():
            w = _compute_weights(rule, fresh, tau_ref[0], scal_ref[0, 0, 0],
                                 num_ref[0], den_ref[0], valid_ref[0])
            w_ref[...] = w.reshape(w_ref.shape)

        @pl.when(p == 1)
        def _apply():
            agg = jnp.dot(w_ref[0], u_ref[0],
                          preferred_element_type=jnp.float32)
            out_ref[0] = params_ref[0] + scal_ref[0, 0, 1] * agg

    return kernel


@functools.partial(jax.jit, static_argnames=("rule", "interpret"))
def sweep_fused_staleness_apply(params, updates, fresh, tau, valid, scal, *,
                                rule="relay", interpret=None):
    """Batched fused server step: new_params[s] = params[s] + lr_s * (w_s @ U_s).

    params: (S, D) fp32, D % D_BLK == 0, aliased input->output; updates:
    (S, n, D) fp32; fresh/valid: (S, n) bool (``fresh`` may instead count
    the fresh updates a row is the mean of, ``_compute_weights``); tau:
    (S, n) int; scal: (S, 2)
    fp32 rows ``(beta_s, server_lr_s)``.  One kernel launch computes every
    cell's deviation partials, in-kernel Eq. 2 weights and aggregate, and
    applies the aggregate to the cell's parameter row in place.  Returns
    (new_params (S, D), weights (S, n)); all-invalid cells get zero weights
    and therefore keep their parameter bits.

    Per-cell operands enter the kernel with a unit middle axis (params
    ``(S, 1, D)``, scal ``(S, 1, 2)``) so that the last two dims of every
    block equal the array's, which the TPU lowering requires for S > 1.
    """
    interpret = resolve_interpret(interpret)
    s, n, d = updates.shape
    assert d % D_BLK == 0 and params.shape == (s, d)
    grid = (s, 2, d // D_BLK)
    new_params, num, den, w = pl.pallas_call(
        _make_sweep_fused_apply_kernel(rule),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, D_BLK), lambda s_, p, i: (s_, 0, i)),
            pl.BlockSpec((1, n, D_BLK), lambda s_, p, i: (s_, 0, i)),
            pl.BlockSpec((1, n, 1), lambda s_, p, i: (s_, 0, 0)),
            pl.BlockSpec((1, n, 1), lambda s_, p, i: (s_, 0, 0)),
            pl.BlockSpec((1, n, 1), lambda s_, p, i: (s_, 0, 0)),
            pl.BlockSpec((1, 1, 2), lambda s_, p, i: (s_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, D_BLK), lambda s_, p, i: (s_, 0, i)),
            pl.BlockSpec((1, n, 1), lambda s_, p, i: (s_, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda s_, p, i: (s_, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda s_, p, i: (s_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((s, n, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, 1, n), jnp.float32),
        ],
        input_output_aliases={0: 0},
        interpret=interpret,
    )(params.astype(jnp.float32)[:, None],
      updates.astype(jnp.float32),
      fresh.astype(jnp.float32)[..., None],
      tau.astype(jnp.float32)[..., None],
      valid.astype(jnp.float32)[..., None],
      scal.astype(jnp.float32)[:, None])
    return new_params[:, 0], w[:, 0]


@functools.partial(jax.jit, static_argnames=("rule", "interpret"))
def sweep_fused_staleness_aggregate(updates, fresh, tau, beta, valid, *,
                                    rule="relay", interpret=None):
    """updates: (S, n, D) fp32, D % D_BLK == 0; fresh/valid: (S, n) bool;
    tau: (S, n) int; beta: (S,) per-simulation Eq. 2 averaging weight.

    One kernel launch aggregates S simulations' rounds: per-cell deviation
    partials, in-kernel per-cell Eq. 2 weights, per-cell weighted aggregate.
    Returns (aggregate (S, D), weights (S, n)); all-invalid cells produce
    zero weights and a zero aggregate row.  beta enters as ``(S, 1, 1)``
    for the TPU block rule (see ``sweep_fused_staleness_apply``).
    """
    interpret = resolve_interpret(interpret)
    s, n, d = updates.shape
    assert d % D_BLK == 0
    grid = (s, 2, d // D_BLK)
    num, den, w, out = pl.pallas_call(
        _make_sweep_fused_kernel(rule),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n, D_BLK), lambda s_, p, i: (s_, 0, i)),
            pl.BlockSpec((1, n, 1), lambda s_, p, i: (s_, 0, 0)),
            pl.BlockSpec((1, n, 1), lambda s_, p, i: (s_, 0, 0)),
            pl.BlockSpec((1, n, 1), lambda s_, p, i: (s_, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda s_, p, i: (s_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n, 1), lambda s_, p, i: (s_, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda s_, p, i: (s_, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda s_, p, i: (s_, 0, 0)),
            pl.BlockSpec((1, 1, D_BLK), lambda s_, p, i: (s_, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, n, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, 1, n), jnp.float32),
            jax.ShapeDtypeStruct((s, 1, d), jnp.float32),
        ],
        interpret=interpret,
    )(updates.astype(jnp.float32),
      fresh.astype(jnp.float32)[..., None],
      tau.astype(jnp.float32)[..., None],
      valid.astype(jnp.float32)[..., None],
      beta.astype(jnp.float32)[:, None, None])
    return out[:, 0], w[:, 0]


@functools.partial(jax.jit, static_argnames=("rule", "interpret"))
def fused_staleness_aggregate(updates, fresh, tau, beta, *, rule="relay",
                              interpret=None, valid=None):
    """updates: (n, D) fp32, D % D_BLK == 0; fresh: (n,) bool; tau: (n,) int.

    One kernel launch: deviation partials, in-kernel Eq. 2 weights, weighted
    aggregate. ``valid`` (n,) bool masks bucket-padding rows (default: all).
    Returns (aggregate (D,), weights (n,)).
    """
    interpret = resolve_interpret(interpret)
    n, D = updates.shape
    assert D % D_BLK == 0
    if valid is None:
        valid = jnp.ones((n,), bool)
    grid = (2, D // D_BLK)
    num, den, w, out = pl.pallas_call(
        _make_fused_kernel(rule),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, D_BLK), lambda p, i: (0, i)),
            pl.BlockSpec((n, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((n, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((n, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda p, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((1, n), lambda p, i: (0, 0)),
            pl.BlockSpec((1, D_BLK), lambda p, i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
        ],
        interpret=interpret,
    )(updates.astype(jnp.float32),
      fresh.astype(jnp.float32)[:, None],
      tau.astype(jnp.float32)[:, None],
      valid.astype(jnp.float32)[:, None],
      jnp.asarray(beta, jnp.float32).reshape(1, 1))
    return out[0], w[0]


@functools.partial(jax.jit, static_argnames=("rule", "interpret"))
def fused_staleness_apply(params, updates, fresh, tau, beta, server_lr, *,
                          rule="relay", interpret=None, valid=None):
    """Fused server step: new_params = params + lr * (w @ U).

    The params buffer is aliased input->output at the kernel level
    (``input_output_aliases``), so the update is in-place within the program.
    params: (D,) fp32 (D % D_BLK == 0). Returns (new_params (D,), weights (n,)).
    """
    interpret = resolve_interpret(interpret)
    n, D = updates.shape
    assert D % D_BLK == 0 and params.shape == (D,)
    if valid is None:
        valid = jnp.ones((n,), bool)
    scal = jnp.stack([jnp.asarray(beta, jnp.float32),
                      jnp.asarray(server_lr, jnp.float32)]).reshape(1, 2)
    new_params, num, den, w = pl.pallas_call(
        _make_fused_apply_kernel(rule),
        grid=(2, D // D_BLK),
        in_specs=[
            pl.BlockSpec((1, D_BLK), lambda p, i: (0, i)),
            pl.BlockSpec((n, D_BLK), lambda p, i: (0, i)),
            pl.BlockSpec((n, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((n, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((n, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((1, 2), lambda p, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, D_BLK), lambda p, i: (0, i)),
            pl.BlockSpec((n, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda p, i: (0, 0)),
            pl.BlockSpec((1, n), lambda p, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, D), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        input_output_aliases={0: 0},
        interpret=interpret,
    )(params.astype(jnp.float32)[None, :],
      updates.astype(jnp.float32),
      fresh.astype(jnp.float32)[:, None],
      tau.astype(jnp.float32)[:, None],
      valid.astype(jnp.float32)[:, None],
      scal)
    return new_params[0], w[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def deviation_partials(updates, fresh, *, interpret=None):
    """updates: (n, D) fp32, D % D_BLK == 0; fresh: (n,) bool.

    Returns (num (n,), den ()) such that Lam = num / (den + eps).
    """
    interpret = resolve_interpret(interpret)
    n, D = updates.shape
    assert D % D_BLK == 0
    grid = (D // D_BLK,)
    num, den = pl.pallas_call(
        _deviation_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, D_BLK), lambda i: (0, i)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(updates, fresh.astype(jnp.float32)[:, None])
    return num[:, 0], den[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def weighted_aggregate(weights, updates, *, interpret=None):
    """weights: (n,) fp32; updates: (n, D) -> (D,)."""
    interpret = resolve_interpret(interpret)
    n, D = updates.shape
    assert D % D_BLK == 0
    out = pl.pallas_call(
        _aggregate_kernel,
        grid=(D // D_BLK,),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((n, D_BLK), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, D_BLK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(weights[None, :], updates)
    return out[0]
