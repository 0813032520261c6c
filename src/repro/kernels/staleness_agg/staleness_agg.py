"""Fused staleness-aware aggregation (SAA, Eq. 2) Pallas TPU kernels.

Aggregating n participant updates of D parameters (D ~ 1e8+) is the server-side
hot-spot RELAY adds: a naive implementation materializes the mixed update
``(u_s + n_F u_hat)/(n_F+1)`` per straggler (n x D extra bytes).  The kernels
here never materialize the mixed tensor; three entry points:

  - ``deviation_partials`` / ``weighted_aggregate``: the original two-launch
    pair (deviation partials, then host-side weights, then a weighted matvec)
    over fixed ``D_BLK``-column blocks;
  - ``sweep_fused_staleness_aggregate``: ONE kernel launch, one grid
    traversal per simulation over a ``(sim, phase, column block)`` grid.
    Phase 0 accumulates each update's deviation numerator and the
    ||u_hat||^2 denominator into resident VMEM accumulators; at the phase
    boundary the Eq. 2 weights are computed *in-kernel* (no host
    round-trip, O(n) work on the accumulators); phase 1 streams U again for
    the weighted sum ``w @ U``;
  - ``sweep_fused_staleness_apply``: same traversal, but phase 1 emits
    ``params + lr * (w @ U)`` with the params buffer aliased input->output,
    so the server step is a single in-place kernel.

``fused_staleness_aggregate`` / ``fused_staleness_apply`` are the one-
simulation calls of the same kernel.

The fused kernels size their column block to the operand (``col_block``):
the widest power-of-two multiple of ``D_BLK`` that divides the width and
whose double-buffered blocks fit ``BLOCK_VMEM``.  The streamed round's
operand, the fresh mean and a few stale rows, then takes 64K-128K columns
a step instead of 2,048, so the grid has a few thousand steps instead of a
few hundred thousand; a cohort of tens of rows keeps a few thousand
columns.  Each row is viewed as ``(D / 128, 128)``, so a block's last two
dims are dense whatever the number of rows.  Phase 0 reads only U: the
params and out blocks stay parked at block 0 until phase 1 streams them.

The accumulators stay VMEM-resident across the grid, the TPU-idiomatic
replacement for the GPU's atomics-based reductions.

``interpret=None`` on every entry point auto-detects the backend: compiled on
TPU, interpreter elsewhere (CPU tests / CI).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.staleness import EPS, SCALING_RULES
from repro.kernels import resolve_interpret

D_BLK = 2048  # narrowest column block: 16 x 128 lanes, dense in a (D/128, 128) view
# the bytes the double-buffered blocks of one fused grid step may take: a
# quarter of the v5e's 16 MiB scoped VMEM, the rest for the step's temporaries
BLOCK_VMEM = 4 * 2**20
LANES = 128


def _widest_block(n: int) -> int:
    """The widest ``D_BLK * 2**k`` columns whose double-buffered float32
    blocks of an ``n``-row operand, its params and its out row fit
    ``BLOCK_VMEM``."""
    blk = D_BLK
    while 2 * 4 * (n + 2) * (2 * blk) <= BLOCK_VMEM:
        blk *= 2
    return blk


def col_block(n: int, d: int) -> int:
    """The fused kernels' column block for an ``(n, d)`` operand (``d`` a
    multiple of ``D_BLK``): the widest block ``BLOCK_VMEM`` allows for
    ``n`` rows, halved until it divides ``d``.  One row takes 131,072
    columns; more than about 120 rows keep ``D_BLK``."""
    assert d % D_BLK == 0, (d, D_BLK)
    blk = _widest_block(n)
    while d % blk:
        blk //= 2
    return blk


def col_quantum(d: int) -> int:
    """The multiple a row of width ``d`` is padded to for the fused kernels:
    a one-row operand's widest block, halved while the padding would add
    more than ``d / 1024`` columns, and never below ``D_BLK``.  So every
    block ``col_block`` picks for the padded width divides it, and the
    padding stays under 0.1% of the row (``D_BLK`` alone for small ``d``)."""
    q = _widest_block(1)
    while q > D_BLK and (-d) % q > d // 1024:
        q //= 2
    return q


def _deviation_increments(u, fresh):
    """Eq. 2 deviation partials for one column block.

    u: a block of each row, (n, D_BLK) or (n, cols / 128, 128) fp32;
    fresh: fp32 fresh counts (1 a fresh row, n_F a fresh mean, 0 a stale
    row), (n, 1) or, in every lane, (n, 1, 128).  Returns the block's
    contribution (num (n, 1[, 128]), den (1, 1[, 128])) — the single
    implementation of the partials math shared by every kernel variant.
    ``u_hat - mixed`` is ``(u_hat - u) / (n_F + 1)``: its squares are summed
    first and divided once.
    """
    def per_row(x):
        # a 3-D block's row sums stay in every lane: Mosaic lowers neither a
        # reduction over two tiled axes nor a broadcast over both at once
        x = x.sum(axis=1, keepdims=True)
        if x.ndim == 3:
            x = jnp.broadcast_to(x.sum(axis=2, keepdims=True), x.shape)
        return x

    n_f = jnp.maximum(fresh.sum(axis=0, keepdims=True), 1.0)
    u_hat = (u * fresh).sum(axis=0, keepdims=True) / n_f
    num = per_row((u_hat - u) ** 2) / (n_f + 1.0) ** 2
    den = per_row(u_hat ** 2)
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


def _compute_weights(rule, fresh, tau, beta, num, den, valid):
    """Eq. 2 normalized weights from the accumulated partials — all
    ``(n, 1, 128)``, one value in every lane of a row.

    ``valid`` masks bucket-padding rows (zero weight, excluded from the
    stale max), mirroring ``core.staleness.staleness_weights``'s mask.
    A fresh row weighs ``fresh``: 1 for one update, ``n_F`` for a row that
    is the mean of ``n_F`` fresh updates (the deviation partials then take
    ``n_F`` as the fresh count too), as in
    ``core.staleness.counted_staleness_weights_by_id``.
    """
    lam = jnp.where(fresh > 0, 0.0, num / (den + EPS))
    stale = (fresh <= 0) & (valid > 0)
    lam_max = jnp.max(jnp.where(stale, lam, 0.0), axis=0, keepdims=True)
    w_stale = SCALING_RULES[rule](tau, lam, lam_max, beta)
    w = jnp.where(fresh > 0, fresh, w_stale)
    w = jnp.where(valid > 0, w, 0.0)
    return w / jnp.maximum(w.sum(axis=0, keepdims=True), EPS)


def _make_sweep_kernel(rule: str, apply: bool):
    """The fused SAA kernel over a grid (S, phase, column block): each
    simulation ``s`` re-initializes the accumulators at its (phase 0,
    block 0) step, so one launch aggregates a whole sweep's round with
    per-cell Eq. 2 weights and per-cell ``(beta, lr)``.  Phase 0
    accumulates the deviation partials; phase 1 computes the weights at
    its first block and emits ``w @ U``, or with ``apply``
    ``params + lr * (w @ U)`` into the aliased params buffer.  Row blocks
    are ``(cols / 128, 128)``; per-row values are ``(n, 1, 128)``, one value
    in every lane, so that they broadcast over a block's sublanes alone."""
    def kernel(*refs):
        if apply:
            params_ref, *refs = refs
        (u_ref, fresh_ref, tau_ref, valid_ref, scal_ref, out_ref, w_ref,
         num_ref, den_ref) = refs
        p = pl.program_id(1)      # phase: 0 = partials, 1 = aggregate
        i = pl.program_id(2)      # column block
        fresh = fresh_ref[0]      # (n, 1, 128)

        @pl.when((p == 0) & (i == 0))
        def _init():
            num_ref[...] = jnp.zeros_like(num_ref)
            den_ref[...] = jnp.zeros_like(den_ref)

        @pl.when(p == 0)
        def _partials():
            num, den = _deviation_increments(u_ref[0], fresh)
            num_ref[...] += num
            den_ref[...] += den

        @pl.when((p == 1) & (i == 0))
        def _weights():
            w_ref[0] = _compute_weights(rule, fresh, tau_ref[0],
                                        scal_ref[0, 0, 0], num_ref[...],
                                        den_ref[...], valid_ref[0])

        @pl.when(p == 1)
        def _aggregate():
            # float32 on the VPU: an in-kernel jnp.dot at the default
            # precision rounds U to bf16 on the TPU, even at weight 1
            agg = (w_ref[0] * u_ref[0]).sum(axis=0)
            if apply:
                agg = params_ref[0, 0] + scal_ref[0, 0, 1] * agg
            out_ref[0, 0] = agg

    return kernel


def _sweep_call(rule, interpret, updates, fresh, tau, valid, scal,
                params=None):
    """One launch of the fused kernel: ``updates`` (S, n, D), D a multiple
    of ``D_BLK``; ``fresh``/``tau``/``valid`` (S, n); ``scal`` (S, 2)
    rows ``(beta, lr)``; ``params`` (S, D) for the server step, aliased to
    the output.  Returns (out (S, D), weights (S, n))."""
    interpret = resolve_interpret(interpret)
    s, n, d = updates.shape
    blk = col_block(n, d)
    rows, cols = blk // LANES, d // LANES
    row_block = pl.BlockSpec((1, n, rows, LANES), lambda s_, p, i: (s_, 0, i, 0))
    # params and out stream in phase 1 only: parked at block 0 in phase 0
    one_block = pl.BlockSpec((1, 1, rows, LANES),
                             lambda s_, p, i: (s_, 0, i * p, 0))
    per_row = pl.BlockSpec((1, n, 1, LANES), lambda s_, p, i: (s_, 0, 0, 0))
    per_row_arr = lambda a: jnp.broadcast_to(
        a.astype(jnp.float32)[..., None, None], (s, n, 1, LANES))
    args = [updates.astype(jnp.float32).reshape(s, n, cols, LANES),
            per_row_arr(fresh), per_row_arr(tau), per_row_arr(valid),
            scal.astype(jnp.float32)[:, None]]
    in_specs = [row_block, per_row, per_row, per_row,
                pl.BlockSpec((1, 1, 2), lambda s_, p, i: (s_, 0, 0))]
    if params is not None:
        args.insert(0, params.astype(jnp.float32).reshape(s, 1, cols, LANES))
        in_specs.insert(0, one_block)
    out, w = pl.pallas_call(
        _make_sweep_kernel(rule, params is not None),
        grid=(s, 2, d // blk),
        in_specs=in_specs,
        out_specs=[one_block, per_row],
        out_shape=[jax.ShapeDtypeStruct((s, 1, cols, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((s, n, 1, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, 1, LANES), jnp.float32),
                        pltpu.VMEM((1, 1, LANES), jnp.float32)],
        input_output_aliases={0: 0} if params is not None else {},
        interpret=interpret,
    )(*args)
    return out.reshape(s, d), w[:, :, 0, 0]


@functools.partial(jax.jit, static_argnames=("rule", "interpret"))
def sweep_fused_staleness_apply(params, updates, fresh, tau, valid, scal, *,
                                rule="relay", interpret=None):
    """Batched fused server step: new_params[s] = params[s] + lr_s * (w_s @ U_s).

    params: (S, D) fp32, D % D_BLK == 0, aliased input->output; updates:
    (S, n, D) fp32; fresh/valid: (S, n) bool (``fresh`` may instead count
    the fresh updates a row is the mean of, ``_compute_weights``); tau:
    (S, n) int; scal: (S, 2) fp32 rows ``(beta_s, server_lr_s)``.  One
    kernel launch computes every cell's deviation partials, in-kernel Eq. 2
    weights and aggregate, and applies the aggregate to the cell's
    parameter row in place, over ``col_block(n, D)``-column blocks.
    Returns (new_params (S, D), weights (S, n)); all-invalid cells get zero
    weights and therefore keep their parameter bits.
    """
    assert params.shape == (updates.shape[0], updates.shape[2])
    return _sweep_call(rule, interpret, updates, fresh, tau, valid, scal,
                       params)


@functools.partial(jax.jit, static_argnames=("rule", "interpret"))
def sweep_fused_staleness_aggregate(updates, fresh, tau, beta, valid, *,
                                    rule="relay", interpret=None):
    """updates: (S, n, D) fp32, D % D_BLK == 0; fresh/valid: (S, n) bool;
    tau: (S, n) int; beta: (S,) per-simulation Eq. 2 averaging weight.

    One kernel launch aggregates S simulations' rounds: per-cell deviation
    partials, in-kernel per-cell Eq. 2 weights, per-cell weighted aggregate.
    Returns (aggregate (S, D), weights (S, n)); all-invalid cells produce
    zero weights and a zero aggregate row.  It takes the blocks the apply
    kernel takes, so its result applied is that kernel's, bit for bit.
    """
    scal = jnp.stack([beta, jnp.zeros_like(beta)], axis=1)
    return _sweep_call(rule, interpret, updates, fresh, tau, valid, scal)


@functools.partial(jax.jit, static_argnames=("rule", "interpret"))
def fused_staleness_aggregate(updates, fresh, tau, beta, *, rule="relay",
                              interpret=None, valid=None):
    """updates: (n, D) fp32, D % D_BLK == 0; fresh: (n,) bool; tau: (n,) int.

    One kernel launch: deviation partials, in-kernel Eq. 2 weights, weighted
    aggregate. ``valid`` (n,) bool masks bucket-padding rows (default: all).
    Returns (aggregate (D,), weights (n,)).
    """
    if valid is None:
        valid = jnp.ones(fresh.shape, bool)
    out, w = sweep_fused_staleness_aggregate(
        updates[None], fresh[None], tau[None],
        jnp.asarray(beta, jnp.float32).reshape(1), valid[None], rule=rule,
        interpret=interpret)
    return out[0], w[0]


@functools.partial(jax.jit, static_argnames=("rule", "interpret"))
def fused_staleness_apply(params, updates, fresh, tau, beta, server_lr, *,
                          rule="relay", interpret=None, valid=None):
    """Fused server step: new_params = params + lr * (w @ U).

    The params buffer is aliased input->output at the kernel level
    (``input_output_aliases``), so the update is in-place within the program.
    params: (D,) fp32 (D % D_BLK == 0). Returns (new_params (D,), weights (n,)).
    """
    if valid is None:
        valid = jnp.ones(fresh.shape, bool)
    scal = jnp.stack([jnp.asarray(beta, jnp.float32),
                      jnp.asarray(server_lr, jnp.float32)]).reshape(1, 2)
    new_params, w = sweep_fused_staleness_apply(
        params[None], updates[None], fresh[None], tau[None], valid[None],
        scal, rule=rule, interpret=interpret)
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
