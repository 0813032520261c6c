"""Per-coordinate trimmed-mean aggregation Pallas TPU kernel.

The robust coordinate-wise aggregators (``trimmed_mean``,
``coord_median`` — ``repro.robust``) need, per coordinate, the mean of
the sorted values inside the index band ``[k_eff, c - k_eff)``.  A full
per-column sort of the ``(n, D)`` operand is O(D·n log n) and Pallas has
no sort primitive; instead the kernel streams the rows and computes each
row's *rank* per coordinate (count of values strictly smaller, ties
broken by row index — exactly a stable sort's order), accumulating rows
whose rank falls inside the band.  O(n^2) per coordinate with n <= a few
hundred cohort rows, one grid traversal over ``(cell, D-block)``, no
host round-trip, and ``k_eff`` / ``c`` are *traced* per-cell scalars so
one compiled kernel serves every trim level and cohort size.

Excluded rows (invalid padding, screened rows, NaN scrub) arrive as
``+inf`` (``repro.robust.aggregators.weighted_rows``): their rank is
``>= c`` so they always fall past the band — appending them never
changes which finite values the band selects.

``interpret=None`` auto-detects the backend like ``staleness_agg``:
compiled on TPU, interpreter elsewhere (CPU tests / CI).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.staleness_agg.staleness_agg import D_BLK


def _trimmed_kernel(y_ref, kp_ref, out_ref):
    """One (cell, D-block) tile of the rank-select trimmed mean.

    y_ref: (1, n, D_BLK) fp32 rows; kp_ref: (1, 1, 2) fp32 ``[k_eff, c]``;
    out_ref: (1, 1, D_BLK) the band mean.
    """
    y = y_ref[0]                                    # (n, D_BLK)
    k = kp_ref[0, 0, 0]
    c = kp_ref[0, 0, 1]
    n = y.shape[0]
    ridx = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)  # TPU iota: ints

    def body(i, acc):
        # row i read from the ref: a dynamic slice of the loaded value has
        # no TPU lowering
        yi = y_ref[0, pl.ds(i, 1), :]                           # (1, D_BLK)
        less = (y < yi) | ((y == yi) & (ridx < i))
        rank = jnp.sum(less.astype(jnp.float32), axis=0, keepdims=True)
        inc = (rank >= k) & (rank < c - k)
        return acc + jnp.where(inc, yi, 0.0)

    acc = jax.lax.fori_loop(0, n, body,
                            jnp.zeros((1, y.shape[1]), jnp.float32))
    out_ref[0] = acc / jnp.maximum(c - 2.0 * k, 1.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sweep_trimmed_aggregate(y, k_eff, c, *, interpret=None):
    """Band means for S cells in one launch.

    y: (S, n, D) fp32 with excluded rows ``+inf``, D % D_BLK == 0;
    k_eff / c: (S,) int32 per-cell trim depth and valid-row count
    (traced — no recompile across trim levels).  Returns (S, D).  The
    per-cell ``[k_eff, c]`` pair and the output carry a unit middle axis so
    the last two dims of every block equal the array's (TPU block rule).
    """
    interpret = resolve_interpret(interpret)
    s, n, d = y.shape
    assert d % D_BLK == 0
    kp = jnp.stack([k_eff.astype(jnp.float32),
                    c.astype(jnp.float32)], axis=1)[:, None]
    out = pl.pallas_call(
        _trimmed_kernel,
        grid=(s, d // D_BLK),
        in_specs=[
            pl.BlockSpec((1, n, D_BLK), lambda s_, i: (s_, 0, i)),
            pl.BlockSpec((1, 1, 2), lambda s_, i: (s_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D_BLK), lambda s_, i: (s_, 0, i)),
        out_shape=jax.ShapeDtypeStruct((s, 1, d), jnp.float32),
        interpret=interpret,
    )(y.astype(jnp.float32), kp)
    return out[:, 0]
