"""Host-facing entry points: full SAA aggregation through the Pallas kernels.

The default path is the fused single-launch kernel (deviation partials,
in-kernel Eq. 2 weights, weighted aggregate in one grid traversal);
``fused=False`` keeps the original two-launch pipeline (partials kernel ->
host O(n) weights -> aggregate kernel) for A/B comparison.

These wrappers are deliberately *not* jitted: D is padded to the fused
kernels' column quantum (``col_quantum``; the two-launch pair's ``D_BLK``)
and (by default) the participant axis is padded to a power-of-two
bucket on the host, so repeated calls with varying fresh+stale counts reuse
one compiled kernel per bucket instead of recompiling per exact shape.
``interpret=None`` auto-detects the backend (compiled on TPU, interpreter
elsewhere).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import bucket_pad
from repro.core.staleness import EPS, SCALING_RULES
from repro.kernels.staleness_agg.staleness_agg import (
    D_BLK, col_quantum, deviation_partials, fused_staleness_aggregate,
    fused_staleness_apply, sweep_fused_staleness_aggregate,
    sweep_fused_staleness_apply, weighted_aggregate)


def staleness_aggregate(updates, fresh, tau, *, rule: str = "relay",
                        beta: float = 0.35, interpret: bool | None = None,
                        fused: bool = True, bucketed: bool = True):
    """updates: (n, D) any-D fp32; fresh: (n,) bool; tau: (n,) int.

    Returns (aggregate (D,), weights (n,)).
    """
    n, D = np.shape(updates)
    if fused:
        u, fr, ta, valid = bucket_pad(updates, fresh, tau, bucketed=bucketed,
                                      lane_block=col_quantum(D))
        agg, w = fused_staleness_aggregate(u, fr, ta, np.float32(beta),
                                           rule=rule, interpret=interpret,
                                           valid=valid)
        return agg[:D], w[:n]
    u = jnp.pad(jnp.asarray(updates, jnp.float32), ((0, 0), (0, (-D) % D_BLK)))
    fresh = jnp.asarray(fresh, bool)
    num, den = deviation_partials(u, fresh, interpret=interpret)
    lam = jnp.where(fresh, 0.0, num / (den + EPS))
    lam_max = jnp.max(jnp.where(~fresh, lam, 0.0))
    w_stale = SCALING_RULES[rule](jnp.asarray(tau, jnp.int32), lam, lam_max, beta)
    w = jnp.where(fresh, 1.0, w_stale)
    w = w / jnp.maximum(w.sum(), EPS)
    agg = weighted_aggregate(w, u, interpret=interpret)
    return agg[:D], w


def sweep_staleness_aggregate(updates, fresh, tau, *, valid=None,
                              rule: str = "relay", beta=0.35,
                              interpret: bool | None = None):
    """Batched SAA over a sweep axis: updates (S, n, any-D) fp32; fresh/tau
    (S, n); ``valid`` masks padded participant slots (default: all real);
    ``beta`` is a scalar or a (S,) per-simulation vector.

    Returns (aggregate (S, D), weights (S, n)) from ONE kernel launch over a
    (S, phase, D-block) grid — the sweep-grid extension of the fused kernel.
    """
    s, n, d = np.shape(updates)
    if valid is None:
        valid = np.ones((s, n), bool)
    u = np.zeros((s, n, d + ((-d) % col_quantum(d))), np.float32)
    u[:, :, :d] = np.asarray(updates)
    beta_vec = np.broadcast_to(np.asarray(beta, np.float32), (s,))
    agg, w = sweep_fused_staleness_aggregate(
        u, np.asarray(fresh), np.asarray(tau), beta_vec, np.asarray(valid),
        rule=rule, interpret=interpret)
    return agg[:, :d], w


def sweep_staleness_apply(params, updates, fresh, tau, *, valid=None,
                          rule: str = "relay", beta=0.35, server_lr=1.0,
                          interpret: bool | None = None):
    """Batched fused server step over a sweep axis: params (S, any-D) fp32,
    updates (S, n, any-D); ``beta``/``server_lr`` scalars or (S,) vectors.

    Returns (new_params (S, D), weights (S, n)) from ONE launch over a
    (S, phase, D-block) grid with the params buffer aliased input->output —
    the sweep-axis extension of ``staleness_apply``.
    """
    s, n, d = np.shape(updates)
    if valid is None:
        valid = np.ones((s, n), bool)
    dp = d + ((-d) % col_quantum(d))
    u = np.zeros((s, n, dp), np.float32)
    u[:, :, :d] = np.asarray(updates)
    p = np.zeros((s, dp), np.float32)
    p[:, :d] = np.asarray(params)
    scal = np.stack([np.broadcast_to(np.asarray(beta, np.float32), (s,)),
                     np.broadcast_to(np.asarray(server_lr, np.float32), (s,))],
                    axis=1)
    new_p, w = sweep_fused_staleness_apply(
        p, u, np.asarray(fresh), np.asarray(tau), np.asarray(valid), scal,
        rule=rule, interpret=interpret)
    return new_p[:, :d], w


def staleness_apply(params, updates, fresh, tau, *, rule: str = "relay",
                    beta: float = 0.35, server_lr: float = 1.0,
                    interpret: bool | None = None, bucketed: bool = True):
    """Fused server step on a flat parameter vector.

    params: (D,) fp32; updates: (n, D). Returns (new_params (D,), weights (n,))
    with ``new_params = params + server_lr * (w @ updates)`` computed in the
    same single grid traversal as the weights (params aliased input->output).
    """
    n, D = np.shape(updates)
    u, fr, ta, valid = bucket_pad(updates, fresh, tau, bucketed=bucketed,
                                  lane_block=col_quantum(D))
    p = np.zeros(u.shape[1], np.float32)
    p[:D] = np.asarray(params)
    new_p, w = fused_staleness_apply(p, u, fr, ta, np.float32(beta),
                                     np.float32(server_lr), rule=rule,
                                     interpret=interpret, valid=valid)
    return new_p[:D], w[:n]
