"""SAA weight-scaling rules (paper §4.2.4, Eq. 2).

Given a round's fresh updates F and stale updates S (delayed tau_s rounds):

  Equal :  w_s = 1
  DynSGD:  w_s = 1 / (tau_s + 1)                    (Jiang et al., 2017)
  AdaSGD:  w_s = exp(-(tau_s + 1))                  (Damaskinos et al., 2020)
  RELAY :  w_s = (1-beta)/(tau_s+1) + beta * (1 - exp(-Lam_s / Lam_max))   (Eq. 2)

with the privacy-preserving deviation score
  Lam_s = || u_hat_F - (u_s + n_F u_hat_F) / (n_F + 1) ||^2 / || u_hat_F ||^2.

Fresh updates always get w_f = 1; the final coefficients are w_i / sum_j w_j.

All functions are jittable over *stacked flat* updates ``U (n, D)`` with a
boolean ``fresh`` mask — this is the oracle for the fused Pallas kernel in
``repro.kernels.staleness_agg``.  ``counted_staleness_weights_by_id`` takes
a fresh *count* per row instead, so that one row can stand for the mean of
``n_F`` fresh updates (the streamed round's operand).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-12


def fresh_average(updates: jnp.ndarray, fresh: jnp.ndarray) -> jnp.ndarray:
    """updates: (n, D); fresh: (n,) bool. Returns u_hat_F (D,) (zeros if no fresh)."""
    n_f = fresh.sum()
    s = jnp.where(fresh[:, None], updates, 0.0).sum(axis=0)
    return s / jnp.maximum(n_f, 1)


def deviation_scores(updates: jnp.ndarray, fresh: jnp.ndarray) -> jnp.ndarray:
    """Lam_s per update (Eq. 2 numerator/denominator); 0 for fresh entries."""
    u_hat = fresh_average(updates, fresh)
    n_f = fresh.sum().astype(updates.dtype)
    mixed = (updates + n_f * u_hat[None, :]) / (n_f + 1.0)
    num = jnp.sum((u_hat[None, :] - mixed) ** 2, axis=-1)
    den = jnp.sum(u_hat ** 2) + EPS
    lam = num / den
    return jnp.where(fresh, 0.0, lam)


def _rule_equal(tau, lam, lam_max, beta):
    return jnp.ones_like(tau, dtype=jnp.float32)


def _rule_dynsgd(tau, lam, lam_max, beta):
    return 1.0 / (tau.astype(jnp.float32) + 1.0)


def _rule_adasgd(tau, lam, lam_max, beta):
    return jnp.exp(-(tau.astype(jnp.float32) + 1.0))


def _rule_relay(tau, lam, lam_max, beta):
    damp = 1.0 / (tau.astype(jnp.float32) + 1.0)
    boost = 1.0 - jnp.exp(-lam / jnp.maximum(lam_max, EPS))
    return (1.0 - beta) * damp + beta * boost


SCALING_RULES = {
    "equal": _rule_equal,
    "dynsgd": _rule_dynsgd,
    "adasgd": _rule_adasgd,
    "relay": _rule_relay,
}

# stable rule indexing for traced rule selection (``staleness_weights_by_id``)
RULE_ORDER = tuple(SCALING_RULES)
RULE_ID = {r: i for i, r in enumerate(RULE_ORDER)}


def staleness_weights(updates: jnp.ndarray, fresh: jnp.ndarray, tau: jnp.ndarray,
                      *, rule: str = "relay", beta: float = 0.35,
                      valid: jnp.ndarray | None = None) -> jnp.ndarray:
    """Normalized aggregation coefficients w_hat (n,).

    updates: (n, D) flat updates; fresh: (n,) bool; tau: (n,) staleness in rounds
    (0 for fresh); valid: optional (n,) mask for padded slots.
    """
    if valid is None:
        valid = jnp.ones_like(fresh)
    lam = deviation_scores(updates, fresh & valid)
    stale_mask = (~fresh) & valid
    lam_max = jnp.max(jnp.where(stale_mask, lam, 0.0))
    w_stale = SCALING_RULES[rule](tau, lam, lam_max, beta)
    w = jnp.where(fresh, 1.0, w_stale)
    w = jnp.where(valid, w, 0.0)
    return w / jnp.maximum(w.sum(), EPS)


def staleness_weights_by_id(updates, fresh, tau, rule_id, *, beta=0.35,
                            valid=None):
    """``staleness_weights`` with the scaling rule as a *traced* operand.

    ``rule_id`` indexes ``RULE_ORDER`` and selects the rule via
    ``lax.switch``, so a sweep can mix scaling rules across its cells inside
    one compiled program.  The selected branch is the same rule function the
    static path calls — per-cell results are bit-identical to
    ``staleness_weights(..., rule=RULE_ORDER[rule_id])``.
    """
    if valid is None:
        valid = jnp.ones_like(fresh)
    lam = deviation_scores(updates, fresh & valid)
    stale_mask = (~fresh) & valid
    lam_max = jnp.max(jnp.where(stale_mask, lam, 0.0))
    w_stale = jax.lax.switch(rule_id, [SCALING_RULES[r] for r in RULE_ORDER],
                             tau, lam, lam_max, beta)
    w = jnp.where(fresh, 1.0, w_stale)
    w = jnp.where(valid, w, 0.0)
    return w / jnp.maximum(w.sum(), EPS)


def counted_staleness_weights_by_id(updates, fresh_n, tau, rule_id, *,
                                    beta=0.35, valid=None):
    """Eq. 2's normalized coefficients when a row may stand for several
    fresh updates: ``fresh_n`` (n,) float counts the fresh updates a row
    is the mean of (0 for a stale row).  With counts of 0 and 1 this is
    ``staleness_weights_by_id``; with the fresh mean of ``n_F`` updates in
    one row of count ``n_F`` it gives that row the weight ``n_F`` (the sum
    of its rows' weights) and every stale row its exact Eq. 2 weight, since
    ``Lam_s`` needs only ``u_hat_F`` and ``n_F``."""
    if valid is None:
        valid = jnp.ones(fresh_n.shape, bool)
    fresh = fresh_n > 0
    n_f = fresh_n.sum()
    u_hat = (fresh_n[:, None] * updates).sum(axis=0) / jnp.maximum(n_f, 1.0)
    mixed = (updates + n_f * u_hat[None, :]) / (n_f + 1.0)
    num = jnp.sum((u_hat[None, :] - mixed) ** 2, axis=-1)
    lam = jnp.where(fresh, 0.0, num / (jnp.sum(u_hat ** 2) + EPS))
    stale_mask = (~fresh) & valid
    lam_max = jnp.max(jnp.where(stale_mask, lam, 0.0))
    w_stale = jax.lax.switch(rule_id, [SCALING_RULES[r] for r in RULE_ORDER],
                             tau, lam, lam_max, beta)
    w = jnp.where(fresh, fresh_n, w_stale)
    w = jnp.where(valid, w, 0.0)
    return w / jnp.maximum(w.sum(), EPS)
