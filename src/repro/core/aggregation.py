"""Stale-Synchronous FedAvg aggregation (paper Alg. 2) over parameter pytrees.

The server receives participant deltas (possibly delayed by tau rounds),
computes SAA coefficients (``repro.core.staleness``), and produces the weighted
aggregate that the server optimizer applies to the global model.

Two code paths:
- pytree path (host-side FL simulation; arbitrary structures),
- stacked-flat path (on-mesh training; feeds the fused Pallas kernel).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.staleness import (RULE_ID, counted_staleness_weights_by_id,
                                  staleness_weights, staleness_weights_by_id)


# ---------------------------------------------------------------------------
# Flatten helpers
# ---------------------------------------------------------------------------


def flatten_update(tree):
    """Pytree -> (flat fp32 vector, treedef+shapes for unflatten)."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    flat = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])
    return flat, (treedef, shapes, [l.dtype for l in leaves])


def make_flat_spec(tree):
    """Flatten spec (treedef, shapes, dtypes, offsets) without moving data.

    Compute once per model; reuse for every ``unflatten_update`` of the run —
    the flat fast path's round loop never re-derives it.  All-tuple (and thus
    hashable), so jitted helpers can be cached per spec across instances.

    ``offsets`` holds each leaf's start position in the flat vector plus a
    final total-D sentinel: ``flat[offsets[i]:offsets[i+1]]`` is leaf ``i``'s
    segment — the layer-blocked view large-D models (the LM zoo) and the
    D-blocked aggregation layout slice by.  Consumers that predate the
    offsets unpack ``spec[:3]``; a flat vector longer than ``offsets[-1]``
    is treated as block-padded and the tail is ignored.
    """
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(l.shape for l in leaves)
    offs, off = [], 0
    for s in shapes:
        offs.append(off)
        off += int(np.prod(s)) if s else 1
    return (treedef, shapes, tuple(l.dtype for l in leaves),
            tuple(offs) + (off,))


def flat_dim(spec) -> int:
    """Total flat vector length D for a spec from ``make_flat_spec``."""
    if len(spec) > 3:
        return int(spec[3][-1])
    _, shapes, _ = spec
    return int(sum(int(np.prod(s)) if s else 1 for s in shapes))


def unflatten_update(flat, spec):
    treedef, shapes, dtypes = spec[0], spec[1], spec[2]
    leaves, off = [], 0
    for shp, dt in zip(shapes, dtypes):
        n = int(np.prod(shp)) if shp else 1
        leaves.append(flat[off:off + n].reshape(shp).astype(dt))
        off += n
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------


def aggregate_updates(stacked: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """stacked: (n, D), weights: (n,) normalized -> (D,)."""
    return jnp.einsum("n,nd->d", weights, stacked)


def _waa(stacked, fresh, tau, valid, beta, *, rule):
    w = staleness_weights(stacked, fresh, tau, rule=rule, beta=beta, valid=valid)
    return aggregate_updates(stacked, w), w


_weights_and_aggregate = jax.jit(_waa, static_argnames=("rule",))


def _waa_by_id(stacked, fresh, tau, valid, beta, rule_id):
    w = staleness_weights_by_id(stacked, fresh, tau, rule_id, beta=beta,
                                valid=valid)
    return aggregate_updates(stacked, w), w


# the per-cell weights+aggregate unit the device-resident round pipeline
# vmaps inside its fused round program (repro.sim.pipeline); same code the
# batched sweep program below runs, so both paths share one set of numerics
weights_and_aggregate_by_id = _waa_by_id


def counted_weights_and_aggregate_by_id(stacked, fresh_n, tau, valid, beta,
                                        rule_id):
    """``weights_and_aggregate_by_id`` over rows that each stand for
    ``fresh_n`` fresh updates (``counted_staleness_weights_by_id``): the
    streamed round's fresh mean and its landing stale rows."""
    w = counted_staleness_weights_by_id(stacked, fresh_n, tau, rule_id,
                                        beta=beta, valid=valid)
    return aggregate_updates(stacked, w), w


@jax.jit
def _sweep_weights_and_aggregate(stacked, fresh, tau, valid, beta, rule_id):
    """vmap of the per-round weights+aggregate program over a leading sweep
    axis: stacked (S, n, D), masks (S, n), beta (S,), rule_id (S,) ->
    ((S, D), (S, n)).  The scaling rule is a traced per-cell operand
    (``lax.switch``), so cells mixing rules share this one compiled program;
    each cell's slice is bit-identical to the unbatched static-rule program
    on the same rows (rows are independent under vmap)."""
    return jax.vmap(_waa_by_id)(stacked, fresh, tau, valid, beta, rule_id)


def bucket_pow2(n: int) -> int:
    """Next power of two — the participant-axis padding bucket shared by the
    compiled aggregation path, the kernel path, and the engine's cohort
    padding (one compiled program per bucket, not per exact count)."""
    return 1 << (n - 1).bit_length()


def bucket_block(n: int, block: int) -> int:
    """Two-tier padding bucket: power-of-two up to ``block``, then multiples
    of ``block``.  Large axes (SAFA-style cohorts, sweep-packed rows) land
    within ``block - 1`` wasted slots instead of pow2's up-to-2x overshoot,
    while the number of distinct compiled shapes stays small.  Padding is
    masked/discarded everywhere, so bucket choice never affects results."""
    if n <= block:
        return bucket_pow2(n)
    return block * ((n + block - 1) // block)


def bucket_pad(updates, fresh, tau, *, bucketed: bool = True,
               lane_block: int = 0):
    """Host-side (numpy) padding of a round's updates for a compiled program.

    Pads the participant axis to ``bucket_pow2(n)`` zero rows (skipped when
    ``bucketed=False``) and, when ``lane_block`` > 0, the feature axis up to
    the next multiple of it.  Returns (updates, fresh, tau, valid) numpy
    arrays; ``valid`` masks the real rows.  Shared by the jnp fast path and
    the Pallas kernel wrappers so both pad identically.
    """
    n, D = np.shape(updates)
    m = bucket_pow2(n) if bucketed else n
    Dp = D + ((-D) % lane_block) if lane_block else D
    u = np.zeros((m, Dp), np.float32)
    u[:n, :D] = np.asarray(updates)
    fr = np.zeros(m, bool)
    fr[:n] = np.asarray(fresh)
    ta = np.zeros(m, np.int32)
    ta[:n] = np.asarray(tau)
    valid = np.arange(m) < n
    return u, fr, ta, valid


def stale_synchronous_aggregate_flat(stacked, fresh, tau, *, rule: str = "relay",
                                     beta: float = 0.35, use_kernel: bool = False,
                                     compiled: bool = True):
    """Aggregate already-stacked flat updates — the round engine's hot path.

    stacked: (n, D) fp32 rows (one per fresh/stale update); fresh: (n,) bool;
    tau: (n,) int staleness. Returns (aggregate (D,), weights (n,)).
    No per-update pytree traversal happens here: callers keep updates as flat
    rows from training to aggregation and unflatten once per round.

    ``compiled=True`` pads the participant axis to a power-of-two bucket
    (zero rows, masked out via ``staleness_weights``'s ``valid`` mask) and
    runs one jitted weights+aggregate program — without the bucketing, every
    new fresh+stale count would trigger a fresh XLA compile of the eager ops,
    which dominates the server step at scale.  ``compiled=False`` keeps the
    seed's unpadded eager evaluation (benchmark baseline).
    """
    n = np.shape(stacked)[0]
    if use_kernel:
        from repro.kernels.staleness_agg import ops as agg_ops
        return agg_ops.staleness_aggregate(stacked, fresh, tau, rule=rule,
                                           beta=beta, bucketed=compiled)
    if not compiled:
        stacked = jnp.asarray(stacked, jnp.float32)
        weights = staleness_weights(stacked, jnp.asarray(fresh, bool),
                                    jnp.asarray(tau, jnp.int32),
                                    rule=rule, beta=beta)
        return aggregate_updates(stacked, weights), weights
    # pad on host (numpy) — eager jnp.pad would itself compile per shape; the
    # single device transfer happens at the jit boundary below
    u, fr, ta, valid = bucket_pad(stacked, fresh, tau)
    agg, w = _weights_and_aggregate(u, fr, ta, valid, np.float32(beta),
                                    rule=rule)
    return agg, w[:n]


def sweep_bucket_pad(cell_updates, d: int):
    """Pad a sweep round's per-cell update stacks to one (S, n_b, D) tensor.

    cell_updates: length-S list; entry ``s`` is either ``None`` (no updates
    this round — the cell contributes all-invalid rows and a zero aggregate)
    or ``(rows, fresh, tau)`` with ``rows`` a list of (D,) fp32 vectors.
    The participant axis is padded to one shared ``bucket_block(n, 32)``
    bucket (power-of-two up to 32 slots, then multiples of 32) so the whole
    sweep reuses a compiled program per bucket; aggregation is
    padding-invariant (zero rows are masked by ``valid`` and contribute
    exact zeros to every reduction), so each cell's result is bit-identical
    to padding it to its own bucket.

    Returns numpy (U (S, n_b, d), fresh (S, n_b), tau (S, n_b),
    valid (S, n_b), has (S,)).
    """
    s_total = len(cell_updates)
    n_max = max([len(c[0]) for c in cell_updates if c is not None] + [1])
    n_b = bucket_block(n_max, 32)
    u = np.zeros((s_total, n_b, d), np.float32)
    fresh = np.zeros((s_total, n_b), bool)
    tau = np.zeros((s_total, n_b), np.int32)
    valid = np.zeros((s_total, n_b), bool)
    has = np.zeros(s_total, bool)
    for s, cell in enumerate(cell_updates):
        if cell is None:
            continue
        rows, fr, ta = cell
        n = len(rows)
        u[s, :n] = np.stack(rows)
        fresh[s, :n] = fr
        tau[s, :n] = ta
        valid[s, :n] = True
        has[s] = True
    return u, fresh, tau, valid, has


def sweep_aggregate_flat(stacked, fresh, tau, valid, beta, *,
                         rule="relay", use_kernel: bool = False):
    """SAA-aggregate S simulations' rounds in one batched program.

    stacked: (S, n, D) fp32 (typically from ``sweep_bucket_pad``); fresh/tau/
    valid: (S, n); beta: (S,) per-cell Eq. 2 averaging weights; ``rule`` is
    one rule name or a length-S sequence — mixed rules run in the same
    compiled program (per-cell ``lax.switch``).  Returns (aggregate (S, D),
    weights (S, n)).  ``use_kernel`` routes through the sweep-axis fused
    Pallas kernel (``kernels.staleness_agg``), which is compiled per rule
    and therefore requires a uniform one.  All-invalid cells produce an
    exactly-zero aggregate row (their weights normalize to 0).
    """
    s = np.shape(stacked)[0]
    rules = [rule] * s if isinstance(rule, str) else list(rule)
    if use_kernel:
        if len(set(rules)) != 1:
            raise ValueError("the sweep kernel is compiled per scaling rule; "
                             f"got mixed rules {sorted(set(rules))}")
        from repro.kernels.staleness_agg import ops as agg_ops
        return agg_ops.sweep_staleness_aggregate(stacked, fresh, tau,
                                                 valid=valid, rule=rules[0],
                                                 beta=beta)
    rule_id = np.array([RULE_ID[r] for r in rules], np.int32)
    return _sweep_weights_and_aggregate(
        stacked, np.asarray(fresh), np.asarray(tau), np.asarray(valid),
        np.asarray(beta, np.float32), rule_id)


# ---------------------------------------------------------------------------
# Guarded aggregation (chaos harness: screen rows before they are weighted)
# ---------------------------------------------------------------------------


def screen_rows(u, valid, *, clip=None, reject_mult=None, norm_d=None):
    """In-program screening of an update operand ``u`` (..., n, D).

    The one screening formula every guarded aggregation path runs — the
    engine's flat/legacy paths, the batched sweep program, and the fused
    round body — so rejection decisions are identical across substrates.
    Three screens, in order:

      1. non-finite reject: any NaN/Inf element invalidates the row;
      2. norm-outlier reject (``reject_mult``): rows whose squared L2 norm
         exceeds ``reject_mult**2`` times the median surviving squared norm;
      3. norm clip (``clip``): surviving rows are rescaled to L2 norm
         ``clip`` when they exceed it.

    Rejected rows are *zeroed*, not merely mask-flagged: deviation scores
    and the weighted einsum read every row downstream, and ``0 * NaN``
    would reintroduce the poison.  With all rows finite and ``clip`` /
    ``reject_mult`` inactive, the output is a bit-exact select of ``u``
    (``jnp.where`` under an all-true mask) — the guards-on/no-faults
    bit-parity guarantee rests on this.

    valid: (..., n) bool masking real rows (padding screens as invalid but
    is not counted).  Returns ``(u_screened, valid_out, n_nonfinite,
    n_norm_rejected, n_clipped)`` with int32 counts summed over the row
    axis.

    ``norm_d`` (D-blocked layouts): the finite test and the squared norms
    reduce over the leading ``norm_d`` columns only, so a block-padded
    operand screens bit-identically to its true-D slice (reducing across
    the appended zero columns would repartition the reduction and move
    bits); the clip rescale and the zeroing still apply to the full row.
    """
    u = jnp.asarray(u, jnp.float32)
    valid = jnp.asarray(valid, bool)
    u_t = u if norm_d is None else u[..., :norm_d]
    finite = jnp.isfinite(u_t).all(axis=-1)
    v1 = valid & finite
    n_nf = (valid & ~finite).sum(axis=-1).astype(jnp.int32)
    # rejected/padded rows get +inf norms: they sort last and never reach
    # the median index, which counts only surviving rows
    n2 = jnp.where(v1, jnp.sum(u_t * u_t, axis=-1), jnp.inf)
    if reject_mult is not None:
        srt = jnp.sort(n2, axis=-1)
        idx = jnp.maximum(v1.sum(axis=-1) - 1, 0) // 2
        med = jnp.take_along_axis(srt, idx[..., None], axis=-1)[..., 0]
        out = v1 & (n2 > (np.float32(reject_mult) ** 2) * med[..., None])
        v2 = v1 & ~out
        n_out = out.sum(axis=-1).astype(jnp.int32)
    else:
        v2 = v1
        n_out = jnp.zeros_like(n_nf)
    if clip is not None:
        c2 = np.float32(clip) * np.float32(clip)
        hit = v2 & (n2 > c2)
        scale = jnp.where(hit, np.float32(clip) / jnp.sqrt(n2),
                          jnp.float32(1.0))
        u = u * scale[..., None]
        n_clip = hit.sum(axis=-1).astype(jnp.int32)
    else:
        n_clip = jnp.zeros_like(n_nf)
    u = jnp.where(v2[..., None], u, 0.0)
    return u, v2, n_nf, n_out, n_clip


@functools.lru_cache(maxsize=16)
def _screen_fn(clip, reject_mult):
    return jax.jit(functools.partial(screen_rows, clip=clip,
                                     reject_mult=reject_mult))


def guarded_aggregate_flat(stacked, fresh, tau, *, rule: str = "relay",
                           beta: float = 0.35, use_kernel: bool = False,
                           compiled: bool = True, clip=None, reject_mult=None,
                           quorum: int = 1):
    """Screened, quorum-checked ``stale_synchronous_aggregate_flat``.

    Returns ``(agg (D,), weights (n,), info)`` where ``info`` holds the
    rejected-row counts (``nonfinite`` / ``norm``), ``clipped``,
    ``survivors``, and ``applied`` — False when survivors fall below
    ``quorum``, in which case the caller must carry params unchanged.

    When nothing is rejected or clipped, the call routes through the
    unguarded ``stale_synchronous_aggregate_flat`` with the caller's exact
    arguments, so guards-on/no-faults is bit-identical to guards-off on
    every route — including the Pallas kernel, which has no row-validity
    input and therefore only ever serves this clean case; screened
    aggregation always runs the jitted masked program.
    """
    n = int(np.shape(stacked)[0])
    u, fr, ta, valid = bucket_pad(stacked, fresh, tau, bucketed=compiled)
    u2, v2, n_nf, n_out, n_clip = _screen_fn(clip, reject_mult)(u, valid)
    n_nf = int(jax.device_get(n_nf))
    n_out = int(jax.device_get(n_out))
    n_clip = int(jax.device_get(n_clip))
    survivors = int(jax.device_get(v2.sum()))
    applied = survivors >= max(int(quorum), 1)
    info = {"nonfinite": n_nf, "norm": n_out, "clipped": n_clip,
            "survivors": survivors, "applied": applied}
    if n_nf == 0 and n_out == 0 and n_clip == 0:
        agg, w = stale_synchronous_aggregate_flat(
            stacked, fresh, tau, rule=rule, beta=beta,
            use_kernel=use_kernel, compiled=compiled)
        return agg, w, info
    agg, w = _weights_and_aggregate(u2, np.asarray(fr), np.asarray(ta),
                                    v2, np.float32(beta), rule=rule)
    return agg, w[:n], info


def stale_synchronous_aggregate(update_trees: Sequence, fresh: Sequence[bool],
                                tau: Sequence[int], *, rule: str = "relay",
                                beta: float = 0.35, use_kernel: bool = False,
                                compiled: bool = False):
    """Aggregate a round's fresh + stale update pytrees into a single delta tree.

    Thin wrapper over ``stale_synchronous_aggregate_flat`` for callers that
    still hold pytrees. Returns (aggregate_tree, weights).  Defaults to the
    eager (seed) evaluation: the stack lives on device here, and the compiled
    path's host-side bucket padding would force a device round trip — flat-row
    callers on the hot loop pass host arrays and default to ``compiled=True``.
    """
    assert len(update_trees) > 0
    flats, spec = [], None
    for t in update_trees:
        f, spec = flatten_update(t)
        flats.append(f)
    stacked = jnp.stack(flats)  # (n, D)
    agg, weights = stale_synchronous_aggregate_flat(
        stacked, fresh, tau, rule=rule, beta=beta, use_kernel=use_kernel,
        compiled=compiled)
    return unflatten_update(agg, spec), weights


# ---------------------------------------------------------------------------
# Server optimizers (operate on the aggregated delta)
# ---------------------------------------------------------------------------


def fedavg_apply(params, delta, server_lr: float = 1.0):
    """x_{t+1} = x_t + lr * Delta  (McMahan et al., 2017)."""
    return jax.tree.map(lambda p, d: (p.astype(jnp.float32)
                                      + server_lr * d.astype(jnp.float32)
                                      ).astype(p.dtype), params, delta)


def yogi_init(params):
    z = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"m": z, "v": jax.tree.map(lambda p: jnp.full(p.shape, 1e-6, jnp.float32), params),
            "t": jnp.zeros((), jnp.int32)}


def yogi_apply(params, delta, state, *, lr=1e-2, b1=0.9, b2=0.99, eps=1e-3):
    """Federated YoGi (Reddi et al. / Ramaswamy et al., 2020).

    v <- v - (1-b2) * d^2 * sign(v - d^2)   (YoGi's additive variant of Adam)
    """
    m = jax.tree.map(lambda m_, d: b1 * m_ + (1 - b1) * d.astype(jnp.float32),
                     state["m"], delta)
    v = jax.tree.map(
        lambda v_, d: v_ - (1 - b2) * jnp.square(d.astype(jnp.float32))
        * jnp.sign(v_ - jnp.square(d.astype(jnp.float32))), state["v"], delta)
    new_params = jax.tree.map(
        lambda p, m_, v_: (p.astype(jnp.float32)
                           + lr * m_ / (jnp.sqrt(v_) + eps)).astype(p.dtype),
        params, m, v)
    return new_params, {"m": m, "v": v, "t": state["t"] + 1}


def yogi_init_flat(d: int):
    """YoGi state over a flat (D,) parameter vector (fast-path server)."""
    return {"m": jnp.zeros((d,), jnp.float32),
            "v": jnp.full((d,), 1e-6, jnp.float32),
            "t": jnp.zeros((), jnp.int32)}


def yogi_apply_flat(flat_params, delta, state, *, lr=1e-2, b1=0.9, b2=0.99,
                    eps=1e-3):
    """``yogi_apply`` on flat fp32 vectors — same elementwise formulas, so the
    values match the pytree version bit-for-bit; vmappable over a leading
    sweep axis."""
    m = b1 * state["m"] + (1 - b1) * delta
    d2 = jnp.square(delta)
    v = state["v"] - (1 - b2) * d2 * jnp.sign(state["v"] - d2)
    new = flat_params + lr * m / (jnp.sqrt(v) + eps)
    return new, {"m": m, "v": v, "t": state["t"] + 1}
