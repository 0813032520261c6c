"""Learner-side local training for the FL simulation.

The simulation model is a 2-layer MLP classifier (the statistical role the
paper's ResNet/ShuffleNet/Albert play, scaled to CPU).  All selected
participants of a round train in one ``vmap``-ed jitted call — the TPU-pod
analogue of FedScale's time-multiplexed GPU workers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def mlp_init(key, dim: int, n_classes: int, hidden: int = 128):
    k1, k2 = jax.random.split(key)
    s1, s2 = dim ** -0.5, hidden ** -0.5
    return {
        "w1": jax.random.normal(k1, (dim, hidden), jnp.float32) * s1,
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jax.random.normal(k2, (hidden, n_classes), jnp.float32) * s2,
        "b2": jnp.zeros((n_classes,), jnp.float32),
    }


def mlp_apply(params, x):
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def _xent(params, x, y):
    logits = mlp_apply(params, x)
    logp = jax.nn.log_softmax(logits)
    losses = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return losses.mean(), losses


@functools.partial(jax.jit, static_argnames=("lr", "prox_mu", "loss"))
def local_train(params, xs, ys, lr: float, prox_mu: float = 0.0, *,
                loss=_xent):
    """K local SGD steps (Alg. 2 participant update).

    xs: (n_steps, batch, ...); ys: (n_steps, batch, ...).
    ``prox_mu > 0`` adds FedProx's proximal term mu/2 ||w - w_global||^2
    (Li et al., MLSys'20) to each local step.  ``loss`` is the model's
    objective ``(params, x, y) -> (mean, per_example)`` — a static arg
    (the default is the MLP's cross-entropy), so each model compiles its
    own program and the default keeps the pre-model-zoo cache key.
    Returns (delta pytree, mean loss, sqrt(mean loss^2) for Oort stat-util).
    """
    final, loss_m, l2 = _local_sgd(params, xs, ys, lr, prox_mu, loss)
    delta = jax.tree.map(lambda a, b: a - b, final, params)
    return delta, loss_m, l2


def _local_sgd(params, xs, ys, lr, prox_mu, loss):
    """The K steps themselves: (trained params, mean loss, Oort l2 stat)."""
    p0 = params
    loss_fn = loss

    def step(p, xy):
        x, y = xy
        (loss, losses), g = jax.value_and_grad(loss_fn, has_aux=True)(p, x, y)
        if prox_mu > 0.0:
            g = jax.tree.map(lambda gw, w, w0: gw + prox_mu * (w - w0), g, p, p0)
        p = jax.tree.map(lambda w, gw: w - lr * gw, p, g)
        return p, (loss, jnp.sqrt(jnp.mean(losses ** 2)))

    final, (losses, l2s) = jax.lax.scan(step, params, (xs, ys))
    return final, losses.mean(), l2s.mean()


# vmap over the participant axis — one compiled program trains the whole cohort
local_train_cohort = jax.jit(
    jax.vmap(local_train, in_axes=(None, 0, 0, None, None)),
    static_argnames=("lr", "prox_mu"))


def local_train_flat(flat_params, xs, ys, *, spec, lr, prox_mu,
                     loss=_xent, out_dim=None):
    """One learner's local round as a pure flat-vector function.

    flat_params: (D,) fp32 in ``spec`` leaf order; xs: (n_steps, batch, ...);
    returns (flat delta, mean loss, Oort l2 stat).  The unflatten and
    per-leaf flatten are pure reshapes, so the delta rows are bit-identical
    to ``local_train``'s pytree output — this is the unit the engine's
    ``flat_cohort_step`` vmaps over a cohort and the sweep runner vmaps over
    packed (cell, participant) rows with per-row parameters.

    ``out_dim`` (block-padded pipelines): when it exceeds the spec's D the
    delta is zero-padded to ``(out_dim,)`` so the caller's persistent
    D-blocked buffers need no per-round repadding; a ``flat_params`` row
    wider than D is likewise accepted (``unflatten_update`` consumes
    exactly D leading elements, the padded tail is ignored).
    """
    from repro.core.aggregation import unflatten_update
    delta, loss_v, l2 = local_train(unflatten_update(flat_params, spec),
                                    xs, ys, lr, prox_mu, loss=loss)
    flat = jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                            for l in jax.tree.leaves(delta)])
    if out_dim is not None and int(out_dim) > flat.shape[0]:
        flat = jnp.concatenate(
            [flat, jnp.zeros((int(out_dim) - flat.shape[0],), jnp.float32)])
    return flat, loss_v, l2


def local_train_leaves(flat_params, xs, ys, *, spec, lr, prox_mu,
                       loss=_xent):
    """One learner's local round from a flat parameter row, with its
    parameters before and after as leaves in ``spec`` order rather than a
    flat delta: the streamed round program writes ``after - before`` leaf
    by leaf into the fresh sum or a cache slot, so no delta row is built.
    Returns (leaves before, leaves after, mean loss, Oort l2 stat); the
    steps are ``local_train``'s."""
    from repro.core.aggregation import unflatten_update
    start = unflatten_update(flat_params, spec)
    final, loss_v, l2 = _local_sgd(start, xs, ys, lr, prox_mu, loss)
    return jax.tree.leaves(start), jax.tree.leaves(final), loss_v, l2


@jax.jit
def evaluate(params, x, y):
    logits = mlp_apply(params, x)
    acc = (logits.argmax(-1) == y).mean()
    loss, _ = _xent(params, x, y)
    return acc, loss


def sample_batch_indices(shard_idx: np.ndarray, n_steps: int, batch: int,
                         rng: np.random.Generator) -> np.ndarray:
    """Sample indices for one learner's fixed-shape local batches (with
    replacement when the shard is small).  The single RNG draw shared by the
    host-materialized and device-gather paths, so both consume the identical
    stream and pick the identical samples."""
    return rng.choice(shard_idx, size=n_steps * batch,
                      replace=len(shard_idx) < n_steps * batch)


def sample_local_batches(shard_idx: np.ndarray, x: np.ndarray, y: np.ndarray,
                         n_steps: int, batch: int, rng: np.random.Generator):
    """Fixed-shape local batches, materialized on host.  The device-resident
    round pipeline keeps only ``sample_batch_indices``' output and gathers
    the rows in-program from the device copy of the dataset."""
    take = sample_batch_indices(shard_idx, n_steps, batch, rng)
    return (x[take].reshape((n_steps, batch) + x.shape[1:]),
            y[take].reshape((n_steps, batch) + y.shape[1:]))
