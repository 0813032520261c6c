"""Reference model of kind ``mlp``: ``relu(x W1 + b1) W2 + b2``, softmax
cross-entropy per example. ``m``: ``dim``, ``hidden``, ``n_classes``."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dims(m: dict):
    return int(m["dim"]), int(m["hidden"]), int(m["n_classes"])


def _dense(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5


def init(key, m: dict):
    k1, k2 = jax.random.split(key)
    dim, hid, c = _dims(m)
    return {"b1": jnp.zeros((hid,), jnp.float32),
            "b2": jnp.zeros((c,), jnp.float32),
            "w1": _dense(k1, (dim, hid)),
            "w2": _dense(k2, (hid, c))}


def loss(p, x, y, prec, m: dict):
    h = jax.nn.relu(jnp.matmul(x, p["w1"], precision=prec) + p["b1"])
    logits = jnp.matmul(h, p["w2"], precision=prec) + p["b2"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return logits, logz - gold


def params(m: dict) -> int:
    d, h, c = _dims(m)
    return d * h + h + h * c + c


def train_flop_per_sample(m: dict, seq_len: int = 0) -> float:
    """One feature row: 6 FLOP per matmul weight."""
    d, h, c = _dims(m)
    return 6.0 * (d * h + h * c)
