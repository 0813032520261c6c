"""Reference model of kind ``transformer``: ``num_hidden_layers`` pre-norm
decoder layers (RMSNorm with a learned scale, eps 1e-5), multi-head causal
attention with rotary positions (base 10,000, rotate-half) and a
1/sqrt(head_dim) softmax scale, a SwiGLU feed-forward
(``(silu(h Wg) * h Wu) Wd``), a final RMSNorm and an untied output head.
The loss of a sequence is its mean next-token cross-entropy.

The layers are stacked on the leading axis of ``stack.sub0``, as the
program's learner stacks its scanned period of one layer, and run in order.
``m``: ``hidden_size``, ``num_attention_heads``, ``intermediate_size``,
``num_hidden_layers``, ``vocab_size``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _dims(m: dict):
    return (int(m["hidden_size"]), int(m["intermediate_size"]),
            int(m["vocab_size"]), int(m["num_hidden_layers"]))


def _dense(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5


def _layer(ks, d, f):
    return {"ffn": {"w_down": _dense(ks[0], (f, d)),
                    "w_gate": _dense(ks[1], (d, f)),
                    "w_up": _dense(ks[2], (d, f))},
            "mixer": {"w_k": _dense(ks[3], (d, d)),
                      "w_o": _dense(ks[4], (d, d)),
                      "w_q": _dense(ks[5], (d, d)),
                      "w_v": _dense(ks[6], (d, d))},
            "norm1": {"scale": jnp.ones((d,), jnp.float32)},
            "norm2": {"scale": jnp.ones((d,), jnp.float32)}}


def init(key, m: dict):
    """Layer 0 draws from the first seven of ``split(key, 9)``, layer ``i``
    from ``split(fold_in(key, i), 7)``."""
    d, f, v, n_layers = _dims(m)
    ks = jax.random.split(key, 9)
    layers = [_layer(ks, d, f)] + [
        _layer(jax.random.split(jax.random.fold_in(key, i), 7), d, f)
        for i in range(1, n_layers)]
    return {"embed": {"embedding": jax.random.normal(ks[7], (v, d)) * 0.02},
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "head": {"w_out": _dense(ks[8], (d, v))},
            "prefix": [],
            "stack": {"sub0": jax.tree.map(lambda *a: jnp.stack(a),
                                           *layers)}}


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * scale


def _rope(x):
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, dh/2)
    cos, sin = (jnp.cos(ang)[None, :, None, :].astype(x.dtype),
                jnp.sin(ang)[None, :, None, :].astype(x.dtype))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _decoder_layer(x, blk, prec, n_heads):
    mm = functools.partial(jnp.matmul, precision=prec)
    b, s, d = x.shape
    dh = d // n_heads
    h = _rms(x, blk["norm1"]["scale"])
    q, k, v = (mm(h, blk["mixer"][w]).reshape(b, s, n_heads, dh)
               for w in ("w_q", "w_k", "w_v"))
    q, k = _rope(q), _rope(k)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) * dh ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v, precision=prec)
    x = x + mm(o.reshape(b, s, d), blk["mixer"]["w_o"])
    h = _rms(x, blk["norm2"]["scale"])
    ffn = blk["ffn"]
    return x + mm(jax.nn.silu(mm(h, ffn["w_gate"])) * mm(h, ffn["w_up"]),
                  ffn["w_down"])


def loss(p, tok, y, prec, m: dict):
    x = p["embed"]["embedding"][tok]
    for i in range(_dims(m)[3]):
        blk = jax.tree.map(lambda a: a[i], p["stack"]["sub0"])
        x = _decoder_layer(x, blk, prec, int(m["num_attention_heads"]))
    logits = jnp.matmul(_rms(x, p["final_norm"]["scale"]), p["head"]["w_out"],
                        precision=prec)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return logits, (logz - gold).mean(-1)


def params(m: dict) -> int:
    d, f, v, n_layers = _dims(m)
    per_layer = 4 * d * d + 3 * d * f + 2 * d      # attention, SwiGLU, norms
    return 2 * v * d + d + n_layers * per_layer    # embedding, head, norm


def train_flop_per_sample(m: dict, seq_len: int) -> float:
    """One sequence of ``seq_len`` tokens: 6 FLOP per matmul weight and
    token, plus attention's 12 * seq_len * d_model per layer and token."""
    d, f, v, n_layers = _dims(m)
    matmul = n_layers * (4 * d * d + 3 * d * f) + d * v
    return seq_len * (6.0 * matmul + 12.0 * n_layers * seq_len * d)
