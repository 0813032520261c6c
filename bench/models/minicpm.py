"""Reference model of kind ``minicpm``: MiniCPM-2B (openbmb,
arXiv:2404.06395), written from the published modelling code of
``https://huggingface.co/openbmb/MiniCPM-2B-sft-bf16``, in plain float32
``jax.numpy``:

- ``x0 = scale_emb * E[tok]``;
- ``num_hidden_layers`` pre-norm decoder layers: RMSNorm (learned scale,
  eps 1e-5), multi-head causal attention (``num_attention_heads`` heads of
  ``hidden_size / num_attention_heads``, as many KV heads, rotary positions
  of base 10,000 in the rotate-half form, a 1/sqrt(head_dim) softmax
  scale) and a SwiGLU feed-forward (``(silu(h Wg) * h Wu) Wd``) of
  ``intermediate_size``, each residual branch scaled by
  ``scale_depth / sqrt(depth_layers)``: ``x <- x + s * branch(x)``;
- a final RMSNorm;
- ``logits = (h / (hidden_size / dim_model_base)) E^T``: the output head
  is the embedding.

The loss of a sequence is its mean next-token cross-entropy. RMSNorm and
the rotary positions are the ``transformer`` kind's (``_rms``, ``_rope``),
loaded through ``kinds.model``; so are the initial draws.

Departures from the published model, each a cut of the configuration:

- ``num_hidden_layers`` of the published 40 run, while the residual scale
  keeps the published depth (``depth_layers``): the layers left out lie on
  other pipeline stages of the deployment;
- the vocabulary is a slice of the published 122,753 ids: the data draws
  its ids from the slice, and logits and loss are over it;
- float32 parameters and arithmetic (the checkpoint is bfloat16);
- seeded random weights, not the checkpoint: the embedding N(0, 0.02),
  each matmul weight N(0, 1/fan_in), norm scales 1 (the ``transformer``
  kind's draws, without its output head).

The layers are stacked on the leading axis of ``stack.sub0``, as the
program's learner stacks its scanned period of one layer. ``m``:
``hidden_size``, ``num_attention_heads``, ``intermediate_size``,
``num_hidden_layers``, ``vocab_size``, ``scale_emb``, ``scale_depth``,
``depth_layers``, ``dim_model_base``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import kinds

_T = kinds.model("transformer")


def init(key, m: dict):
    p = _T.init(key, m)
    del p["head"]                      # the head is the embedding
    return p


def _residual_scale(m: dict) -> float:
    return float(m["scale_depth"]) / int(m["depth_layers"]) ** 0.5


def _decoder_layer(x, blk, prec, n_heads, scale):
    mm = functools.partial(jnp.matmul, precision=prec)
    b, s, d = x.shape
    dh = d // n_heads
    h = _T._rms(x, blk["norm1"]["scale"])
    q, k, v = (mm(h, blk["mixer"][w]).reshape(b, s, n_heads, dh)
               for w in ("w_q", "w_k", "w_v"))
    q, k = _T._rope(q), _T._rope(k)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) * dh ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v, precision=prec)
    x = x + scale * mm(o.reshape(b, s, d), blk["mixer"]["w_o"])
    h = _T._rms(x, blk["norm2"]["scale"])
    ffn = blk["ffn"]
    return x + scale * mm(jax.nn.silu(mm(h, ffn["w_gate"]))
                          * mm(h, ffn["w_up"]), ffn["w_down"])


def loss(p, tok, y, prec, m: dict):
    emb = p["embed"]["embedding"]
    x = emb[tok] * float(m["scale_emb"])
    scale = _residual_scale(m)
    for i in range(int(m["num_hidden_layers"])):
        blk = jax.tree.map(lambda a: a[i], p["stack"]["sub0"])
        x = _decoder_layer(x, blk, prec, int(m["num_attention_heads"]), scale)
    h = _T._rms(x, p["final_norm"]["scale"]) / (
        int(m["hidden_size"]) / int(m["dim_model_base"]))
    logits = jnp.matmul(h, emb.T, precision=prec)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return logits, (logz - gold).mean(-1)


def params(m: dict) -> int:
    """The ``transformer`` kind's count less its untied head."""
    return _T.params(m) - int(m["vocab_size"]) * int(m["hidden_size"])


def train_flop_per_sample(m: dict, seq_len: int) -> float:
    """The ``transformer`` kind's count: the tied head is the one
    ``hidden_size x vocab_size`` matmul it counts for the head."""
    return _T.train_flop_per_sample(m, seq_len)
