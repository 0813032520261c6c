"""Language models from the model zoo, as federated learner plugins.

Each spec wraps :mod:`repro.models.transformer`'s composable decoder
(the zoo's GQA/SWA transformer, its MoE variant, and the RWKV6 hybrid)
into the :class:`~repro.learners.base.ModelFns` triple the round engine
consumes.  Federated specifics:

- ``param_dtype`` is forced to fp32: the aggregation substrate ships
  updates as flat fp32 rows (stale cache, SAA kernels, yogi state), and
  a bf16 parameter tree would round-trip through fp32 flatten/unflatten
  every round, changing the numerics the parity tests pin.
- ``loss`` returns *per-sequence* cross-entropy next to the mean so
  Oort's statistical utility (``sqrt(mean(loss^2))``) works unchanged
  on token workloads.
- ``evaluate`` reports (next-token accuracy, mean NLL) — the eval lane
  treats these exactly like the classifier's (accuracy, loss) pair.
- ``vocab`` (every LM) sets the vocabulary; 0 takes the dataset's.  The
  ``transformer`` learner also takes a tied head and MiniCPM's muP
  multipliers (``scale_emb``, ``scale_depth`` over ``sqrt(depth_layers)``,
  ``dim_model_base``); 0 leaves each out of the program.

These models train on ``data_kind="tokens"`` benchmarks (``tokens`` /
``tokens_skew``: ``repro.data.synthetic.federated_token_shards`` wired
through ``repro.sim.partition.make_token_dataset``), where a sample is
an ``(S,)`` int32 sequence and the label its next-token shift.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.learners.base import Knob, ModelFns, ModelSpec
from repro.learners.registry import register_model
from repro.models import transformer as tf

_AUX_WEIGHT = 0.01   # MoE load-balance weight (matches transformer.lm_loss)


def _seq_xent(mcfg, params, x, y):
    """(per-sequence mean next-token cross-entropy, aux loss)."""
    h, aux, _ = tf.forward(mcfg, params, {"tokens": x})
    logits = tf._logits(mcfg, params, h).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return (logz - gold).mean(axis=-1), aux, logits


def _fns_for(mcfg: tf.ModelConfig) -> ModelFns:
    def init(key):
        return tf.init_params(mcfg, key)

    def loss(params, x, y):
        per_seq, aux, _ = _seq_xent(mcfg, params, x, y)
        return per_seq.mean() + _AUX_WEIGHT * aux, per_seq

    def evaluate(params, x, y):
        per_seq, _aux, logits = _seq_xent(mcfg, params, x, y)
        acc = (logits.argmax(-1) == y).mean()
        return acc, per_seq.mean()

    return ModelFns(init=init, loss=loss, evaluate=evaluate)


_BASE_KNOBS = (
    Knob("n_layers", 2, "decoder layers"),
    Knob("d_model", 64, "model width"),
    Knob("n_heads", 2, "attention / wkv heads"),
    Knob("d_ff", 128, "dense SwiGLU width"),
    Knob("vocab", 0, "vocabulary size (0 = the dataset's)"),
)


def _base_cfg(knobs: dict, meta, **over) -> tf.ModelConfig:
    return tf.ModelConfig(
        n_layers=int(knobs["n_layers"]),
        d_model=int(knobs["d_model"]),
        n_heads=int(knobs["n_heads"]),
        n_kv_heads=int(knobs["n_heads"]),
        d_ff=int(knobs["d_ff"]),
        vocab_size=int(knobs["vocab"]) or int(meta.vocab),
        param_dtype=jnp.float32,
        **over)


def _mup(knobs: dict) -> dict:
    """MiniCPM's muP multipliers and the tied head, from the knobs; a knob
    at 0 leaves its multiplier out of the program."""
    def opt(name, cast):
        v = cast(knobs[name])
        return v if v else None
    return dict(tie_embeddings=bool(int(knobs["tie_embeddings"])),
                scale_emb=opt("scale_emb", float),
                scale_depth=opt("scale_depth", float),
                scale_depth_layers=opt("depth_layers", int),
                dim_model_base=opt("dim_model_base", int))


def _check_kernels_trainable(knobs: dict, why: str) -> None:
    """A learner trains through its kernels, so ``use_kernels=1`` on a TPU
    backend raises instead of running the Pallas interpreter there."""
    if int(knobs["use_kernels"]) and jax.default_backend() == "tpu":
        raise NotImplementedError(
            f"use_kernels=1 cannot train on TPU: {why} (ROADMAP 2.3); "
            f"set use_kernels=0")


def _build_transformer(knobs: dict, meta) -> ModelFns:
    _check_kernels_trainable(
        knobs, "the swa_attention Pallas kernel is forward-only (no VJP)")
    window = int(knobs["window"])
    return _fns_for(_base_cfg(
        knobs, meta, arch_id="fl-transformer",
        window=window if window > 0 else None,
        use_kernels=bool(int(knobs["use_kernels"])), **_mup(knobs)))


def _build_moe(knobs: dict, meta) -> ModelFns:
    return _fns_for(_base_cfg(
        knobs, meta, arch_id="fl-moe", family="moe", moe=True,
        n_experts=int(knobs["n_experts"]), top_k=int(knobs["top_k"]),
        moe_d_ff=int(knobs["moe_d_ff"])))


def _build_rwkv6(knobs: dict, meta) -> ModelFns:
    _check_kernels_trainable(
        knobs, "the wkv6 Pallas kernel has no TPU lowering and no VJP")
    return _fns_for(_base_cfg(
        knobs, meta, arch_id="fl-rwkv6", family="hybrid",
        block_pattern=("rwkv6",),
        use_kernels=bool(int(knobs["use_kernels"]))))


register_model(ModelSpec(
    name="transformer",
    build=_build_transformer,
    doc="decoder-only GQA transformer LM (optional sliding-window attention)",
    data_kind="tokens",
    family="dense",
    kernel="swa attention (pallas, use_kernels=1)",
    knobs=_BASE_KNOBS + (
        Knob("window", 0, "sliding-window width (0 = full causal)"),
        Knob("use_kernels", 0, "route attention through the Pallas kernel"),
        Knob("tie_embeddings", 0, "1 = the output head is the embedding"),
        Knob("scale_emb", 0.0, "muP embedding multiplier (0 = none)"),
        Knob("scale_depth", 0.0, "muP residual-branch scale over "
                                 "sqrt(depth_layers) (0 = none)"),
        Knob("depth_layers", 0, "depth scale_depth is divided by "
                                "(0 = n_layers)"),
        Knob("dim_model_base", 0, "muP logit divisor's base width: logits "
                                  "of h / (d_model / base) (0 = none)"),
    ),
))

register_model(ModelSpec(
    name="moe",
    build=_build_moe,
    doc="mixture-of-experts transformer LM (top-k router + balance aux)",
    data_kind="tokens",
    family="moe",
    kernel="-",
    knobs=_BASE_KNOBS + (
        Knob("n_experts", 4, "routed experts"),
        Knob("top_k", 2, "experts per token"),
        Knob("moe_d_ff", 64, "per-expert SwiGLU width"),
    ),
))

register_model(ModelSpec(
    name="rwkv6",
    build=_build_rwkv6,
    doc="RWKV6 token/channel-mix LM (linear-attention wkv6 recurrence)",
    data_kind="tokens",
    family="rnn",
    kernel="wkv6 scan (pallas, use_kernels=1)",
    knobs=_BASE_KNOBS + (
        Knob("use_kernels", 0, "route the wkv6 recurrence through Pallas"),
    ),
))
