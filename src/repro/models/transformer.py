"""Composable decoder-only model covering all assigned architectures.

A model is a sequence of *layers*, each layer = (mixer, ffn) where
mixer in {gqa attention, MLA attention, mamba, rwkv6} and ffn in {dense SwiGLU,
MoE, none (rwkv6 uses its own channel-mix = dense here)}.

Layers are grouped into an optional unrolled *prefix* (e.g. DeepSeek's first
dense layer) followed by a periodic *super-block* that is ``lax.scan``-ed over
its repeats (Jamba: 8-layer super-block x 4; homogeneous stacks: 1-layer block
x n_layers).  HLO size is therefore depth-independent.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import layers as L
from repro.models import shard_hints
from repro.models import mamba as mb
from repro.models import moe as moe_lib
from repro.models import rwkv6 as rw
from repro.models import frontends as fr


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str = "custom"
    family: str = "dense"            # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""                 # citation for the config
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 1024
    d_head: Optional[int] = None
    qkv_bias: bool = False
    attn_type: str = "gqa"           # gqa | mla
    window: Optional[int] = None     # sliding-window width (None = full causal)
    rope_theta: float = 1e4
    # --- MLA (DeepSeek-V2) ---
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    # --- MoE ---
    moe: bool = False
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    shared_d_ff: int = 0
    first_k_dense: int = 0
    moe_every: int = 1               # MoE ffn on layers where idx % moe_every == moe_offset
    moe_offset: int = 0
    moe_group_size: int = 4096
    # --- hybrid / ssm ---
    block_pattern: Tuple[str, ...] = ("attn",)  # mixer per layer, tiled
    mamba_d_state: int = 16
    mamba_expand: int = 2
    mamba_conv_width: int = 4
    rwkv_lora_rank: int = 32
    rwkv_w_lora_rank: int = 64
    # --- frontend ---
    frontend: Optional[str] = None   # "vision" | None (audio uses plain tokens)
    d_frontend: int = 1024
    n_frontend_tokens: int = 256
    # --- misc ---
    tie_embeddings: bool = False
    vocab_pad_to: int = 0            # pad vocab rows so "model" axis divides
    norm_eps: float = 1e-5
    param_dtype: Any = jnp.bfloat16
    use_kernels: bool = False
    mla_absorb: bool = False         # absorbed-matmul MLA decode (beyond-paper)
    loss_chunk: int = 0              # >0: chunk the LM loss over sequence
    remat: bool = False              # activation checkpointing on super-blocks
    # --- muP (MiniCPM, arXiv:2404.06395); None leaves the path untouched ---
    scale_emb: Optional[float] = None     # x0 = scale_emb * E[tok]
    scale_depth: Optional[float] = None   # x += scale_depth/sqrt(L) * branch
    scale_depth_layers: Optional[int] = None  # that L (None: n_layers)
    dim_model_base: Optional[int] = None  # logits of h / (d_model / base)

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head is not None else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        if self.vocab_pad_to <= 0:
            return self.vocab_size
        m = self.vocab_pad_to
        return (self.vocab_size + m - 1) // m * m

    @property
    def residual_scale(self) -> Optional[float]:
        """Scale of each residual branch, ``scale_depth / sqrt(L)``; ``L``
        is the published depth where the model is cut in depth (the layers
        left out lie on other pipeline stages)."""
        if self.scale_depth is None:
            return None
        layers = self.scale_depth_layers or self.n_layers
        return self.scale_depth / layers ** 0.5

    def mixer_of(self, layer_idx: int) -> str:
        return self.block_pattern[layer_idx % len(self.block_pattern)]

    def ffn_of(self, layer_idx: int) -> str:
        if self.mixer_of(layer_idx) == "rwkv6":
            return "dense"  # channel-mix approximated by a dense SwiGLU
        if (self.moe and layer_idx >= self.first_k_dense
                and layer_idx % self.moe_every == self.moe_offset):
            return "moe"
        return "dense"

    def layer_spec(self, layer_idx: int) -> Tuple[str, str]:
        return (self.mixer_of(layer_idx), self.ffn_of(layer_idx))

    def segment_plan(self) -> Tuple[list, list, int]:
        """Returns (prefix_specs, period_specs, n_repeats)."""
        prefix = [self.layer_spec(i) for i in range(self.first_k_dense)]
        rest = self.n_layers - self.first_k_dense
        period = 1
        # the super-block period must tile both the mixer pattern and moe cadence
        for cand in (len(self.block_pattern), self.moe_every):
            period = _lcm(period, cand)
        assert rest % period == 0, (
            f"{self.arch_id}: {rest} layers not divisible by super-block {period}")
        specs = [self.layer_spec(self.first_k_dense + i) for i in range(period)]
        return prefix, specs, rest // period


def _lcm(a, b):
    import math
    return a * b // math.gcd(a, b)


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------


def _layer_init(cfg: ModelConfig, key, spec):
    mixer, ffn = spec
    kmix, kffn, kn1, kn2 = jax.random.split(key, 4)
    p = {"norm1": L.rmsnorm_init(cfg.d_model), "norm2": L.rmsnorm_init(cfg.d_model)}
    if mixer == "attn":
        if cfg.attn_type == "mla":
            p["mixer"] = attn.mla_init(
                kmix, cfg.d_model, cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
                v_head_dim=cfg.v_head_dim, dtype=cfg.param_dtype)
        else:
            p["mixer"] = attn.gqa_init(kmix, cfg.d_model, cfg.n_heads,
                                       cfg.n_kv_heads, cfg.head_dim,
                                       cfg.qkv_bias, cfg.param_dtype)
    elif mixer == "mamba":
        p["mixer"] = mb.mamba_init(kmix, cfg.d_model, d_state=cfg.mamba_d_state,
                                   expand=cfg.mamba_expand,
                                   conv_width=cfg.mamba_conv_width,
                                   dtype=cfg.param_dtype)
    elif mixer == "rwkv6":
        p["mixer"] = rw.rwkv6_init(kmix, cfg.d_model, cfg.n_heads,
                                   lora_rank=cfg.rwkv_lora_rank,
                                   w_lora_rank=cfg.rwkv_w_lora_rank,
                                   dtype=cfg.param_dtype)
    else:
        raise ValueError(mixer)
    if ffn == "dense":
        p["ffn"] = L.mlp_init(kffn, cfg.d_model, cfg.d_ff, cfg.param_dtype)
    elif ffn == "moe":
        p["ffn"] = moe_lib.moe_init(kffn, cfg.d_model, cfg.moe_d_ff, cfg.n_experts,
                                    cfg.n_shared_experts, cfg.shared_d_ff or cfg.moe_d_ff,
                                    cfg.param_dtype)
    return p


def _mixer_forward(cfg, spec, p, x, positions, state):
    """Full-sequence mixer. Returns (out, new_state_or_cache)."""
    mixer, _ = spec
    if mixer == "attn":
        if cfg.attn_type == "mla":
            out, kv = attn.mla_forward(
                p, x, positions, n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
                v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta, window=cfg.window)
            new_state = {"c_kv": kv[0], "k_rope": kv[1],
                         "pos": positions.astype(jnp.int32)}
        else:
            out, kv = attn.gqa_forward(
                p, x, positions, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                d_head=cfg.head_dim, rope_theta=cfg.rope_theta, window=cfg.window,
                use_kernel=cfg.use_kernels)
            new_state = {"k": kv[0], "v": kv[1], "pos": positions.astype(jnp.int32)}
        return out, new_state
    if mixer == "mamba":
        return mb.mamba_forward(p, x, d_state=cfg.mamba_d_state,
                                expand=cfg.mamba_expand,
                                conv_width=cfg.mamba_conv_width, state=state)
    if mixer == "rwkv6":
        return rw.rwkv6_forward(p, x, n_heads=cfg.n_heads, state=state,
                                use_kernel=cfg.use_kernels)
    raise ValueError(mixer)


def _mixer_decode(cfg, spec, p, x, position, state):
    mixer, _ = spec
    if mixer == "attn":
        if cfg.attn_type == "mla":
            return attn.mla_decode(
                p, x, position, state, n_heads=cfg.n_heads,
                kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
                qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
                rope_theta=cfg.rope_theta, window=cfg.window,
                absorbed=cfg.mla_absorb)
        return attn.gqa_decode(p, x, position, state, n_heads=cfg.n_heads,
                               n_kv_heads=cfg.n_kv_heads, d_head=cfg.head_dim,
                               rope_theta=cfg.rope_theta, window=cfg.window)
    if mixer == "mamba":
        return mb.mamba_decode(p, x, state, d_state=cfg.mamba_d_state,
                               expand=cfg.mamba_expand,
                               conv_width=cfg.mamba_conv_width)
    if mixer == "rwkv6":
        return rw.rwkv6_decode(p, x, state, n_heads=cfg.n_heads)
    raise ValueError(mixer)


def _ffn_forward(cfg, spec, p, x):
    """Returns (out, aux_loss)."""
    _, ffn = spec
    if ffn == "dense":
        return L.mlp(p["ffn"], x), jnp.zeros((), jnp.float32)
    return moe_lib.moe_forward(p["ffn"], x, n_experts=cfg.n_experts,
                               top_k=cfg.top_k, group_size=cfg.moe_group_size)


def _branch(cfg, h):
    """A residual branch's output, muP-scaled where the model says so."""
    scale = cfg.residual_scale
    return h if scale is None else h * jnp.asarray(scale, h.dtype)


def _layer_forward(cfg, spec, p, x, positions, state):
    h, new_state = _mixer_forward(cfg, spec, p["mixer"],
                                  L.rmsnorm(p["norm1"], x, cfg.norm_eps),
                                  positions, state)
    x = x + _branch(cfg, h)
    h, aux = _ffn_forward(cfg, spec, p, L.rmsnorm(p["norm2"], x, cfg.norm_eps))
    return x + _branch(cfg, h), new_state, aux


def _layer_decode(cfg, spec, p, x, position, state):
    h, new_state = _mixer_decode(cfg, spec, p["mixer"],
                                 L.rmsnorm(p["norm1"], x, cfg.norm_eps),
                                 position, state)
    x = x + _branch(cfg, h)
    h, aux = _ffn_forward(cfg, spec, p, L.rmsnorm(p["norm2"], x, cfg.norm_eps))
    return x + _branch(cfg, h), new_state, aux


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key):
    prefix, specs, n_rep = cfg.segment_plan()
    keys = jax.random.split(key, 4 + len(prefix))
    params = {"embed": L.embed_init_params(keys[0], cfg.padded_vocab, cfg.d_model,
                                           cfg.param_dtype),
              "final_norm": L.rmsnorm_init(cfg.d_model)}
    if not cfg.tie_embeddings:
        params["head"] = {"w_out": L.dense_init(keys[1],
                                                (cfg.d_model, cfg.padded_vocab),
                                                cfg.param_dtype)}
    if cfg.frontend == "vision":
        params["frontend"] = fr.frontend_init(keys[2], cfg.d_frontend, cfg.d_model,
                                              cfg.param_dtype)
    params["prefix"] = [
        _layer_init(cfg, keys[4 + i], spec) for i, spec in enumerate(prefix)]

    def superblock_init(k):
        ks = jax.random.split(k, len(specs))
        return {f"sub{i}": _layer_init(cfg, ks[i], spec)
                for i, spec in enumerate(specs)}

    rep_keys = jax.random.split(keys[3], n_rep)
    params["stack"] = jax.vmap(superblock_init)(rep_keys)
    return params


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed_tokens(cfg, params, tokens):
    x = L.embed_lookup(params["embed"], tokens)
    if cfg.scale_emb is not None:
        x = x * jnp.asarray(cfg.scale_emb, x.dtype)
    return x


def _embed_inputs(cfg, params, batch):
    """batch: {"tokens": (B, S_text)[, "frontend_embeds": (B, P, d_frontend)]}"""
    x = _embed_tokens(cfg, params, batch["tokens"])
    if cfg.frontend == "vision":
        fe = fr.project_frontend(params["frontend"], batch["frontend_embeds"])
        x = jnp.concatenate([fe.astype(x.dtype), x], axis=1)
    return x


def _logits(cfg, params, x):
    if cfg.dim_model_base is not None:
        # muP's output multiplier: the final hidden state over the width
        # ratio to the base model, before the head
        x = x / jnp.asarray(cfg.d_model / cfg.dim_model_base, x.dtype)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["embedding"].T.astype(x.dtype)
    else:
        logits = x @ params["head"]["w_out"].astype(x.dtype)
    if cfg.padded_vocab != cfg.vocab_size:
        # mask pad rows out of the softmax support (sharded-safe: iota compare)
        pad_mask = jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, logits.ndim - 1) >= cfg.vocab_size
        logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype), logits)
    return logits


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill)
# ---------------------------------------------------------------------------


def forward(cfg: ModelConfig, params, batch, *, return_states: bool = False):
    """Returns (logits or final hidden, aux_loss, states)."""
    prefix, specs, n_rep = cfg.segment_plan()
    x = shard_hints.constrain_activations(_embed_inputs(cfg, params, batch))
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    aux_total = jnp.zeros((), jnp.float32)
    prefix_states = []
    for p, spec in zip(params["prefix"], prefix):
        x, st, aux = _layer_forward(cfg, spec, p, x, positions, None)
        aux_total += aux
        prefix_states.append(st if return_states else None)

    def superblock(carry, p_slice):
        x, aux_acc = carry
        states = {}
        for i, spec in enumerate(specs):
            x, st, aux = _layer_forward(cfg, spec, p_slice[f"sub{i}"], x,
                                        positions, None)
            aux_acc = aux_acc + aux
            states[f"sub{i}"] = st if return_states else 0
        return (shard_hints.constrain_activations(x), aux_acc), states

    block_fn = jax.checkpoint(superblock) if cfg.remat else superblock
    (x, aux_total), stack_states = jax.lax.scan(
        block_fn, (x, aux_total), params["stack"])
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    states = {"prefix": prefix_states, "stack": stack_states} if return_states else None
    return x, aux_total, states


def lm_loss(cfg: ModelConfig, params, batch, *, aux_weight: float = 0.01):
    """Cross-entropy next-token loss (labels = batch["labels"])."""
    x, aux, _ = forward(cfg, params, batch)
    labels = batch["labels"]
    # only score the token positions (frontend positions carry no labels)
    if cfg.frontend == "vision":
        x = x[:, -labels.shape[1]:]

    def chunk_loss(xc, yc):
        logits = _logits(cfg, params, xc).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return (logz - gold).sum()

    B, S, _ = x.shape
    if cfg.loss_chunk and S > cfg.loss_chunk and S % cfg.loss_chunk == 0:
        nch = S // cfg.loss_chunk
        xs = x.reshape(B, nch, cfg.loss_chunk, -1).transpose(1, 0, 2, 3)
        ys = labels.reshape(B, nch, cfg.loss_chunk).transpose(1, 0, 2)
        total = jax.lax.scan(
            lambda c, xy: (c + chunk_loss(*xy), None), jnp.zeros((), jnp.float32),
            (xs, ys))[0]
    else:
        total = chunk_loss(x, labels)
    return total / (B * S) + aux_weight * aux


def prefill(cfg: ModelConfig, params, batch):
    """Run the full prompt; returns (last-position logits, states for decode)."""
    x, aux, states = forward(cfg, params, batch, return_states=True)
    logits = _logits(cfg, params, x[:, -1:])[..., :cfg.vocab_size]
    return logits, states


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def _mixer_state_shape(cfg, spec, B, cache_len):
    mixer, _ = spec
    dt = cfg.param_dtype
    if mixer == "attn":
        if cfg.attn_type == "mla":
            return {"c_kv": jnp.zeros((B, cache_len, cfg.kv_lora_rank), dt),
                    "k_rope": jnp.zeros((B, cache_len, cfg.qk_rope_dim), dt),
                    "pos": jnp.full((B, cache_len), -1, jnp.int32)}
        return {"k": jnp.zeros((B, cache_len, cfg.n_kv_heads, cfg.head_dim), dt),
                "v": jnp.zeros((B, cache_len, cfg.n_kv_heads, cfg.head_dim), dt),
                "pos": jnp.full((B, cache_len), -1, jnp.int32)}
    if mixer == "mamba":
        d_inner = cfg.mamba_expand * cfg.d_model
        return {"conv": jnp.zeros((B, cfg.mamba_conv_width - 1, d_inner), dt),
                "ssm": jnp.zeros((B, d_inner, cfg.mamba_d_state), jnp.float32)}
    if mixer == "rwkv6":
        N = cfg.d_model // cfg.n_heads
        return {"x_prev": jnp.zeros((B, cfg.d_model), dt),
                "wkv": jnp.zeros((B, cfg.n_heads, N, N), jnp.float32)}
    raise ValueError(mixer)


def init_decode_state(cfg: ModelConfig, B: int, max_seq: int):
    """Allocate the serve-time state. Attention caches are ring buffers of
    ``min(max_seq, window)`` slots when a sliding window is configured."""
    cache_len = max_seq if cfg.window is None else min(max_seq, cfg.window)
    prefix, specs, n_rep = cfg.segment_plan()
    state = {"prefix": [_mixer_state_shape(cfg, s, B, cache_len) for s in prefix]}

    def one(_):
        return {f"sub{i}": _mixer_state_shape(cfg, s, B, cache_len)
                for i, s in enumerate(specs)}

    state["stack"] = jax.vmap(one)(jnp.arange(n_rep))
    return state


def decode_step(cfg: ModelConfig, params, state, tokens, position):
    """One-token decode. tokens: (B,), position: (B,) absolute positions.

    Returns (logits (B, vocab), new_state).
    """
    prefix, specs, n_rep = cfg.segment_plan()
    x = _embed_tokens(cfg, params, tokens[:, None])

    new_prefix = []
    for p, spec, st in zip(params["prefix"], prefix, state["prefix"]):
        x, st_new, _ = _layer_decode(cfg, spec, p, x, position, st)
        new_prefix.append(st_new)

    def superblock(x, slc):
        p_slice, st_slice = slc
        new_states = {}
        for i, spec in enumerate(specs):
            x, st_new, _ = _layer_decode(cfg, spec, p_slice[f"sub{i}"], x,
                                         position, st_slice[f"sub{i}"])
            new_states[f"sub{i}"] = st_new
        return x, new_states

    x, new_stack = jax.lax.scan(superblock, x, (params["stack"], state["stack"]))
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(cfg, params, x)[:, 0, :cfg.vocab_size]
    return logits, {"prefix": new_prefix, "stack": new_stack}
