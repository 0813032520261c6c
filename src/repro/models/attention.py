"""Attention variants: GQA (covers MHA), sliding-window, and MLA (DeepSeek-V2).

Full-sequence attention (training / prefill) is computed with a memory-bounded
double-blocked online-softmax (flash-attention structure in pure jnp) so that
``memory_analysis()`` of the dry-run reflects a deployable implementation rather
than an O(S^2) score materialization.  The Pallas SWA kernel in
``repro.kernels.swa_attention`` shares this function as its oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Blocked online-softmax attention core
# ---------------------------------------------------------------------------


def blocked_attention(q, k, v, q_positions, kv_positions, *, window=None,
                      q_chunk: int = 1024, kv_chunk: int = 1024, softmax_scale=None):
    """Causal (optionally sliding-window) attention.

    q: (B, Sq, Hkv, G, Dk)   grouped query heads
    k: (B, Sk, Hkv, Dk); v: (B, Sk, Hkv, Dv)   (Dk may differ from Dv, e.g. MLA)
    q_positions: (B, Sq) absolute positions of queries
    kv_positions: (B, Sk) absolute positions of keys; negative = invalid slot
    Returns (B, Sq, Hkv, G, Dv).
    """
    B, Sq, Hkv, G, Dh = q.shape
    Dv = v.shape[-1]
    Sk = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else Dh ** -0.5

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    # Pad to multiples of the chunk sizes; padded kv slots get position -1.
    pad_q = (-Sq) % q_chunk
    pad_k = (-Sk) % kv_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad_q)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad_k)), constant_values=-1)
    Sq_p, Sk_p = q.shape[1], k.shape[1]
    nq, nk = Sq_p // q_chunk, Sk_p // kv_chunk

    q = q.reshape(B, nq, q_chunk, Hkv, G, Dh).transpose(1, 0, 2, 3, 4, 5)
    qpos = q_positions.reshape(B, nq, q_chunk).transpose(1, 0, 2)
    kc = k.reshape(B, nk, kv_chunk, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, kv_chunk, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    kpos = kv_positions.reshape(B, nk, kv_chunk).transpose(1, 0, 2)

    def q_block(carry, q_in):
        qb, qp = q_in  # (B, Cq, Hkv, G, Dh), (B, Cq)

        def kv_block(state, kv_in):
            m, l, o = state
            kb, vb, kp = kv_in  # (B, Ck, Hkv, Dh), ..., (B, Ck)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            mask = qp[:, None, None, :, None] >= kp[:, None, None, None, :]
            mask &= kp[:, None, None, None, :] >= 0
            if window is not None:
                mask &= (qp[:, None, None, :, None] - kp[:, None, None, None, :]) < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, Hkv, G, q_chunk, Dv), jnp.float32)
        (m, l, o), _ = jax.lax.scan(kv_block, (m0, l0, o0), (kc, vc, kpos))
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return carry, out.transpose(0, 3, 1, 2, 4)  # (B, Cq, Hkv, G, Dh)

    _, out = jax.lax.scan(q_block, None, (q, qpos))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq_p, Hkv, G, Dv)
    return out[:, :Sq].astype(v.dtype)


def decode_attention(q, k_cache, v_cache, q_position, kv_positions, *, window=None,
                     softmax_scale=None):
    """One-token attention against a (possibly ring-buffered) cache.

    q: (B, 1, Hkv, G, Dh); caches (B, Sc, Hkv, Dh); kv_positions (B, Sc) with -1
    marking unwritten slots.
    """
    B, _, Hkv, G, Dh = q.shape
    scale = softmax_scale if softmax_scale is not None else Dh ** -0.5
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    mask = kv_positions[:, None, None, None, :] >= 0
    mask &= kv_positions[:, None, None, None, :] <= q_position[:, None, None, None, None]
    if window is not None:
        mask &= (q_position[:, None, None, None, None]
                 - kv_positions[:, None, None, None, :]) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache.astype(jnp.float32))
    return out.astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer (covers MHA when n_kv_heads == n_heads)
# ---------------------------------------------------------------------------


def gqa_init(key, d_model, n_heads, n_kv_heads, d_head, qkv_bias, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "w_q": dense_init(ks[0], (d_model, n_heads * d_head), dtype),
        "w_k": dense_init(ks[1], (d_model, n_kv_heads * d_head), dtype),
        "w_v": dense_init(ks[2], (d_model, n_kv_heads * d_head), dtype),
        "w_o": dense_init(ks[3], (n_heads * d_head, d_model), dtype),
    }
    if qkv_bias:
        p["b_q"] = jnp.zeros((n_heads * d_head,), dtype)
        p["b_k"] = jnp.zeros((n_kv_heads * d_head,), dtype)
        p["b_v"] = jnp.zeros((n_kv_heads * d_head,), dtype)
    return p


def gqa_project_qkv(params, x, n_heads, n_kv_heads, d_head, positions, rope_theta):
    B, S, _ = x.shape
    q = x @ params["w_q"]
    k = x @ params["w_k"]
    v = x @ params["w_v"]
    if "b_q" in params:
        q = q + params["b_q"]
        k = k + params["b_k"]
        v = v + params["b_v"]
    q = q.reshape(B, S, n_heads, d_head)
    k = k.reshape(B, S, n_kv_heads, d_head)
    v = v.reshape(B, S, n_kv_heads, d_head)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    return q, k, v


def gqa_forward(params, x, positions, *, n_heads, n_kv_heads, d_head,
                rope_theta, window=None, use_kernel=False):
    """Full-sequence GQA (training / prefill). Returns (out, (k, v)).

    ``use_kernel`` routes sliding-window attention through the Pallas
    flash-SWA kernel (requires a window that is a multiple of its 128 tile and
    contiguous positions — i.e. the standard prefill layout); any other
    window raises rather than quietly taking the jnp path.
    """
    B, S, _ = x.shape
    G = n_heads // n_kv_heads
    q, k, v = gqa_project_qkv(params, x, n_heads, n_kv_heads, d_head, positions, rope_theta)
    if use_kernel:
        if window is None or window % 128:
            raise ValueError(
                f"use_kernels needs a sliding window that is a multiple of "
                f"the kernel's 128 tile, got window={window}")
        from repro.kernels.swa_attention import ops as swa_ops
        out = swa_ops.swa_attention(q, k, v, window=window)
        out = out.reshape(B, S, n_heads * d_head)
    else:
        qg = q.reshape(B, S, n_kv_heads, G, d_head)
        out = blocked_attention(qg, k, v, positions, positions, window=window)
        out = out.reshape(B, S, n_heads * d_head)
    return out @ params["w_o"], (k, v)


def gqa_decode(params, x, position, cache, *, n_heads, n_kv_heads, d_head,
               rope_theta, window=None):
    """Single-token GQA against a cache dict {"k","v","pos"} (ring buffer).

    cache["k"/"v"]: (B, Sc, Hkv, Dh); cache["pos"]: (B, Sc) absolute positions,
    -1 for never-written slots.  ``position``: (B,) current absolute position.
    """
    B, S1, _ = x.shape
    G = n_heads // n_kv_heads
    q, k, v = gqa_project_qkv(params, x, n_heads, n_kv_heads, d_head,
                              position[:, None], rope_theta)
    Sc = cache["k"].shape[1]
    slot = (position % Sc).astype(jnp.int32)  # ring buffer (full cache: slot==pos)
    b_idx = jnp.arange(B)
    k_cache = cache["k"].at[b_idx, slot].set(k[:, 0])
    v_cache = cache["v"].at[b_idx, slot].set(v[:, 0])
    kv_pos = cache["pos"].at[b_idx, slot].set(position.astype(jnp.int32))
    qg = q.reshape(B, 1, n_kv_heads, G, d_head)
    out = decode_attention(qg, k_cache, v_cache, position, kv_pos, window=window)
    out = out.reshape(B, 1, n_heads * d_head)
    new_cache = {"k": k_cache, "v": v_cache, "pos": kv_pos}
    return out @ params["w_o"], new_cache


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2), compressed KV cache
# ---------------------------------------------------------------------------


def mla_init(key, d_model, n_heads, *, kv_lora_rank, qk_nope_dim, qk_rope_dim,
             v_head_dim, dtype):
    ks = jax.random.split(key, 6)
    return {
        "w_q": dense_init(ks[0], (d_model, n_heads * (qk_nope_dim + qk_rope_dim)), dtype),
        "w_dkv": dense_init(ks[1], (d_model, kv_lora_rank), dtype),
        "w_kr": dense_init(ks[2], (d_model, qk_rope_dim), dtype),
        "w_uk": dense_init(ks[3], (kv_lora_rank, n_heads * qk_nope_dim), dtype),
        "w_uv": dense_init(ks[4], (kv_lora_rank, n_heads * v_head_dim), dtype),
        "w_o": dense_init(ks[5], (n_heads * v_head_dim, d_model), dtype),
    }


def _mla_qkr(params, x, positions, n_heads, qk_nope_dim, qk_rope_dim, rope_theta):
    B, S, _ = x.shape
    q = (x @ params["w_q"]).reshape(B, S, n_heads, qk_nope_dim + qk_rope_dim)
    q_nope, q_rope = q[..., :qk_nope_dim], q[..., qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, rope_theta)
    c_kv = x @ params["w_dkv"]  # (B, S, r)
    k_rope = apply_rope((x @ params["w_kr"])[:, :, None, :], positions, rope_theta)
    return q_nope, q_rope, c_kv, k_rope[:, :, 0, :]


def _mla_expand_kv(params, c_kv, n_heads, qk_nope_dim, v_head_dim):
    B, S, _ = c_kv.shape
    k_nope = (c_kv @ params["w_uk"]).reshape(B, S, n_heads, qk_nope_dim)
    v = (c_kv @ params["w_uv"]).reshape(B, S, n_heads, v_head_dim)
    return k_nope, v


def mla_forward(params, x, positions, *, n_heads, kv_lora_rank, qk_nope_dim,
                qk_rope_dim, v_head_dim, rope_theta, window=None):
    B, S, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(
        params, x, positions, n_heads, qk_nope_dim, qk_rope_dim, rope_theta)
    k_nope, v = _mla_expand_kv(params, c_kv, n_heads, qk_nope_dim, v_head_dim)
    # Assemble full-width q/k: rope part is shared across heads on the k side.
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)  # (B,S,H,dn+dr)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, n_heads, qk_rope_dim))],
        axis=-1)
    scale = (qk_nope_dim + qk_rope_dim) ** -0.5
    qg = q_full[:, :, :, None, :]  # G = 1 per head (MHA over latent kv)
    out = blocked_attention(qg, k_full, v, positions, positions, window=window,
                            softmax_scale=scale)
    out = out.reshape(B, S, n_heads * v_head_dim)
    return out @ params["w_o"], (c_kv, k_rope)


def mla_decode(params, x, position, cache, *, n_heads, kv_lora_rank, qk_nope_dim,
               qk_rope_dim, v_head_dim, rope_theta, window=None, absorbed=False):
    """Decode with the compressed cache {"c_kv": (B,Sc,r), "k_rope": (B,Sc,dr), "pos"}.

    ``absorbed=False`` (paper-exact naive path) re-expands k/v for the whole cache.
    ``absorbed=True`` folds w_uk into the query and w_uv into the output so the
    attention runs directly in the latent space — a beyond-paper perf variant.
    """
    B, _, _ = x.shape
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkr(
        params, x, position[:, None], n_heads, qk_nope_dim, qk_rope_dim, rope_theta)
    Sc = cache["c_kv"].shape[1]
    slot = (position % Sc).astype(jnp.int32)
    b_idx = jnp.arange(B)
    c_kv = cache["c_kv"].at[b_idx, slot].set(c_kv_new[:, 0])
    k_rope = cache["k_rope"].at[b_idx, slot].set(k_rope_new[:, 0])
    kv_pos = cache["pos"].at[b_idx, slot].set(position.astype(jnp.int32))
    scale = (qk_nope_dim + qk_rope_dim) ** -0.5

    if absorbed:
        # q_lat[b,h,r] = sum_d q_nope[b,h,d] * w_uk[r, h*dn+d]
        w_uk = params["w_uk"].reshape(kv_lora_rank, n_heads, qk_nope_dim)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                           w_uk.astype(jnp.float32))
        s = jnp.einsum("bhr,bkr->bhk", q_lat, c_kv.astype(jnp.float32))
        s = s + jnp.einsum("bhd,bkd->bhk", q_rope[:, 0].astype(jnp.float32),
                           k_rope.astype(jnp.float32))
        s = s * scale
        mask = (kv_pos >= 0) & (kv_pos <= position[:, None])
        if window is not None:
            mask &= (position[:, None] - kv_pos) < window
        p = jax.nn.softmax(jnp.where(mask[:, None, :], s, NEG_INF), axis=-1)
        o_lat = jnp.einsum("bhk,bkr->bhr", p, c_kv.astype(jnp.float32))
        w_uv = params["w_uv"].reshape(kv_lora_rank, n_heads, v_head_dim)
        out = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv.astype(jnp.float32))
        out = out.reshape(B, 1, n_heads * v_head_dim).astype(x.dtype)
    else:
        k_nope, v = _mla_expand_kv(params, c_kv, n_heads, qk_nope_dim, v_head_dim)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, Sc, n_heads, qk_rope_dim))], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)[:, :, :, None, :]
        out = decode_attention(q_full, k_full, v, position, kv_pos, window=window,
                               softmax_scale=scale)
        out = out.reshape(B, 1, n_heads * v_head_dim)
    new_cache = {"c_kv": c_kv, "k_rope": k_rope, "pos": kv_pos}
    return out @ params["w_o"], new_cache
