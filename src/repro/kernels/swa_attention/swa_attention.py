"""Sliding-window flash attention Pallas TPU kernel (prefill / full-seq path).

The band structure is exploited *structurally*: the kv grid dimension only
spans the ``window/BLK + 1`` blocks that can intersect each query block's
band, so compute is O(S * window) instead of O(S^2) — this is what makes
``long_500k`` viable on the dense assigned architectures.

Grid: (B * H, n_q_blocks, n_band_blocks), innermost sequential; the online
softmax state (m, l, acc) lives in VMEM scratch across the band sweep.
Out-of-range band positions (left edge) load a clamped block and are fully
masked, which wastes at most one block per row.  BlockSpec tiles are
(BLK=128) x d_head — MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret

BLK = 128
NEG_INF = -1e30


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                window: int, n_band: int, seq_len: int, scale: float):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_blk = qi - (n_band - 1) + j               # raw band block index
    q = q_ref[0].astype(jnp.float32)             # (BLK, Dh)
    k = k_ref[0].astype(jnp.float32)             # (BLK, Dh)
    v = v_ref[0].astype(jnp.float32)

    q_pos = qi * BLK + jax.lax.broadcasted_iota(jnp.int32, (BLK, BLK), 0)
    k_pos = kv_blk * BLK + jax.lax.broadcasted_iota(jnp.int32, (BLK, BLK), 1)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    mask = (k_pos >= 0) & (k_pos < seq_len) & (k_pos <= q_pos) \
        & (q_pos - k_pos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1)
    acc_new = acc_prev * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    @pl.when(j == n_band - 1)
    def _finalize():
        o_ref[0] = (acc_new / jnp.maximum(l_new, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "n_kv_heads", "interpret"))
def swa_attention_bhsd(q, k, v, *, window: int, n_kv_heads: int,
                       interpret: bool | None = None):
    """q: (BH, S, Dh); k, v: (B*Hkv, S, Dh); S % BLK == 0; window % BLK == 0.

    Query head bh maps to kv head bh // (H // Hkv) via the BlockSpec index map.
    """
    BH, S, Dh = q.shape
    BHkv = k.shape[0]
    G = BH // BHkv
    n_q = S // BLK
    n_band = window // BLK + 1
    scale = Dh ** -0.5

    kernel = functools.partial(_swa_kernel, window=window, n_band=n_band,
                               seq_len=S, scale=scale)

    def kv_index(bh, qi, j):
        blk = qi - (n_band - 1) + j
        return (bh // G, jnp.maximum(blk, 0), 0)

    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_band),
        in_specs=[
            pl.BlockSpec((1, BLK, Dh), lambda bh, qi, j: (bh, qi, 0)),
            pl.BlockSpec((1, BLK, Dh), kv_index),
            pl.BlockSpec((1, BLK, Dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, BLK, Dh), lambda bh, qi, j: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BLK,), jnp.float32),
            pltpu.VMEM((BLK,), jnp.float32),
            pltpu.VMEM((BLK, Dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
