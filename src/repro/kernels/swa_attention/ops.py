"""Jit wrapper: (B, S, H, Dh) layout handling, padding, GQA head mapping."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.swa_attention.swa_attention import BLK, swa_attention_bhsd


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def swa_attention(q, k, v, *, window: int, interpret: bool | None = None):
    """q: (B, S, H, Dh); k, v: (B, S, Hkv, Dh) -> (B, S, H, Dh).

    Pads S to the 128 block and window to a block multiple (a slightly larger
    window is attention-superset-safe only at block granularity, so we keep
    the *exact* window by requiring window % BLK == 0 — configs use 8192).
    Forward only: there is no VJP, so ``jax.grad`` through it fails.
    """
    assert window % BLK == 0, "window must be a multiple of the 128 tile"
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    pad = (-S) % BLK
    if pad:
        zq = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(t, zq) for t in (q, k, v))
    Sp = S + pad
    qb = q.transpose(0, 2, 1, 3).reshape(B * H, Sp, Dh)
    kb = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sp, Dh)
    vb = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sp, Dh)
    # mask padded keys structurally: kernel masks k_pos >= seq_len
    out = swa_attention_bhsd(qb, kb, vb, window=window, n_kv_heads=Hkv,
                             interpret=interpret)
    out = out.reshape(B, H, Sp, Dh).transpose(0, 2, 1, 3)
    return out[:, :S]
