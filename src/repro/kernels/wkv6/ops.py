"""Jit wrapper matching the model-side (B, S, H, N) layout + padding."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.wkv6.wkv6 import CHUNK, wkv6_bhsn


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6(r, k, v, w, u, state0=None, *, interpret: bool | None = None):
    """r,k,v,w: (B, S, H, N); u: (H, N); state0: (B, H, N, N) | None.

    Returns (y (B, S, H, N), final state (B, H, N, N)).  Interpreter only:
    the per-step row slicing inside the chunk has no TPU lowering, so a
    compiled call raises instead of failing deep in the compiler.
    """
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            "the wkv6 Pallas kernel has no TPU lowering (per-step "
            "dynamic_slice of a loaded chunk); use the jnp scan "
            "(repro.models.rwkv6.wkv6_scan) or interpret=True")
    B, S, H, N = r.shape
    pad = (-S) % CHUNK
    if pad:
        # pad with w=1 (identity decay) and k=0 so padded steps leave S alone
        pz = ((0, 0), (0, pad), (0, 0), (0, 0))
        r = jnp.pad(r, pz)
        k = jnp.pad(k, pz)
        v = jnp.pad(v, pz)
        w = jnp.pad(w, pz, constant_values=1.0)
    Sp = S + pad

    def to_bh(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, Sp, N)

    s0 = (jnp.zeros((B * H, N, N), jnp.float32) if state0 is None
          else state0.reshape(B * H, N, N).astype(jnp.float32))
    u_bh = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, 1, N)
    y, s_fin = wkv6_bhsn(to_bh(r).astype(jnp.float32), to_bh(k).astype(jnp.float32),
                         to_bh(v).astype(jnp.float32), to_bh(w).astype(jnp.float32),
                         u_bh.astype(jnp.float32), s0, interpret=True)
    y = y.reshape(B, H, Sp, N).transpose(0, 2, 1, 3)[:, :S]
    return y.astype(v.dtype), s_fin.reshape(B, H, N, N)
