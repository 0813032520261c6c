"""RWKV6 WKV recurrence Pallas TPU kernel (chunked sequential scan).

    y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T) ;  S_t = diag(w_t) S_{t-1} + k_t v_t^T

The GPU reference implementation (RWKV-CUDA) assigns one thread per (head,
channel) and marches time in registers.  The TPU adaptation keeps the
(N x N) per-head state resident in VMEM scratch across a grid of time chunks
(grid innermost = chunk index, sequential on TPU), and expresses each step's
rank-1 update as (N,1)x(1,N) outer products on the VPU.  HBM traffic is one
read of (r,k,v,w) and one write of y per chunk — the state never leaves VMEM.

Grid: (B*H, n_chunks); blocks: (CHUNK, N) per operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import resolve_interpret

CHUNK = 128


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, s_out_ref,
                 s_scr, *, n_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    r = r_ref[0].astype(jnp.float32)   # (CHUNK, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)   # (1, N) bonus, per head

    def step(t, carry):
        s, y = carry                    # s: (N, N) keyed k-dim x v-dim
        kt = k[t][:, None]              # (N, 1)
        vt = v[t][None, :]              # (1, N)
        kv = kt * vt                    # (N, N)
        yt = (r[t][:, None] * (s + u.T * kv)).sum(axis=0)   # (N,)
        y = y.at[t].set(yt)
        s = w[t][:, None] * s + kv
        return s, y

    s0 = s_scr[...]
    y0 = jnp.zeros_like(r)
    s_fin, y = jax.lax.fori_loop(0, r.shape[0], step, (s0, y0))
    s_scr[...] = s_fin
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(c == n_chunks - 1)
    def _final():
        s_out_ref[0] = s_fin


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6_bhsn(r, k, v, w, u, s0, *, interpret: bool | None = None):
    """r,k,v,w: (BH, S, N); u: (BH, 1, N); s0: (BH, N, N); S % CHUNK == 0.

    Returns (y (BH, S, N), s_final (BH, N, N)).
    """
    BH, S, N = r.shape
    n_chunks = S // CHUNK
    kernel = functools.partial(_wkv6_kernel, n_chunks=n_chunks)
    seq_spec = pl.BlockSpec((1, CHUNK, N), lambda bh, c: (bh, c, 0))
    y, s_fin = pl.pallas_call(
        kernel,
        grid=(BH, n_chunks),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, 1, N), lambda bh, c: (bh, 0, 0)),
            pl.BlockSpec((1, N, N), lambda bh, c: (bh, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, N, N), lambda bh, c: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, N), v.dtype),
            jax.ShapeDtypeStruct((BH, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(r, k, v, w, u, s0)
    return y, s_fin
