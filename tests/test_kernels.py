"""Pallas kernel sweeps: shapes x dtypes, allclose vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.staleness_agg import ops as agg_ops
from repro.kernels.staleness_agg import ref as agg_ref
from repro.kernels.staleness_agg.staleness_agg import (
    BLOCK_VMEM, D_BLK, col_block, col_quantum, sweep_fused_staleness_apply)
from repro.kernels.swa_attention import ops as swa_ops
from repro.kernels.swa_attention import ref as swa_ref
from repro.kernels.wkv6 import ops as wkv_ops
from repro.kernels.wkv6.ref import wkv6_scan


# ---------------------------------------------------------------------------
# staleness_agg
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,D", [(2, 2048), (5, 2048), (8, 4096 + 77),
                                 (3, 1000), (16, 8192)])
@pytest.mark.parametrize("rule", ["equal", "dynsgd", "adasgd", "relay"])
def test_staleness_agg_matches_oracle(n, D, rule):
    rng = np.random.default_rng(n * D)
    U = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    fresh = jnp.asarray([True] + list(rng.random(n - 1) < 0.5))
    tau = jnp.where(fresh, 0, jnp.asarray(rng.integers(1, 6, n)))
    agg_k, w_k = agg_ops.staleness_aggregate(U, fresh, tau, rule=rule)
    agg_r, w_r = agg_ref.staleness_aggregate_ref(U, fresh, tau, rule=rule)
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(agg_k), np.asarray(agg_r),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n,D", [(2, 2048), (5, 2048), (8, 4096 + 77)])
@pytest.mark.parametrize("rule", ["equal", "dynsgd", "adasgd", "relay"])
def test_fused_staleness_agg_matches_two_pass(n, D, rule):
    """Single-traversal fused kernel == two-launch pipeline == jnp oracle."""
    rng = np.random.default_rng(n + D)
    U = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    fresh = jnp.asarray([True] + list(rng.random(n - 1) < 0.5))
    tau = jnp.where(fresh, 0, jnp.asarray(rng.integers(1, 6, n)))
    agg_f, w_f = agg_ops.staleness_aggregate(U, fresh, tau, rule=rule,
                                             fused=True)
    agg_2, w_2 = agg_ops.staleness_aggregate(U, fresh, tau, rule=rule,
                                             fused=False)
    agg_r, w_r = agg_ref.staleness_aggregate_ref(U, fresh, tau, rule=rule)
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(agg_f), np.asarray(agg_r),
                               rtol=1e-4, atol=1e-5)


def test_fused_staleness_apply_in_place_step():
    """params + lr * aggregate, computed in the same grid traversal with the
    params buffer aliased input->output."""
    rng = np.random.default_rng(42)
    n, D = 6, 4096 + 33
    U = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    p0 = jnp.asarray(rng.standard_normal(D), jnp.float32)
    fresh = jnp.asarray([True, True, True, False, False, False])
    tau = jnp.asarray([0, 0, 0, 2, 3, 5], jnp.int32)
    agg_r, w_r = agg_ref.staleness_aggregate_ref(U, fresh, tau, rule="relay")
    new_p, w = agg_ops.staleness_apply(p0, U, fresh, tau, rule="relay",
                                       server_lr=0.5)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_p),
                               np.asarray(p0 + 0.5 * agg_r),
                               rtol=1e-4, atol=1e-5)


# (n, D): the first a bucket-padded cohort at the narrowest block; the rest
# at widths the wrappers pad to where every n takes blocks wider than
# D_BLK, two or more of them: 131,072 columns for one or two rows, 65,536
# for three, 32,768 for eight, 4,096 for 64
SWEEP_SHAPES = [(6, 4096 + 33), (1, 2**18 - 100), (2, 2**18 - 100),
                (3, 2**18 - 100), (8, 2**16 - 50), (64, 2**13 - 5)]


@pytest.mark.parametrize("n,D", SWEEP_SHAPES)
@pytest.mark.parametrize("rule", ["equal", "relay"])
def test_sweep_staleness_apply_matches_aggregate_kernel(rule, n, D):
    """Sweep-axis fused server step == the sweep aggregate kernel's result
    applied with per-cell lr (same blockwise partials math, params buffer
    aliased input->output), both match the jnp oracle per cell, and an
    all-invalid cell keeps its bits."""
    d_pad = D + (-D) % col_quantum(D)
    blk = col_block(n, d_pad)
    assert (blk > D_BLK and d_pad // blk >= 2) or D == 4096 + 33
    rng = np.random.default_rng(7)
    S = 3
    U = rng.standard_normal((S, n, D)).astype(np.float32)
    params = rng.standard_normal((S, D)).astype(np.float32)
    fresh = rng.random((S, n)) < 0.5
    fresh[:, 0] = True
    tau = np.where(fresh, 0, rng.integers(1, 5, (S, n))).astype(np.int32)
    valid = np.ones((S, n), bool)
    valid[2] = False                                  # all-invalid cell
    beta = np.array([0.2, 0.35, 0.5], np.float32)
    lr = np.array([1.0, 0.5, 2.0], np.float32)
    agg_k, w_k = agg_ops.sweep_staleness_aggregate(U, fresh, tau, valid=valid,
                                                   rule=rule, beta=beta)
    new_p, w_a = agg_ops.sweep_staleness_apply(params, U, fresh, tau,
                                               valid=valid, rule=rule,
                                               beta=beta, server_lr=lr)
    np.testing.assert_array_equal(np.asarray(w_a), np.asarray(w_k))
    np.testing.assert_array_equal(
        np.asarray(new_p), params + lr[:, None] * np.asarray(agg_k))
    np.testing.assert_array_equal(np.asarray(new_p)[2], params[2])
    for s in (0, 1):
        agg_r, w_r = agg_ref.staleness_aggregate_ref(
            jnp.asarray(U[s]), jnp.asarray(fresh[s]), jnp.asarray(tau[s]),
            rule=rule, beta=float(beta[s]))
        np.testing.assert_allclose(np.asarray(w_k)[s], np.asarray(w_r),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(agg_k)[s], np.asarray(agg_r),
                                   rtol=1e-4, atol=1e-5)


def test_one_fresh_row_applies_exactly():
    """A one-row operand that is the mean of n_F fresh updates weighs
    n_F / n_F = 1 exactly, so the server step is params + lr * u bit for
    bit at the widest block (the streamed round when no stale row lands)."""
    rng = np.random.default_rng(3)
    d = 2**18
    assert col_block(1, d) == 2**17
    u = rng.standard_normal((1, 1, d)).astype(np.float32)
    params = rng.standard_normal((1, d)).astype(np.float32)
    lr = np.float32(0.5)   # a power of two: no multiply-add contraction moves a bit
    new_p, w = sweep_fused_staleness_apply(
        params, u, np.full((1, 1), 8.0, np.float32), np.zeros((1, 1), np.int32),
        np.ones((1, 1), bool), np.array([[0.35, lr]], np.float32))
    np.testing.assert_array_equal(np.asarray(w), [[1.0]])
    np.testing.assert_array_equal(np.asarray(new_p), params + lr * u[:, 0])


@pytest.mark.parametrize("n", [1, 2, 3, 8, 16, 64, 127, 512])
@pytest.mark.parametrize("d", [14_336, 215_040, 2**18, 279_707_648])
def test_col_block_divides_and_fits(n, d):
    """The block divides the padded width, is a multiple of D_BLK, its
    double-buffered blocks (n operand rows, params, out) fit BLOCK_VMEM,
    and a cohort of more than about 120 rows keeps D_BLK."""
    blk = col_block(n, d)
    assert d % blk == 0 and blk % D_BLK == 0
    assert blk == D_BLK or 2 * 4 * (n + 2) * blk <= BLOCK_VMEM
    if n >= 64:
        assert blk <= 2 * D_BLK
    if n >= 127:
        assert blk == D_BLK


@pytest.mark.parametrize("d,quantum", [
    (12_835, 2048), (213_312, 2048), (2**18 - 100, 2**17),
    (279_597_312, 2**17)])
def test_col_quantum_keeps_the_padding_small(d, quantum):
    """Rows pad to the widest one-row block that adds at most d / 1024
    columns, else to D_BLK: MiniCPM-2B's 279,597,312 columns gain 110,336
    (0.04%), the small learners keep their 2048-multiple widths."""
    q = col_quantum(d)
    assert q == quantum
    d_pad = d + (-d) % q
    assert d_pad - d <= max(2047, d // 1024)
    assert col_block(1, d_pad) == q


def test_staleness_agg_deviation_partials():
    from repro.kernels.staleness_agg.staleness_agg import deviation_partials
    from repro.kernels.staleness_agg.ref import deviation_partials_ref
    rng = np.random.default_rng(0)
    U = jnp.asarray(rng.standard_normal((6, 4096)), jnp.float32)
    fresh = jnp.asarray([True, True, True, False, False, False])
    num_k, den_k = deviation_partials(U, fresh)
    num_r, den_r = deviation_partials_ref(U, fresh)
    np.testing.assert_allclose(np.asarray(num_k), np.asarray(num_r), rtol=1e-4)
    np.testing.assert_allclose(float(den_k), float(den_r), rtol=1e-5)


# ---------------------------------------------------------------------------
# swa_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,Hkv,Dh,W", [
    (1, 256, 2, 1, 64, 128),
    (2, 384, 4, 2, 64, 256),
    (1, 200, 2, 2, 128, 128),   # unaligned S -> padding path
    (1, 512, 8, 2, 64, 384),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_attention_matches_oracle(B, S, H, Hkv, Dh, W, dtype):
    rng = np.random.default_rng(S + W)
    q = jnp.asarray(rng.standard_normal((B, S, H, Dh)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, Dh)), dtype)
    out_k = swa_ops.swa_attention(q, k, v, window=W)
    out_r = swa_ref.swa_attention_ref(q, k, v, window=W)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)


def test_swa_attention_respects_window():
    """Tokens beyond the window must have zero influence."""
    B, S, H, Dh, W = 1, 384, 1, 64, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, Dh)), jnp.float32)
    out1 = swa_ops.swa_attention(q, k, v, window=W)
    # perturb keys/values far outside the last query's window
    k2 = k.at[:, :S - W - 1].set(rng.standard_normal((B, S - W - 1, H, Dh)))
    v2 = v.at[:, :S - W - 1].set(rng.standard_normal((B, S - W - 1, H, Dh)))
    out2 = swa_ops.swa_attention(q, k2, v2, window=W)
    np.testing.assert_allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,N", [(2, 128, 2, 16), (1, 200, 3, 32),
                                     (2, 256, 1, 64), (1, 384, 4, 8)])
def test_wkv6_matches_oracle(B, S, H, N):
    rng = np.random.default_rng(B * S)
    r, k, v = (jnp.asarray(rng.standard_normal((B, S, H, N)), jnp.float32) * 0.5
               for _ in range(3))
    w = jnp.asarray(rng.uniform(0.8, 0.999, (B, S, H, N)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, N)), jnp.float32) * 0.1
    s0 = jnp.asarray(rng.standard_normal((B, H, N, N)), jnp.float32) * 0.1
    y_k, s_k = wkv_ops.wkv6(r, k, v, w, u, state0=s0)
    y_r, s_r = wkv6_scan(r, k, v, w, u, state0=s0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-4, atol=1e-5)


def test_wkv6_state_continuation():
    """Running [0:S/2] then [S/2:S] with carried state == one full pass."""
    B, S, H, N = 1, 256, 2, 16
    rng = np.random.default_rng(7)
    r, k, v = (jnp.asarray(rng.standard_normal((B, S, H, N)), jnp.float32) * 0.5
               for _ in range(3))
    w = jnp.asarray(rng.uniform(0.9, 0.999, (B, S, H, N)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, N)), jnp.float32) * 0.1
    y_full, s_full = wkv_ops.wkv6(r, k, v, w, u)
    h = S // 2
    y1, s1 = wkv_ops.wkv6(r[:, :h], k[:, :h], v[:, :h], w[:, :h], u)
    y2, s2 = wkv_ops.wkv6(r[:, h:], k[:, h:], v[:, h:], w[:, h:], u, state0=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-4, atol=1e-5)


def test_wkv6_compiled_call_raises():
    """wkv6 has no TPU lowering: asking for the compiled kernel raises a
    clear error up front instead of failing inside the compiler."""
    B, S, H, N = 1, 128, 1, 8
    x = jnp.zeros((B, S, H, N), jnp.float32)
    with pytest.raises(NotImplementedError, match="no TPU lowering"):
        wkv_ops.wkv6(x, x, x, x + 0.9, jnp.zeros((H, N)), interpret=False)
