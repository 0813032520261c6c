"""MoE router/dispatch unit + property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.models.moe import (load_balance_loss, moe_forward, moe_init,
                              router_topk)


def test_router_topk_normalized():
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((32, 8)),
                         jnp.float32)
    gates, idx = router_topk(logits, 3)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    assert idx.shape == (32, 3)
    assert len(np.unique(np.asarray(idx[0]))) == 3  # distinct experts


def test_topk_selects_argmax():
    logits = jnp.zeros((4, 8)).at[:, 5].set(10.0)
    _, idx = router_topk(logits, 1)
    assert (np.asarray(idx) == 5).all()


def test_load_balance_loss_uniform_is_one():
    """Perfectly uniform routing gives loss == E * E*(1/E^2) == 1."""
    N, E = 1024, 8
    logits = jnp.zeros((N, E))
    idx = jnp.tile(jnp.arange(E), N // E)[:N, None]
    lb = load_balance_loss(logits, idx, E)
    np.testing.assert_allclose(float(lb), 1.0, rtol=1e-2)


def test_load_balance_loss_penalizes_collapse():
    N, E = 1024, 8
    logits = jnp.zeros((N, E)).at[:, 0].set(5.0)
    idx = jnp.zeros((N, 1), jnp.int32)
    lb_collapsed = load_balance_loss(logits, idx, E)
    uniform_idx = jnp.tile(jnp.arange(E), N // E)[:N, None]
    lb_uniform = load_balance_loss(jnp.zeros((N, E)), uniform_idx, E)
    assert float(lb_collapsed) > 2 * float(lb_uniform)


@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 3), s=st.sampled_from([8, 16]),
       e=st.sampled_from([4, 8]), k=st.integers(1, 3), seed=st.integers(0, 20))
def test_moe_forward_properties(b, s, e, k, seed):
    d, f = 32, 16
    key = jax.random.PRNGKey(seed)
    p = moe_init(key, d, f, e, 1, f, jnp.float32)
    x = jax.random.normal(key, (b, s, d), jnp.float32)
    out, aux = moe_forward(p, x, n_experts=e, top_k=min(k, e), group_size=64)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(aux))


def test_moe_capacity_overflow_drops_tokens_gracefully():
    """With capacity_factor ~0, most tokens overflow — output stays finite and
    shrinks toward the shared-expert-only path."""
    d, f, e = 16, 8, 4
    key = jax.random.PRNGKey(0)
    p = moe_init(key, d, f, e, 0, f, jnp.float32)
    x = jax.random.normal(key, (2, 32, d), jnp.float32)
    full, _ = moe_forward(p, x, n_experts=e, top_k=2, group_size=64,
                          capacity_factor=4.0)
    tiny, _ = moe_forward(p, x, n_experts=e, top_k=2, group_size=64,
                          capacity_factor=0.01)
    assert bool(jnp.isfinite(tiny).all())
    assert float(jnp.abs(tiny).mean()) <= float(jnp.abs(full).mean()) + 1e-6


def test_rwkv_kernel_path_matches_scan_in_model():
    """cfg.use_kernels routes rwkv6 through the Pallas kernel — same logits."""
    from repro.models import ModelConfig, init_params
    from repro.models.transformer import forward
    import dataclasses
    cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                      d_ff=128, vocab_size=97, block_pattern=("rwkv6",),
                      rwkv_lora_rank=8, rwkv_w_lora_rank=8,
                      param_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 97)}
    x1, _, _ = forward(cfg, params, toks)
    cfg_k = dataclasses.replace(cfg, use_kernels=True)
    x2, _, _ = forward(cfg_k, params, toks)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2),
                               rtol=1e-4, atol=1e-4)


def test_swa_kernel_path_matches_blocked_in_model():
    """cfg.use_kernels + sliding window routes GQA through the Pallas flash-SWA
    kernel — same hidden states as the blocked-jnp path."""
    from repro.models import ModelConfig, init_params
    from repro.models.transformer import forward
    import dataclasses
    cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=128, vocab_size=97, window=128,
                      param_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0, 97)}
    x1, _, _ = forward(cfg, params, toks)
    x2, _, _ = forward(dataclasses.replace(cfg, use_kernels=True), params, toks)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("window", [None, 64])
def test_swa_kernel_path_rejects_untileable_window(window):
    """use_kernels with no window, or one off the kernel's 128 tile, raises
    instead of quietly taking the blocked-jnp path."""
    from repro.models import ModelConfig, init_params
    from repro.models.transformer import forward
    cfg = ModelConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                      d_ff=64, vocab_size=97, window=window,
                      use_kernels=True, param_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = {"tokens": jnp.zeros((1, 128), jnp.int32)}
    with pytest.raises(ValueError, match="multiple of"):
        forward(cfg, params, toks)


def test_fedprox_local_train():
    """FedProx's proximal term shrinks local drift from the global model."""
    from repro.sim.learner import local_train, mlp_init
    key = jax.random.PRNGKey(0)
    params = mlp_init(key, 16, 5)
    xs = jax.random.normal(key, (8, 4, 16))
    ys = jax.random.randint(key, (8, 4), 0, 5)
    d0, _, _ = local_train(params, xs, ys, 0.1, 0.0)
    dp, _, _ = local_train(params, xs, ys, 0.1, 1.0)
    n0 = sum(float(jnp.sum(x * x)) for x in jax.tree.leaves(d0))
    np_ = sum(float(jnp.sum(x * x)) for x in jax.tree.leaves(dp))
    assert np_ < n0  # proximal term bounds the delta
