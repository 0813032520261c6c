"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and refuses what the chip would refuse (block shapes off the
tiling, primitives without a TPU lowering) — what interpret-mode tests on
the CPU cannot see.  Each test asserts the kernel survived as a
``tpu_custom_call`` in the compiled HLO.

The topology is described inside a module fixture, never at import, and
every test skips where it cannot be described.  The persistent
compilation cache is off around these compiles: an entry written for a
described chip cannot be read back without one.

Widths: the mlp learner's D=12,835 pads to 14,336 and the transformer
learner's D=213,312 to 215,040 (``col_quantum``: 2048 columns at these
widths); n=16 is a bucket-padded cohort.  MiniCPM-2B at 4 layers and
15,360 ids, D=279,597,312, pads to 279,707,648 (131,072 columns): the
streamed round's operand, whose one to three rows take 64K-128K-column
blocks.  At 2**18 columns a 64-row cohort takes 4,096-column blocks and
three rows take 65,536.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.staleness_agg import staleness_agg as sa
from repro.kernels.swa_attention import ops as swa_ops
from repro.kernels.trimmed_agg import ops as trimmed_ops

N = 16
D_MLP = 14_336
D_LM = 215_040
D_MINICPM = 279_707_648


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def tpu_hlo(one_chip, no_persistent_cache):
    """Compile ``fn`` for one described v5e chip at the given
    ``(shape, dtype)`` arguments; returns the compiled HLO text."""
    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return compile_


F32, I32, BOOL = jnp.float32, jnp.int32, jnp.bool_


@pytest.mark.parametrize("d", [D_MLP, D_LM])
def test_fused_staleness_apply_compiles(tpu_hlo, d):
    hlo = tpu_hlo(
        lambda p, u, f, t: sa.fused_staleness_apply(
            p, u, f, t, 0.35, 1.0, interpret=False),
        ((d,), F32), ((N, d), F32), ((N,), BOOL), ((N,), I32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n,d", [(N, D_MLP), (64, 2**18)])
def test_sweep_fused_staleness_apply_compiles_g4(tpu_hlo, n, d):
    g = 4
    hlo = tpu_hlo(
        lambda p, u, f, t, v, s: sa.sweep_fused_staleness_apply(
            p, u, f, t, v, s, interpret=False),
        ((g, d), F32), ((g, n, d), F32), ((g, n), BOOL),
        ((g, n), I32), ((g, n), BOOL), ((g, 2), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n,d", [(N, D_MLP), (3, 2**18)])
def test_sweep_fused_staleness_aggregate_compiles_g4(tpu_hlo, n, d):
    g = 4
    hlo = tpu_hlo(
        lambda u, f, t, b, v: sa.sweep_fused_staleness_aggregate(
            u, f, t, b, v, interpret=False),
        ((g, n, d), F32), ((g, n), BOOL), ((g, n), I32), ((g,), F32),
        ((g, n), BOOL))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("ns", [0, 2])
def test_streamed_staleness_apply_compiles(tpu_hlo, ns):
    """The streamed round's call: the fresh mean, carrying its fresh count,
    and ``ns`` landing stale rows, at MiniCPM-2B's padded width."""
    hlo = tpu_hlo(
        lambda p, u, f, t, v, s: sa.sweep_fused_staleness_apply(
            p, u, f, t, v, s, interpret=False),
        ((1, D_MINICPM), F32), ((1, 1 + ns, D_MINICPM), F32),
        ((1, 1 + ns), F32), ((1, 1 + ns), I32), ((1, 1 + ns), BOOL),
        ((1, 2), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("g", [1, 4])
def test_sweep_trimmed_aggregate_compiles(tpu_hlo, g):
    hlo = tpu_hlo(
        lambda y, k, c: trimmed_ops.sweep_trimmed_aggregate(
            y, k, c, interpret=False),
        ((g, N, D_MLP), F32), ((g,), I32), ((g,), I32))
    assert "tpu_custom_call" in hlo


def test_swa_attention_forward_compiles(tpu_hlo):
    # the transformer learner's default knobs: a local batch of 16
    # sequences of 64 tokens, 2 heads of 32 (d_model 64)
    b, s, h, h_kv, dh = 16, 64, 2, 2, 32
    hlo = tpu_hlo(
        lambda q, k, v: swa_ops.swa_attention(q, k, v, window=128,
                                              interpret=False),
        ((b, s, h, dh), F32), ((b, s, h_kv, dh), F32),
        ((b, s, h_kv, dh), F32))
    assert "tpu_custom_call" in hlo
