# Pallas TPU kernels for the compute hot-spots this system adds or relies on:
#   staleness_agg  — fused SAA deviation + weighted aggregation (server side)
#   trimmed_agg    — rank-select trimmed-mean band (robust aggregation)
#   swa_attention  — sliding-window flash attention (forward only)
#   wkv6           — RWKV6 data-dependent-decay recurrence (chunked scan;
#                    interpreter only: no TPU lowering yet)
# Each package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper),
# ref.py (pure-jnp oracle).  Tests run them in interpret mode on the CPU;
# tests/test_tpu_compile.py compiles them for a described TPU v5e.
from __future__ import annotations

import jax


def resolve_interpret(interpret):
    """The ``interpret`` flag every kernel entry point takes: ``None``
    means compiled on a TPU backend and the Pallas interpreter elsewhere
    (CPU tests / CI); an explicit bool is kept as given."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
