"""Operations and bytes, counted from a configuration's shapes.

These are the algorithm's counts, not the compiler's: they do not change
when the implementation does. A model's own counts are in its kind's file
(``models/<kind>.py``); the SAA kernel's are here.

- Parameters: every weight of the model as its equations define it.
- Training FLOP: 6 per matmul weight per sample or token (forward 2,
  backward 4), plus, for attention, 12 * seq_len * d_model per layer and
  token (the score and value products over the whole sequence, forward and
  backward, as PaLM's accounting counts them). Elementwise work, norms and
  the embedding gather are left out.
- The SAA kernel's least work for one aggregation group of ``n`` rows of
  width ``D`` in float32: read the ``(n, D)`` operand once, read the
  ``(D,)`` global row once and write it once; ``2 n D`` FLOP for the
  weighted sum and ``2 D`` for the server step.
"""
from __future__ import annotations

import kinds


def params(m: dict) -> int:
    """Parameters of a configuration's ``model`` block, counted by its
    kind's file (``models/<kind>.py``)."""
    return kinds.model(m["kind"]).params(m)


def train_flop_per_sample(m: dict, seq_len: int = 0) -> float:
    """FLOP of one trained sample (a feature row, or a whole sequence of
    ``seq_len`` tokens), counted by the kind's file."""
    return kinds.model(m["kind"]).train_flop_per_sample(m, seq_len)


def saa_min_bytes(n: int, d: int) -> float:
    return 4.0 * (n * d + 2 * d)


def saa_flop(n: int, d: int) -> float:
    return 2.0 * n * d + 2.0 * d
