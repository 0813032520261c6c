"""Operations and bytes, counted from a configuration's shapes.

These are the algorithm's counts, not the compiler's: they do not change
when the implementation does.

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


def mlp_params(m: dict) -> int:
    d, h, c = int(m["dim"]), int(m["hidden"]), int(m["n_classes"])
    return d * h + h + h * c + c


def mlp_train_flop_per_sample(m: dict) -> float:
    d, h, c = int(m["dim"]), int(m["hidden"]), int(m["n_classes"])
    return 6.0 * (d * h + h * c)


def _lm_dims(m: dict):
    return (int(m["hidden_size"]), int(m["intermediate_size"]),
            int(m["vocab_size"]), int(m["num_hidden_layers"]))


def transformer_params(m: dict) -> int:
    d, f, v, n_layers = _lm_dims(m)
    per_layer = 4 * d * d + 3 * d * f + 2 * d      # attention, SwiGLU, norms
    return 2 * v * d + d + n_layers * per_layer    # embedding, head, norm


def transformer_train_flop_per_token(m: dict, seq_len: int) -> float:
    d, f, v, n_layers = _lm_dims(m)
    matmul = n_layers * (4 * d * d + 3 * d * f) + d * v
    return 6.0 * matmul + 12.0 * n_layers * seq_len * d


def params(m: dict) -> int:
    return {"mlp": mlp_params,
            "transformer": transformer_params}[m["kind"]](m)


def train_flop_per_sample(m: dict, seq_len: int = 0) -> float:
    """FLOP of one trained sample: a feature row for the mlp, a whole
    sequence of ``seq_len`` tokens for the transformer."""
    if m["kind"] == "mlp":
        return mlp_train_flop_per_sample(m)
    return seq_len * transformer_train_flop_per_token(m, seq_len)


def saa_min_bytes(n: int, d: int) -> float:
    return 4.0 * (n * d + 2 * d)


def saa_flop(n: int, d: int) -> float:
    return 2.0 * n * d + 2.0 * d
