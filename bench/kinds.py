"""Files of the benchmark found by name and loaded by path.

A configuration's ``model`` block names its ``kind``; the reference model
of that kind is ``models/<kind>.py``. A configuration adds its model as a
file there, next to nothing else. Each such file defines:

- ``init(key, m)``: the initial weights from a PRNG key, in the tree the
  program's learner builds (``m`` is the configuration's ``model`` block);
- ``loss(p, x, y, prec, m)``: ``(logits, per-example loss)`` with matmuls
  at precision ``prec``;
- ``params(m)`` and ``train_flop_per_sample(m, seq_len)``: the kind's
  counts, from its shapes (``counts/work.py`` says what they count).

Nothing here imports the program.
"""
from __future__ import annotations

import importlib.util
import pathlib

MODELS = pathlib.Path(__file__).resolve().parent / "models"


def module(path: pathlib.Path):
    """A bench file loaded by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model(kind: str):
    """The reference model of ``kind``: ``models/<kind>.py``."""
    path = MODELS / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"no reference model of kind {kind!r}: {path} is "
                         f"missing")
    return module(path)
