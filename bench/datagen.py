"""Learner data for a cell, made from the seed by the benchmark itself.

One general generator reads the ``data`` block of a configuration file:

- ``"kind": "classifier"``: Gaussian class clusters (``n_classes`` centres
  of norm ``class_sep * dim ** 0.25`` in ``dim`` features, unit noise),
  ``n_train`` training and ``n_test`` held-out samples, split over the
  learners by ``mapping``: ``"fedscale"`` gives power-law shard sizes
  (Zipf exponent ``zipf_a``, at least 2 samples each) over a random
  permutation, as FedScale's per-client mapping does.
- ``"kind": "tokens"``: ``per_learner`` sequences of ``seq_len`` tokens
  for each learner and ``n_test`` held-out ones, drawn from a Zipf unigram
  law (exponent ``zipf_s``) over ``vocab`` ids; the label of a position is
  the next token.

The program under test receives the arrays; the reference reads the same
arrays. Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, stream])


def _classifier(spec: dict, n_learners: int, seed: int) -> dict:
    rng = _rng(seed, 1)
    c, dim = int(spec["n_classes"]), int(spec["dim"])
    centres = rng.standard_normal((c, dim))
    centres *= (float(spec["class_sep"]) * dim ** 0.25
                / np.linalg.norm(centres, axis=1, keepdims=True))

    def sample(n):
        y = rng.integers(0, c, size=n)
        x = centres[y] + rng.standard_normal((n, dim))
        return x.astype(np.float32), y.astype(np.int32)

    x_tr, y_tr = sample(int(spec["n_train"]))
    x_te, y_te = sample(int(spec["n_test"]))
    if spec["mapping"] != "fedscale":
        raise ValueError(f"unknown mapping {spec['mapping']!r}")
    n = len(y_tr)
    sizes = rng.zipf(float(spec["zipf_a"]), size=n_learners).astype(float)
    sizes = np.maximum(sizes / sizes.sum() * n, 2).astype(int)
    perm = rng.permutation(n)
    shards, off = [], 0
    for s in sizes:
        shards.append(perm[off:off + s] if off < n else perm[-s:])
        off += s
    return dict(x_train=x_tr, y_train=y_tr, x_test=x_te, y_test=y_te,
                shards=shards, n_classes=c)


def _tokens(spec: dict, n_learners: int, seed: int) -> dict:
    rng = _rng(seed, 2)
    vocab, s = int(spec["vocab"]), int(spec["seq_len"])
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(spec["zipf_s"])
    p /= p.sum()
    per = int(spec["per_learner"])

    def sample(n):
        t = rng.choice(vocab, size=(n, s + 1), p=p).astype(np.int32)
        return t[:, :-1].copy(), t[:, 1:].copy()

    x_tr, y_tr = sample(per * n_learners)
    x_te, y_te = sample(int(spec["n_test"]))
    shards = [np.arange(i * per, (i + 1) * per) for i in range(n_learners)]
    return dict(x_train=x_tr, y_train=y_tr, x_test=x_te, y_test=y_te,
                shards=shards, vocab=vocab)


def make(spec: dict, n_learners: int, seed: int) -> dict:
    """The cell's arrays: x/y train and test, and one index shard per
    learner."""
    if spec["kind"] == "classifier":
        return _classifier(spec, n_learners, seed)
    if spec["kind"] == "tokens":
        return _tokens(spec, n_learners, seed)
    raise ValueError(f"unknown data kind {spec['kind']!r}")
