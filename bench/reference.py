"""Plain reference of the federated round, written from the published
equations, importing nothing of the program.

The models:

- ``mlp``: ``relu(x W1 + b1) W2 + b2``, mean softmax cross-entropy.
- ``transformer``: pre-norm decoder layers (RMSNorm with a learned scale,
  eps 1e-5), multi-head causal attention with rotary positions (base
  10,000, rotate-half) and a 1/sqrt(head_dim) softmax scale, a SwiGLU
  feed-forward (``(silu(h Wg) * h Wu) Wd``), a final RMSNorm and an untied
  output head. The loss is the mean over sequences of the per-sequence mean
  next-token cross-entropy.

The round (REFL, arXiv:2111.01108, Alg. 2 and Eq. 2): every learner that
reports trains ``local_steps`` plain SGD steps of ``local_lr`` from the
round's global model and reports ``delta = w_local - w_global``. A
straggler's delta is kept under (learner, round of origin) until its
landing round. The server weighs fresh deltas 1 and a stale delta of
staleness tau ``(1 - beta) / (tau + 1) + beta * (1 - exp(-lam / lam_max))``
with ``lam = |u_F - (u_s + n_F u_F) / (n_F + 1)|^2 / |u_F|^2`` (``u_F`` the
fresh mean), normalises the weights to sum 1, and adds ``server_lr`` times
the weighted sum to the global model.

Which learners train, with which samples, and which deltas arrive fresh or
land stale is the host scheduler's decision: the replay takes it from the
round log that ``bench/run.py`` records and recomputes all of the
arithmetic.

``dtype``/``precision`` select the arithmetic: float32 at ``highest`` is the
reference; bfloat16 is the lower-precision control. ``half_batch`` plants a
fault (each local step trains on the first half of its batch only).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12


def _key(seed: int):
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(k, (int(seed) >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# Models
# ---------------------------------------------------------------------------


def _dense(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5


def init_mlp(key, m: dict):
    k1, k2 = jax.random.split(key)
    dim, hid, c = int(m["dim"]), int(m["hidden"]), int(m["n_classes"])
    return {"b1": jnp.zeros((hid,), jnp.float32),
            "b2": jnp.zeros((c,), jnp.float32),
            "w1": _dense(k1, (dim, hid)),
            "w2": _dense(k2, (hid, c))}


def init_transformer(key, m: dict):
    d, f, v = int(m["hidden_size"]), int(m["intermediate_size"]), \
        int(m["vocab_size"])
    ks = jax.random.split(key, 9)
    one = lambda a: a[None]         # the program stacks its one layer period
    layer = {
        "ffn": {"w_down": one(_dense(ks[0], (f, d))),
                "w_gate": one(_dense(ks[1], (d, f))),
                "w_up": one(_dense(ks[2], (d, f)))},
        "mixer": {"w_k": one(_dense(ks[3], (d, d))),
                  "w_o": one(_dense(ks[4], (d, d))),
                  "w_q": one(_dense(ks[5], (d, d))),
                  "w_v": one(_dense(ks[6], (d, d)))},
        "norm1": {"scale": one(jnp.ones((d,), jnp.float32))},
        "norm2": {"scale": one(jnp.ones((d,), jnp.float32))},
    }
    return {"embed": {"embedding": jax.random.normal(ks[7], (v, d)) * 0.02},
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
            "head": {"w_out": _dense(ks[8], (d, v))},
            "prefix": [],
            "stack": {"sub0": layer}}


def _mlp_loss(p, x, y, prec):
    h = jax.nn.relu(jnp.matmul(x, p["w1"], precision=prec) + p["b1"])
    logits = jnp.matmul(h, p["w2"], precision=prec) + p["b2"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return logits, logz - gold


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * scale


def _rope(x):
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, dh/2)
    cos, sin = (jnp.cos(ang)[None, :, None, :].astype(x.dtype),
                jnp.sin(ang)[None, :, None, :].astype(x.dtype))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _lm_loss(p, tok, y, prec, n_heads):
    mm = functools.partial(jnp.matmul, precision=prec)
    blk = jax.tree.map(lambda a: a[0], p["stack"]["sub0"])
    x = p["embed"]["embedding"][tok]
    b, s, d = x.shape
    dh = d // n_heads
    h = _rms(x, blk["norm1"]["scale"])
    q, k, v = (mm(h, blk["mixer"][w]).reshape(b, s, n_heads, dh)
               for w in ("w_q", "w_k", "w_v"))
    q, k = _rope(q), _rope(k)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) * dh ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v, precision=prec)
    x = x + mm(o.reshape(b, s, d), blk["mixer"]["w_o"])
    h = _rms(x, blk["norm2"]["scale"])
    ffn = blk["ffn"]
    x = x + mm(jax.nn.silu(mm(h, ffn["w_gate"])) * mm(h, ffn["w_up"]),
               ffn["w_down"])
    logits = mm(_rms(x, p["final_norm"]["scale"]), p["head"]["w_out"])
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return logits, (logz - gold).mean(-1)


class Model:
    """One configuration's reference model: init, per-example losses and
    eval, in the arithmetic that ``dtype``/``precision`` select."""

    def __init__(self, model: dict, dtype=jnp.float32, precision="highest"):
        self.spec = model
        self.dtype = dtype
        if model["kind"] == "mlp":
            self._init = init_mlp
            self._loss = functools.partial(_mlp_loss, prec=precision)
        elif model["kind"] == "transformer":
            self._init = init_transformer
            self._loss = functools.partial(
                _lm_loss, prec=precision,
                n_heads=int(model["num_attention_heads"]))
        else:
            raise ValueError(f"unknown model kind {model['kind']!r}")

    def init(self, seed: int):
        """Initial weights from the seed, made on the device in one call."""
        return jax.jit(functools.partial(self._init, m=self.spec))(_key(seed))

    def cast(self, tree):
        return jax.tree.map(lambda a: a.astype(self.dtype), tree)

    def _x(self, x):
        return x.astype(self.dtype) if jnp.issubdtype(x.dtype, jnp.floating) \
            else x

    def mean_loss(self, p, x, y):
        return self._loss(p, self._x(x), y)[1].mean()

    def evaluate(self, p, x, y):
        logits, per = self._loss(p, self._x(x), y)
        acc = (logits.argmax(-1) == y).mean()
        return acc, per.astype(jnp.float32).mean()


# ---------------------------------------------------------------------------
# The round replay
# ---------------------------------------------------------------------------


def sqnorm(tree) -> jnp.ndarray:
    return sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
               for a in jax.tree.leaves(tree))


class Replay:
    """Replays a recorded simulation through the reference arithmetic."""

    def __init__(self, model: Model, sim: dict, data: dict,
                 half_batch: bool = False):
        self.m = model
        steps, batch = int(sim["local_steps"]), int(sim["local_batch"])
        lr = float(sim["local_lr"])
        self.server_lr = float(sim.get("server_lr", 1.0))
        self.beta = float(sim.get("beta", 0.35))
        keep = batch // 2 if half_batch else batch
        dt = model.dtype
        self.x_tr, self.y_tr = jnp.asarray(data["x_train"]), \
            jnp.asarray(data["y_train"])
        self.x_te, self.y_te = jnp.asarray(data["x_test"]), \
            jnp.asarray(data["y_test"])

        def train(p0, idx):
            xs = self.x_tr[idx].reshape((steps, batch) + self.x_tr.shape[1:])
            ys = self.y_tr[idx].reshape((steps, batch) + self.y_tr.shape[1:])
            p = p0
            for t in range(steps):
                g = jax.grad(model.mean_loss)(p, xs[t, :keep], ys[t, :keep])
                p = jax.tree.map(lambda w, gw: w - jnp.asarray(lr, dt) * gw,
                                 p, g)
            return jax.tree.map(jnp.subtract, p, p0)

        def weighted(acc, c, stack):
            # acc + sum_j c_j stack_j, elementwise (no matmul unit)
            return jax.tree.map(
                lambda s, a: s + jnp.sum(
                    c.astype(dt).reshape((-1,) + (1,) * (a.ndim - 1)) * a, 0),
                acc, stack)

        # a round's rows, one after another in one call: the memory of
        # one row's training, one dispatch per round
        self._train = jax.jit(
            lambda p0, idx: jax.lax.map(lambda i: train(p0, i), idx))
        self._row = jax.jit(lambda stack, j: jax.tree.map(
            lambda a: a[j], stack))
        self._weighted = jax.jit(weighted)
        self._axpy = jax.jit(lambda acc, a, u: jax.tree.map(
            lambda s, x: s + a.astype(dt) * x, acc, u))
        self._zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        self._lam = jax.jit(lambda uf, us, nf: sqnorm(jax.tree.map(
            lambda f, s: f - (s + nf.astype(dt) * f) / (nf.astype(dt) + 1),
            uf, us)) / (sqnorm(uf) + EPS))
        self._eval = jax.jit(model.evaluate)

    def weights(self, n_fresh: int, taus, lams) -> list:
        """Eq. 2's normalised weights: fresh first, then stale."""
        w = [1.0] * n_fresh
        lam_max = max(lams) if lams else 0.0
        for tau, lam in zip(taus, lams):
            boost = 1.0 - math.exp(-lam / max(lam_max, EPS))
            w.append((1.0 - self.beta) / (tau + 1.0) + self.beta * boost)
        tot = max(sum(w), EPS)
        return [x / tot for x in w]

    def run(self, params0, log, eval_rounds) -> tuple:
        """Returns (final params, {round: eval loss})."""
        p = self.m.cast(params0)
        cache, losses = {}, {}
        width = max(len(e["bidx"]) for e in log)
        for e in log:
            r = e["round"]
            rows = sorted(set(e["fresh"]) | {i for i, _ in e["new_stale"]})
            slot = {i: j for j, i in enumerate(rows)}
            if rows:
                # a round's rows train in one call, padded to one width (a
                # pad row repeats the first and is dropped)
                idx = np.stack([e["bidx"][i] for i in rows]
                               + [e["bidx"][rows[0]]] * (width - len(rows)))
                batch = self._train(p, jnp.asarray(idx))
            for i, lid in e["new_stale"]:
                cache[(lid, r)] = self._row(batch, jnp.int32(slot[i]))
            n_fresh = len(e["fresh"])
            stale = [cache.pop(key) for key in e["landing"]]
            taus = [r - origin for _lid, origin in e["landing"]]
            if n_fresh or stale:
                # fresh rows by their slot in the round's stack
                pick = np.zeros(width, np.float32)
                pick[[slot[i] for i in e["fresh"]]] = 1.0
                mean = self._zeros(p)
                if n_fresh:
                    mean = self._weighted(mean, jnp.asarray(pick / n_fresh),
                                          batch)
                lams = [float(self._lam(mean, u, jnp.int32(n_fresh)))
                        for u in stale]
                w = self.weights(n_fresh, taus, lams)
                # the server step, global += server_lr * sum_i w_i u_i
                if n_fresh:
                    p = self._weighted(
                        p, jnp.asarray(pick * self.server_lr * w[0]), batch)
                for wi, u in zip(w[n_fresh:], stale):
                    p = self._axpy(p, jnp.float32(self.server_lr * wi), u)
            if r in eval_rounds:
                losses[r] = float(self._eval(p, self.x_te, self.y_te)[1])
        return p, losses


def leaf_norm_gap(p0, prog_leaves, ref_leaves) -> float:
    """The worst leaf's gap between the program's and the reference's norm
    of the change from ``p0``, over the reference's norm of that leaf's
    change. Leaves the reference moves by less than a thousandth of the
    median leaf are left out (they move by rounding alone)."""
    def change(a, b):
        return float(np.linalg.norm(np.asarray(a, np.float64)
                                    - np.asarray(b, np.float64)))

    p0s = jax.tree.leaves(p0)
    ref = [change(r, a) for r, a in zip(ref_leaves, p0s)]
    prog = [change(q, a) for q, a in zip(prog_leaves, p0s)]
    floor = max(1e-3 * float(np.median(ref)), EPS)
    return max((abs(a - b) / b for a, b in zip(prog, ref) if b >= floor),
               default=0.0)
