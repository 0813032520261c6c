"""Plain reference of the federated round, written from the published
equations, importing nothing of the program.

The model is the configuration's own: ``models/<kind>.py``, found by the
``kind`` its ``model`` block names (``kinds.py`` says what such a file
defines). A configuration adds its model as a file there. The training
loss is the mean of the model's per-example losses.

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

import kinds

EPS = 1e-12


def _key(seed: int):
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(k, (int(seed) >> 32) & 0xFFFFFFFF)


class Model:
    """One configuration's reference model (``kinds.model``, found by the
    ``kind`` of its ``model`` block): init, per-example losses and eval, in
    the arithmetic that ``dtype``/``precision`` select."""

    def __init__(self, model: dict, dtype=jnp.float32, precision="highest"):
        self.spec = model
        self.dtype = dtype
        kind = kinds.model(model["kind"])
        self._init = kind.init
        self._loss = functools.partial(kind.loss, prec=precision, m=model)

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

            def step(t, p):
                g = jax.grad(model.mean_loss)(p, xs[t, :keep], ys[t, :keep])
                return jax.tree.map(
                    lambda w, gw: w - jnp.asarray(lr, dt) * gw, p, g)

            # a loop, not unrolled: one step's memory at a time
            p = jax.lax.fori_loop(0, steps, step, p0)
            return jax.tree.map(jnp.subtract, p, p0)

        def lam(fresh_sum, us, nf):
            # u_F is the fresh sum over n_F (0 in a round with none)
            nf = nf.astype(dt)
            uf = jax.tree.map(lambda s: s / jnp.maximum(nf, 1), fresh_sum)
            return sqnorm(jax.tree.map(
                lambda f, s: f - (s + nf * f) / (nf + 1), uf, us)) \
                / (sqnorm(uf) + EPS)

        def sum_rows(p0, idx, n):
            # the first n rows of idx, trained one after another from p0
            # and each added into the sum that the loop carries: the memory
            # of one row's training beside the weights and the sum
            return jax.lax.fori_loop(
                0, n, lambda j, acc: jax.tree.map(jnp.add, acc,
                                                  train(p0, idx[j])),
                jax.tree.map(jnp.zeros_like, p0))

        self._sum_rows = jax.jit(sum_rows)
        self._axpy = jax.jit(lambda acc, a, u: jax.tree.map(
            lambda s, x: s + a.astype(dt) * x, acc, u))
        self._zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        self._lam = jax.jit(lam)
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

        def rows_sum(p, bidx, rows):
            # the rows' samples padded to one width (a pad row repeats the
            # first and is not trained): one program for every round
            idx = np.stack([bidx[i] for i in rows]
                           + [bidx[rows[0]]] * (width - len(rows)))
            return self._sum_rows(p, jnp.asarray(idx), jnp.int32(len(rows)))

        for e in log:
            r = e["round"]
            n_fresh = len(e["fresh"])
            fresh_sum = rows_sum(p, e["bidx"], e["fresh"]) if n_fresh \
                else self._zeros(p)
            for i, lid in e["new_stale"]:
                cache[(lid, r)] = rows_sum(p, e["bidx"], [i])
            stale = [cache.pop(key) for key in e["landing"]]
            taus = [r - origin for _lid, origin in e["landing"]]
            if n_fresh or stale:
                lams = [float(self._lam(fresh_sum, u, jnp.int32(n_fresh)))
                        for u in stale]
                w = self.weights(n_fresh, taus, lams)
                # the server step, global += server_lr * sum_i w_i u_i, the
                # fresh rows' equal weights applied to their sum
                if n_fresh:
                    p = self._axpy(p, jnp.float32(self.server_lr * w[0]),
                                   fresh_sum)
                for wi, u in zip(w[n_fresh:], stale):
                    p = self._axpy(p, jnp.float32(self.server_lr * wi), u)
            del fresh_sum                     # a row's memory, freed for eval
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
