"""CPU test of the reference's round replay: trained one row at a time into
a fresh sum (``reference.Replay``), it agrees with the earlier replay that
trained a round's rows in one call and weighed the stacked rows, on the
small mlp and LM cells."""
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
import run  # noqa: E402
from bench_cells import LM, MLP, small  # noqa: E402


class StackedReplay(reference.Replay):
    """The replay as it was before it held one row at a time, frozen: a
    round's rows trained in one ``lax.map`` call into a ``(rows, ...)``
    stack, the fresh mean and the server step as weighted sums over it."""

    def __init__(self, model, sim, data):
        super().__init__(model, sim, data)
        import jax
        import jax.numpy as jnp
        steps, batch = int(sim["local_steps"]), int(sim["local_batch"])
        lr, dt = float(sim["local_lr"]), model.dtype

        def train(p0, idx):
            xs = self.x_tr[idx].reshape((steps, batch) + self.x_tr.shape[1:])
            ys = self.y_tr[idx].reshape((steps, batch) + self.y_tr.shape[1:])
            p = p0
            for t in range(steps):
                g = jax.grad(model.mean_loss)(p, xs[t], ys[t])
                p = jax.tree.map(lambda w, gw: w - jnp.asarray(lr, dt) * gw,
                                 p, g)
            return jax.tree.map(jnp.subtract, p, p0)

        def weighted(acc, c, stack):
            return jax.tree.map(
                lambda s, a: s + jnp.sum(
                    c.astype(dt).reshape((-1,) + (1,) * (a.ndim - 1)) * a, 0),
                acc, stack)

        self._train = jax.jit(
            lambda p0, idx: jax.lax.map(lambda i: train(p0, i), idx))
        self._row = jax.jit(lambda stack, j: jax.tree.map(
            lambda a: a[j], stack))
        self._weighted = jax.jit(weighted)
        self._lam_mean = jax.jit(lambda uf, us, nf: reference.sqnorm(
            jax.tree.map(lambda f, s: f - (s + nf.astype(dt) * f)
                         / (nf.astype(dt) + 1), uf, us))
            / (reference.sqnorm(uf) + reference.EPS))

    def run(self, params0, log, eval_rounds):
        import jax.numpy as jnp
        p = self.m.cast(params0)
        cache, losses = {}, {}
        width = max(len(e["bidx"]) for e in log)
        for e in log:
            r = e["round"]
            rows = sorted(set(e["fresh"]) | {i for i, _ in e["new_stale"]})
            slot = {i: j for j, i in enumerate(rows)}
            if rows:
                idx = np.stack([e["bidx"][i] for i in rows]
                               + [e["bidx"][rows[0]]] * (width - len(rows)))
                batch = self._train(p, jnp.asarray(idx))
            for i, lid in e["new_stale"]:
                cache[(lid, r)] = self._row(batch, jnp.int32(slot[i]))
            n_fresh = len(e["fresh"])
            stale = [cache.pop(key) for key in e["landing"]]
            taus = [r - origin for _lid, origin in e["landing"]]
            if n_fresh or stale:
                pick = np.zeros(width, np.float32)
                pick[[slot[i] for i in e["fresh"]]] = 1.0
                mean = self._zeros(p)
                if n_fresh:
                    mean = self._weighted(mean, jnp.asarray(pick / n_fresh),
                                          batch)
                lams = [float(self._lam_mean(mean, u, jnp.int32(n_fresh)))
                        for u in stale]
                w = self.weights(n_fresh, taus, lams)
                if n_fresh:
                    p = self._weighted(
                        p, jnp.asarray(pick * self.server_lr * w[0]), batch)
                for wi, u in zip(w[n_fresh:], stale):
                    p = self._axpy(p, jnp.float32(self.server_lr * wi), u)
            if r in eval_rounds:
                losses[r] = float(self._eval(p, self.x_te, self.y_te)[1])
        return p, losses


@pytest.mark.parametrize("name", [MLP, LM])
def test_row_at_a_time_replay_matches_stacked(name):
    import jax
    c = small(name)
    world = run.build_world(c["config"], c["traffic"], 2**31 + 29)
    sim, acct = run.simulate(world)
    losses, log = run.eval_losses(acct), sim.round_log
    assert any(e["new_stale"] for e in log) or name == LM
    rows_p, rows_l = reference.Replay(
        world.model, world.sim_fields, world.arrays).run(
            world.params0, log, set(losses))
    stack_p, stack_l = StackedReplay(
        world.model, world.sim_fields, world.arrays).run(
            world.params0, log, set(losses))
    for a, b in zip(jax.tree.leaves(rows_p), jax.tree.leaves(stack_p)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b)
    assert rows_l.keys() == stack_l.keys() == losses.keys()
    for r in losses:
        assert abs(rows_l[r] - stack_l[r]) <= 1e-6 * abs(stack_l[r])
