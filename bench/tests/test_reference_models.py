"""CPU tests of the reference models found by kind (``kinds.py``,
``models/<kind>.py``): a kind the repository does not have enters as files
alone, and the ``transformer`` reference runs every layer its
configuration names, matching the program's learner at two layers and
its own earlier one-layer form bit for bit.
"""
import functools
import json
import pathlib
import shutil
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import kinds  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from bench_cells import LM, shrink, small  # noqa: E402
from counts import work  # noqa: E402


def _lm_check(c, seed):
    """A cell's sound run on the CPU: the program's simulation against the
    reference's replay, as ``run.run_cell`` compares them."""
    world = run.build_world(c["config"], c["traffic"], seed)
    sim, acct = run.simulate(world)
    flat = np.asarray(sim.flat_params)[:world.substrate.flat_params0.size]
    return world, sim.round_log, run.eval_losses(acct), flat


def test_missing_kind_names_its_file():
    with pytest.raises(ValueError, match=r"models/no_such_kind\.py"):
        reference.Model({"kind": "no_such_kind"})


def test_a_new_kind_enters_as_files(tmp_path, monkeypatch):
    """A copy of the transformer reference under a kind the repository does
    not have, with a configuration, traffic, limits and a BENCHMARK.json
    entry beside it, all under ``tmp_path``: its cell runs through
    ``run.run_cell`` and is correct, and the bf16 control fails it."""
    kind, cell = "decoder_copy", "decoder-copy.silo8"
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "models"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(BENCH / "models" / "transformer.py",
                bench / "models" / f"{kind}.py")
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    conf = run._json(BENCH / "configs" / "lm-minicpm2b-l1.json")
    conf["model"]["kind"] = kind
    (bench / "configs" / "decoder-copy.json").write_text(json.dumps(conf))
    shutil.copy(BENCH / "traffic" / "silo8.json", bench / "traffic")
    shutil.copy(BENCH / "limits" / f"{LM}.json",
                bench / "limits" / f"{cell}.json")
    spec = run._json(run.ROOT / "BENCHMARK.json")
    spec["configs"].append({"name": "decoder-copy",
                            "file": "bench/configs/decoder-copy.json"})
    spec["workloads"].append({"name": cell, "config": "decoder-copy",
                              "traffic": "silo8", "chips": 1})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "ROOT", tmp_path)
    monkeypatch.setattr(run, "BENCH", bench)
    monkeypatch.setattr(kinds, "MODELS", bench / "models")

    c = shrink(run.load_cell(cell))
    assert work.params(c["config"]["model"]) == \
        kinds.module(BENCH / "models" / "transformer.py").params(
            c["config"]["model"])
    res = run.run_cell(c, 2**31 + 13, 0.05, False, require_tpu=False,
                       log=lambda *a, **k: None)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}

    import jax.numpy as jnp
    world, log, losses, _flat = _lm_check(c, 2**31 + 13)
    ref_p, ref_l = run.replay(world, log, losses)
    ctl_p, ctl_l = run.replay(world, log, losses, dtype=jnp.bfloat16)
    got = run.compare(world, run.leaves_flat(ctl_p), ctl_l, ref_p, ref_l)
    assert any(got[k] > lim for k, lim in c["limits"].items()), got


def _program_and_reference(n_layers, vocab=256, seq_len=16):
    from repro.learners import DataMeta, build_model
    m = {"kind": "transformer", "hidden_size": 64, "num_attention_heads": 4,
         "intermediate_size": 128, "num_hidden_layers": n_layers,
         "vocab_size": vocab}
    fns = build_model("transformer", (("n_layers", n_layers),
                                      ("d_model", 64), ("n_heads", 4),
                                      ("d_ff", 128)),
                      DataMeta(kind="tokens", vocab=vocab, seq_len=seq_len))
    return m, fns


def test_transformer_reference_matches_learner_at_depth():
    """Two layers: the reference's tree is the learner's, leaf for leaf, and
    on the reference's seeded weights both give the same per-sequence
    losses at ``highest`` precision."""
    import jax
    m, fns = _program_and_reference(2)
    model = reference.Model(m)
    p = model.init(2**31 + 17)
    prog = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(p) == jax.tree.structure(prog)
    assert [a.shape for a in jax.tree.leaves(p)] == \
        [a.shape for a in jax.tree.leaves(prog)]
    assert p["stack"]["sub0"]["mixer"]["w_q"].shape[0] == 2
    rng = np.random.default_rng(7)
    tok = rng.integers(0, 256, size=(8, 17)).astype(np.int32)
    x, y = tok[:, :-1], tok[:, 1:]
    with jax.default_matmul_precision("highest"):
        prog_per = np.asarray(jax.jit(fns.loss)(p, x, y)[1])
    ref_per = np.asarray(jax.jit(model._loss)(p, x, y)[1])
    np.testing.assert_allclose(prog_per, ref_per, rtol=1e-5)
    # the second layer is run: dropping it changes the losses
    one = jax.tree.map(lambda a: a[:1], p["stack"]["sub0"])
    p1 = dict(p, stack={"sub0": one})
    m1 = dict(m, num_hidden_layers=1)
    per1 = np.asarray(reference.Model(m1)._loss(p1, x, y)[1])
    assert np.max(np.abs(per1 - ref_per) / ref_per) > 1e-3


def test_two_layer_lm_cell_is_correct():
    c = small(LM, n_layers=2)
    world, log, losses, flat = _lm_check(c, 2**31 + 19)
    ok, checks = run.check(world, flat, losses, log, c["limits"])
    assert ok, checks


# The one-layer transformer reference as it stood before it took a depth,
# frozen: at one layer the reference's weights and losses are its own.

def _dense(key, shape):
    import jax
    import jax.numpy as jnp
    return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5


def _frozen_init(key, m):
    import jax
    import jax.numpy as jnp
    d, f, v = int(m["hidden_size"]), int(m["intermediate_size"]), \
        int(m["vocab_size"])
    ks = jax.random.split(key, 9)
    one = lambda a: a[None]  # noqa: E731
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


def _frozen_loss(p, tok, y, prec, n_heads):
    import jax
    import jax.numpy as jnp

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + 1e-5) * scale

    def rope(x):
        s, dh = x.shape[1], x.shape[-1]
        inv = 1.0 / (10000.0 ** (jnp.arange(0, dh, 2, dtype=jnp.float32)
                                 / dh))
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
        cos, sin = (jnp.cos(ang)[None, :, None, :].astype(x.dtype),
                    jnp.sin(ang)[None, :, None, :].astype(x.dtype))
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    mm = functools.partial(jnp.matmul, precision=prec)
    blk = jax.tree.map(lambda a: a[0], p["stack"]["sub0"])
    x = p["embed"]["embedding"][tok]
    b, s, d = x.shape
    dh = d // n_heads
    h = rms(x, blk["norm1"]["scale"])
    q, k, v = (mm(h, blk["mixer"][w]).reshape(b, s, n_heads, dh)
               for w in ("w_q", "w_k", "w_v"))
    q, k = rope(q), rope(k)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) * dh ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v, precision=prec)
    x = x + mm(o.reshape(b, s, d), blk["mixer"]["w_o"])
    h = rms(x, blk["norm2"]["scale"])
    ffn = blk["ffn"]
    x = x + mm(jax.nn.silu(mm(h, ffn["w_gate"])) * mm(h, ffn["w_up"]),
               ffn["w_down"])
    logits = mm(rms(x, p["final_norm"]["scale"]), p["head"]["w_out"])
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return logits, (logz - gold).mean(-1)


def test_one_layer_reference_is_the_earlier_one():
    import jax
    m, _fns = _program_and_reference(1)
    model = reference.Model(m)
    seed = 2**31 + 23
    p = model.init(seed)
    old = jax.jit(functools.partial(_frozen_init, m=m))(reference._key(seed))
    assert jax.tree.structure(p) == jax.tree.structure(old)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tok = np.random.default_rng(3).integers(0, 256, size=(4, 17)) \
        .astype(np.int32)
    x, y = tok[:, :-1], tok[:, 1:]
    new = jax.jit(model._loss)(p, x, y)
    was = jax.jit(functools.partial(_frozen_loss, prec="highest",
                                    n_heads=4))(p, x, y)
    for a, b in zip(new, was):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
