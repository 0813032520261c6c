"""The benchmark's cells cut to a size the CPU tests can run: the mlp cell
keeps its widths and runs 40 rounds; a token cell gets toy widths
(``hidden_size`` 64, 4 heads, ``intermediate_size`` 128) and 4 rounds.
"""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

MLP, LM = "mlp-speech-n1000.refl", "lm-minicpm2b-l1.silo8"


def cell_files(name: str) -> dict:
    """A cell's files, found by name (``<config>.<traffic>``) even before
    the cell has an entry in BENCHMARK.json."""
    config, traffic = name.split(".")
    return dict(config=run._json(BENCH / "configs" / f"{config}.json"),
                traffic=run._json(BENCH / "traffic" / f"{traffic}.json"),
                limits=run._json(BENCH / "limits" / f"{name}.json"))


def shrink(c: dict, n_layers: int = 1) -> dict:
    if c["config"]["data"]["kind"] == "tokens":
        c["config"]["model"].update(hidden_size=64, num_attention_heads=4,
                                    intermediate_size=128,
                                    num_hidden_layers=n_layers)
        c["config"]["sim"]["model_params"] = [
            ["n_layers", n_layers], ["d_model", 64], ["n_heads", 4],
            ["d_ff", 128]]
        c["traffic"]["sim"].update(rounds=4, eval_every=2)
    else:
        c["traffic"]["sim"].update(rounds=40, eval_every=20)
    return c


def small(name: str, n_layers: int = 1) -> dict:
    c = run.load_cell(name) if name == MLP else cell_files(name)
    return shrink(c, n_layers)


def run_quiet(c: dict) -> dict:
    """One run of a cell loaded by ``run.load_cell``, off the chip."""
    return run.run_cell(c, 2**31 + 11, 0.05, False, require_tpu=False,
                        log=lambda *a, **k: None)
