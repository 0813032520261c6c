"""Placement of the persistent compilation cache, and the entry-point
contracts around the chip: ``chip_smoke.py`` refuses to run without a TPU,
and the benchmark harness reports a failed figure in its exit code."""
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    meta = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", meta)


def test_env_dir_is_the_only_cache_dir(monkeypatch, tmp_path,
                                       cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_unset_env_uses_the_fixed_checkout_dir(monkeypatch,
                                               cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CHECKOUT_CACHE_DIR == REPO / ".jax_cache"
    assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert "/.jax_cache/" in ignored


@pytest.mark.parametrize("env_dir", [True, False])
def test_cache_key_holds_the_program_metadata(monkeypatch, tmp_path,
                                              cache_dir_restored, env_dir):
    """Named scopes live in metadata only: without it in the key, a cached
    executable of another version would carry that version's op names."""
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    enable_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_library_import_leaves_the_cache_alone():
    code = ("import jax, repro.sim, repro.sweeps, repro.kernels; "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu"},
        timeout=120, check=True)
    assert out.stdout.strip() == "None"


def test_chip_smoke_fails_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], capture_output=True,
        text=True, env={"JAX_PLATFORMS": "cpu"}, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_benchmark_run_exits_nonzero_when_a_figure_raises(monkeypatch,
                                                          capsys):
    monkeypatch.syspath_prepend(str(REPO))
    from benchmarks import figures, run

    ran = []

    def fig01_ok():
        ran.append("fig01")

    def fig02_broken():
        raise RuntimeError("boom")

    def fig03_ok():
        ran.append("fig03")

    monkeypatch.setattr(figures, "ALL_FIGURES",
                        [fig01_ok, fig02_broken, fig03_ok])
    monkeypatch.setattr(sys, "argv", ["run"])
    assert run.main() == 1
    assert ran == ["fig01", "fig03"]     # the failure hid no other figure
    assert "fig02_broken,0,ERROR=RuntimeError('boom')" in \
        capsys.readouterr().out
    monkeypatch.setattr(figures, "ALL_FIGURES", [fig01_ok])
    assert run.main() == 0
