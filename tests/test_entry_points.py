"""The entry points a user runs: the train CLI, ``chip_smoke.py`` and the
compile-cache helper they share."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache, train

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_compile_cache_leaves_a_set_directory_to_jax(
        monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_gitignored_repo_dir(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_full_config_flag_builds_the_published_config():
    ap = train.build_parser()
    full = train.make_config(ap.parse_args(["--arch", "qwen1.5-0.5b",
                                            "--full-config"]))
    assert (full.n_layers, full.d_model, full.vocab_size) == \
        (24, 1024, 151936)
    reduced = train.make_config(ap.parse_args(["--arch", "qwen1.5-0.5b"]))
    assert reduced.n_layers == 2


def test_train_cli_runs_on_the_device_mesh(monkeypatch, tmp_path, capsys):
    # a set cache directory keeps the helper from pointing this worker's
    # compiles at the repo
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    args = ["--arch", "qwen1.5-0.5b", "--rounds", "1",
            "--cyclic-rounds", "1", "--clients", "4",
            "--clients-per-round", "2", "--local-steps", "1", "--batch", "2",
            "--seq", "16", "--chunk-size", "1"]
    assert train.main(args) in (0, 1)
    out = capsys.readouterr().out
    assert f"backend={jax.default_backend()} update_impl=fused " \
           f"interpret={jax.default_backend() != 'tpu'}" in out
    assert "loss" in out


def _run(code_or_args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    return subprocess.run([sys.executable] + code_or_args, cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120, **kw)


def test_chip_smoke_refuses_to_run_off_tpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "platform=cpu" in r.stdout


def test_chip_paths_never_import_the_fake_device_modules():
    """dryrun and perf_hillclimb force 512 host devices through XLA_FLAGS
    when imported; nothing the chip paths import may pull them in."""
    code = ("import sys; sys.path.insert(0, '.'); "
            "import chip_smoke, repro.launch.train, repro.kernels.parity; "
            "bad = [m for m in ('repro.launch.dryrun', "
            "'benchmarks.perf_hillclimb') if m in sys.modules]; "
            "assert not bad, bad")
    r = _run(["-c", code])
    assert r.returncode == 0, r.stderr
