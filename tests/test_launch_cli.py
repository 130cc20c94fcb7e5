"""Entry points a user runs: the trainer CLI's flags, and ``chip_smoke.py``
refusing to run without a TPU."""
import os
import subprocess
import sys

import pytest

from repro import configs
from repro.launch.train import arch_for, parse_args

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_smoke_is_the_default():
    args = parse_args(["--mode", "gfm"])
    assert args.smoke is True
    assert arch_for(args) == configs.get_smoke("hydragnn-gfm")


def test_no_smoke_trains_the_published_config():
    """--no-smoke is the paper's model at its published widths, unchanged."""
    args = parse_args(["--mode", "gfm", "--no-smoke", "--steps", "3"])
    assert args.smoke is False and args.steps == 3
    cfg = arch_for(args)
    assert cfg == configs.get("hydragnn-gfm")
    assert (cfg.gnn_hidden, cfg.gnn_layers, cfg.head_hidden,
            cfg.head_layers, cfg.n_tasks) == (866, 4, 889, 3, 5)


@pytest.mark.parametrize("mode", ["lm", "lm-mtl"])
def test_lm_modes_follow_the_smoke_flag(mode):
    arch = "qwen1.5-0.5b"
    assert arch_for(parse_args(["--mode", mode, "--arch", arch])) == \
        configs.get_smoke(arch)
    assert arch_for(parse_args(["--mode", mode, "--arch", arch,
                                "--no-smoke"])) == configs.get(arch)


@pytest.fixture
def cache_config():
    """Restore JAX's compile-cache directory after a test moves it."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_the_env_var_to_jax(cache_config, monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/jax-cache")
    before = cache_config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/elsewhere/jax-cache"
    assert cache_config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout(
        cache_config, monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first, again = compile_cache.enable(), compile_cache.enable()
    assert first == again == cache_config.jax_compilation_cache_dir
    assert first == os.path.join(os.path.realpath(ROOT), ".jax_cache")


def test_chip_smoke_refuses_the_cpu():
    """No accelerator, no result: a CPU fallback would report a chip run
    that never happened."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr
