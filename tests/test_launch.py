"""Launch-layer plumbing: where the persistent compilation cache lives, how
``--arch`` is read, and the chip smoke's refusal to run off the TPU."""

import importlib.util
import os
import tempfile
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.launch import compile_cache, serve

REPO_ROOT = Path(__file__).resolve().parents[1]


def test_compile_cache_follows_env_then_repo_root(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/operator")
        assert compile_cache.enable_compile_cache() == "/placed/by/operator"
        assert jax.config.jax_compilation_cache_dir == before  # left to JAX

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        # fixed: never under the temp dir, never keyed by the process
        assert not path.startswith(tempfile.gettempdir())
        assert str(os.getpid()) not in path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("arch", ["dsv2-lite-reduced", "dsv2-lite-4l", "dsv2-lite"])
def test_serve_arch_is_literal(arch):
    """``--arch`` names a registered config as is: nothing appends
    ``-reduced``, so published widths are servable where they fit."""
    args = serve.parse_args(["--arch", arch, "--draft", arch])
    assert args.arch == args.draft == arch
    assert get_config(args.arch).name == arch


def test_chip_smoke_refuses_cpu():
    """Off the TPU the smoke exits non-zero naming the platform it found,
    before any phase runs."""
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO_ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        smoke.check_devices(1)
