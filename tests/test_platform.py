"""Backend helpers (repro.core.platform): the persistent compile cache
that the chip entry points turn on, and the per-backend compiler params."""
import pathlib

import jax
import pytest

from repro.core import platform

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture()
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them, so the
    test process never turns the persistent cache on."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_env_var_wins(config_updates, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert platform.enable_compile_cache() == str(tmp_path)
    # JAX read the variable itself; no code sets another directory
    assert "jax_compilation_cache_dir" not in config_updates
    assert config_updates["jax_persistent_cache_min_compile_time_secs"] == 0


def test_fixed_repo_path_otherwise(config_updates, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = platform.enable_compile_cache()
    assert path == str(REPO / ".jax_cache") == str(platform.REPO_COMPILE_CACHE)
    assert config_updates["jax_compilation_cache_dir"] == path
    gitignore = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in gitignore


def test_importing_repro_leaves_the_cache_alone():
    import os

    import repro.filters  # noqa: F401
    import repro.serve  # noqa: F401
    assert (jax.config.jax_compilation_cache_dir
            == os.environ.get("JAX_COMPILATION_CACHE_DIR"))


def test_compiler_params_per_backend():
    assert platform.grid_compiler_params(("parallel",), True) is None
    params = platform.grid_compiler_params(("parallel", "arbitrary"), False)
    assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")
