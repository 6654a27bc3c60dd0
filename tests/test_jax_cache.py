"""The entry points' compilation-cache helper: the directory comes from
``JAX_COMPILATION_CACHE_DIR`` when set, otherwise one fixed path inside the
checkout."""
from pathlib import Path

import jax
import pytest

from repro.jax_cache import CHECKOUT_CACHE_DIR, configure_compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_dir_from_env_or_checkout(monkeypatch, tmp_path,
                                                restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = configure_compile_cache()
    assert path == str(CHECKOUT_CACHE_DIR) == configure_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    repo_root = Path(__file__).resolve().parents[1]
    assert Path(path) == repo_root / ".jax_cache"
    assert ".jax_cache/" in (repo_root / ".gitignore").read_text().split()
