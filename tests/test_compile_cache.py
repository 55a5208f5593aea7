"""Where the persistent compile cache lives (utils/compile_cache.py): the
directory is placed from outside through JAX's own variable, else it is a
fixed path in the checkout — never under `~`, never a temporary name."""

import os

import jax
import pytest

from quickwit_tpu.utils import compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def updates(monkeypatch):
    """Record `jax.config.update` calls instead of applying them."""
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.__setitem__(name, value))
    return seen


def test_env_places_the_cache_and_code_sets_no_directory(monkeypatch,
                                                         updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    assert compile_cache.enable_persistent_compile_cache() == \
        str(tmp_path / "cache")
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_default_is_the_checkout(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert compile_cache.enable_persistent_compile_cache() == want
    assert updates["jax_compilation_cache_dir"] == want
    assert os.path.isdir(want)


def test_a_directory_that_cannot_be_created_raises(monkeypatch, updates,
                                                   tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.setattr(compile_cache, "_CHECKOUT_CACHE_DIR",
                        str(blocker / ".jax_cache"))
    with pytest.raises(OSError):
        compile_cache.enable_persistent_compile_cache()
    assert "jax_compilation_cache_dir" not in updates
