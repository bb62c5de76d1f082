"""The persistent compilation cache goes where the deployment says, or to
one fixed directory inside the checkout."""
import pathlib

import jax
import pytest

from repro.runtime import compile_cache

#: Cache options the helper must leave exactly as it found them.
_OTHER_OPTIONS = ("jax_enable_compilation_cache",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restore_cache_config():
    before = {k: getattr(jax.config, k)
              for k in ("jax_compilation_cache_dir", *_OTHER_OPTIONS)}
    yield before
    for k, v in before.items():
        jax.config.update(k, v)


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, tmp_path,
                                              restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    for k in _OTHER_OPTIONS:
        assert getattr(jax.config, k) == restore_cache_config[k], k


def test_default_is_fixed_path_in_checkout(monkeypatch,
                                           restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert path == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    for k in _OTHER_OPTIONS:
        assert getattr(jax.config, k) == restore_cache_config[k], k
    # the directory is ignored by git, so a cache never reaches a commit
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
