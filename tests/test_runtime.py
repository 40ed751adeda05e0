"""Placement of the persistent compilation cache."""
import os
import tempfile
import time

import jax
import pytest

from repro.launch import runtime

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_entry_size_bytes",
         "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_cache_dir_env_var_wins(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert runtime.enable_compilation_cache() == str(tmp_path / "cc")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cc")


def test_cache_dir_default_is_fixed_inside_checkout(monkeypatch, tmp_path,
                                                    restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    seen = []
    for i in range(2):
        # Nothing about the temp dir, user, pid or clock may move it.
        monkeypatch.setattr(tempfile, "tempdir", str(tmp_path / f"t{i}"))
        monkeypatch.setenv("TMPDIR", str(tmp_path / f"t{i}"))
        monkeypatch.setenv("USER", f"user{i}")
        monkeypatch.setattr(os, "getpid", lambda i=i: 1000 + i)
        monkeypatch.setattr(time, "time", lambda i=i: 1e9 + i)
        seen.append(runtime.enable_compilation_cache())
    assert seen == [want, want]
    assert jax.config.jax_compilation_cache_dir == want
