"""repro.compile_cache: where the persistent compilation cache goes.

Only the decision is tested; no test turns the cache on.
"""

from __future__ import annotations

from pathlib import Path

import jax

from repro.compile_cache import CACHE_ENV, compile_cache_dir, use_compile_cache


def test_environment_directory_wins_and_sets_nothing(tmp_path):
    env = {CACHE_ENV: str(tmp_path / "elsewhere")}
    assert compile_cache_dir(tmp_path, env) == (str(tmp_path / "elsewhere"), False)


def test_default_is_fixed_dir_in_the_checkout(tmp_path):
    path, set_in_code = compile_cache_dir(tmp_path, {})
    assert set_in_code
    assert Path(path) == tmp_path.resolve() / ".jax_cache"
    # the same checkout always maps to the same directory (cache key)
    assert compile_cache_dir(tmp_path, {CACHE_ENV: ""}) == (path, True)


def test_use_leaves_jax_config_alone_when_env_is_set(tmp_path, monkeypatch):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "cc"))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache(tmp_path) == str(tmp_path / "cc")
    assert jax.config.jax_compilation_cache_dir == before
