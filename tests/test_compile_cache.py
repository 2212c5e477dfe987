"""The one rule for the persistent compilation cache's place
(horovod_tpu/utils/compile_cache.py): $JAX_COMPILATION_CACHE_DIR if set,
else <checkout>/.jax_cache — from any cwd, and the same for the
subprocesses the smoke tools spawn."""

import os
import sys

import jax

from horovod_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)


def test_default_is_the_checkout_from_any_cwd(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.chdir(tmp_path)
    assert compile_cache.cache_dir() == os.path.join(_REPO, ".jax_cache")


def test_smoke_workers_inherit_the_same_place(monkeypatch, tmp_path):
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import smoke_util
    finally:
        sys.path.remove(os.path.join(_REPO, "tools"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    env = smoke_util.jit_cache_env({"PATH": "/bin"})
    assert env["JAX_COMPILATION_CACHE_DIR"] == \
        os.path.join(_REPO, ".jax_cache")
    env = smoke_util.jit_cache_env(
        {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
