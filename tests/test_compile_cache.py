"""The persistent compilation cache helper (``repro.utils.compile_cache``):
``JAX_COMPILATION_CACHE_DIR`` wins where set, otherwise a fixed directory
under the checkout that git ignores."""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.utils import compile_cache as cc


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    # the cache binds its directory once per process: drop what an earlier
    # compile in this process bound
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
    compilation_cache.reset_cache()


def test_env_dir_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    assert cc.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_dir_is_fixed_and_ignored(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    path = cc.enable_compile_cache()
    assert path == cc.DEFAULT_DIR == os.path.join(cc.CHECKOUT, ".jax_cache")
    assert os.path.isdir(os.path.join(cc.CHECKOUT, "src", "repro"))
    with open(os.path.join(cc.CHECKOUT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_keeps_jax_write_threshold(monkeypatch, tmp_path,
                                       restore_cache_dir):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    cc.enable_compile_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0


def test_rejit_of_identical_program_hits(monkeypatch, tmp_path,
                                         restore_cache_dir):
    """On an accelerator backend two separately jitted copies of one quick
    program: the second is served from the cache directory the first wrote
    to.  The backend check is faked; the compiles run on the CPU."""
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cc.enable_compile_cache()
    monkeypatch.undo()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    hits = []

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    def program(x):
        return jnp.tanh(x) * 3.0 + 0.125

    jax.monitoring.register_event_listener(on_event)
    try:
        x = jnp.arange(8.0)
        first = jax.jit(lambda x: program(x))(x)
        assert os.listdir(tmp_path), "nothing written to the cache"
        assert not hits
        second = jax.jit(lambda x: program(x))(x)
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    assert len(hits) == 1
    assert (first == second).all()
