"""The serve launcher's model loading and the launchers' compile cache."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch import compile_cache
from repro.launch.serve import build_engine, load_model
from repro.serving.scheduler import Request


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache directory after a test that sets it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.parent.joinpath("pyproject.toml")\
        .is_file()
    assert jax.config.jax_compilation_cache_dir == path


def test_load_model_picks_kernels_by_platform_and_params_in_compute_dtype():
    model, params = load_model("h2o-danube-3-4b", reduced=True)
    assert model.cfg.use_kernels == (jax.default_backend() == "tpu")
    assert model.cfg.param_dtype == model.cfg.dtype
    dtypes = {leaf.dtype for leaf in jax.tree.leaves(params)}
    assert dtypes == {jnp.dtype(model.cfg.dtype)}


def test_build_engine_serves_greedy_requests():
    eng = build_engine("h2o-danube-3-4b", reduced=True, slots=2, max_len=48,
                       temperature=0.0)
    comps = eng.run([Request(rid=i, prompt=tuple(range(1, 9 + i)),
                             max_new_tokens=4) for i in range(3)])
    assert [len(c.tokens) for c in comps] == [4, 4, 4]
    assert eng.paged and eng.prefix_cache is not None
