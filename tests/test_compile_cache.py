"""Where entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_cache_dir_from_env_or_fixed_checkout_path(env, monkeypatch,
                                                   restore_cache_dir):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.enable_compile_cache()
    if env is None:
        assert got == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.DEFAULT_DIR.parent.joinpath("src", "repro").is_dir()
    else:
        # JAX read the variable itself; nothing else is set in code
        assert got == env
        assert jax.config.jax_compilation_cache_dir == before
