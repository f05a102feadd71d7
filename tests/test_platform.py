"""Where the program runs: the one interpret-mode decision and the
persistent compilation cache directory."""
import jax
import pytest

from repro import platform


@pytest.mark.parametrize("name,interpret", [("cpu", True), ("tpu", False)])
def test_pallas_interpret_only_on_cpu(monkeypatch, name, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: name)
    assert platform.pallas_interpret() is interpret


def test_pallas_interpret_refuses_other_platforms(monkeypatch):
    """A kernel never drops to the interpreter on an accelerator it was
    not written for."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        platform.pallas_interpret()


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = platform.use_compile_cache()
    assert got == str(platform.CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_env_is_left_to_jax(monkeypatch, tmp_path,
                                          cache_dir_config):
    """With JAX_COMPILATION_CACHE_DIR set, no other directory is set in
    code: JAX's own setting stands."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert platform.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
