"""The two platform decisions of ``repro.runtime``: the Pallas kernel mode
and the persistent compile-cache directory.  The backend is steered with
``monkeypatch``; nothing here touches a chip or turns the cache on."""
import os

import jax
import pytest

from repro import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False)])
def test_kernel_mode_follows_the_backend(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert runtime.kernel_interpret() is interpret


@pytest.mark.parametrize("backend", ["gpu", "rocm", "METAL"])
def test_kernel_mode_refuses_other_backends(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(RuntimeError, match=backend):
        runtime.kernel_interpret()


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_honours_the_env_var(monkeypatch, config_updates,
                                           tmp_path):
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    assert runtime.compile_cache_dir() == str(tmp_path)
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert config_updates == []            # JAX reads the variable itself


def test_compile_cache_defaults_to_a_fixed_ignored_checkout_dir(
        monkeypatch, config_updates):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    want = os.path.join(REPO, ".cache", "jax")
    assert runtime.REPO_ROOT == REPO
    assert runtime.compile_cache_dir() == want
    assert runtime.enable_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".cache/" in f.read().split()
