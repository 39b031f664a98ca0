"""The persistent-compilation-cache helper used by the entry points."""
import jax

from repro import compile_cache as CC


def _updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_environment_directory_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(CC.CACHE_ENV, str(tmp_path))
    assert CC.resolve_cache_dir() == (str(tmp_path), False)
    calls = _updates(monkeypatch)
    assert CC.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in calls
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_default_is_the_fixed_in_checkout_directory(monkeypatch):
    monkeypatch.delenv(CC.CACHE_ENV, raising=False)
    path = str(CC.CHECKOUT / ".jax_cache")
    assert CC.resolve_cache_dir() == (path, True)
    assert (CC.CHECKOUT / "src" / "repro" / "compile_cache.py").is_file()
    calls = _updates(monkeypatch)
    assert CC.enable_compile_cache() == path
    assert calls["jax_compilation_cache_dir"] == path
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0
