"""Where the persistent compilation cache goes (never turned on here:
``jax.config.update`` is intercepted)."""
import jax

from repro.launch import compile_cache


def _updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_setting_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _updates(monkeypatch)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_is_the_repo_cache_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _updates(monkeypatch)
    path = compile_cache.enable_compile_cache()
    root = compile_cache.DEFAULT_DIR.parent
    assert path == str(root / ".jax_cache")
    assert (root / "src" / "repro").is_dir() and (root / "chip_smoke.py"
                                                  ).is_file()
    assert calls == [("jax_compilation_cache_dir", path)]
