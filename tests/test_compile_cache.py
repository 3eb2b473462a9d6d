"""Launchers' compile cache: JAX's own variable wins; otherwise a fixed
directory in the checkout. The real JAX config is never touched here."""
from pathlib import Path

import jax

from repro.launch import compile_cache


def test_env_variable_is_left_to_jax(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_checkout_cache() == str(tmp_path)
    assert calls == []


def test_unset_uses_checkout_dir(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = Path(__file__).resolve().parents[1]
    want = str(checkout / ".jax_cache")
    assert compile_cache.use_checkout_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
    assert ".jax_cache/" in (checkout / ".gitignore").read_text().split()
