"""The persistent compilation cache helper (``repro.launch.compile_cache``):
the environment's directory wins untouched; otherwise a fixed in-checkout
path, ignored by git."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_only_the_helper_sets_a_cache_dir():
    setter = "jax_compilation_cache" + "_dir"
    sources = [*(REPO / "src").rglob("*.py"), *REPO.glob("*.py"),
               *(REPO / "benchmarks").glob("*.py")]
    hits = {p.relative_to(REPO).as_posix() for p in sources
            if setter in p.read_text()}
    assert hits == {"src/repro/launch/compile_cache.py"}
