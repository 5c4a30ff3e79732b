"""The persistent compile cache every entry point turns on."""

import ast
import os
import pathlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch.compile_cache import (
    CACHE_ENV,
    DEFAULT_CACHE_DIR,
    enable_compile_cache,
)

ROOT = pathlib.Path(__file__).resolve().parent.parent
LAUNCHERS = sorted((ROOT / "src" / "repro" / "launch").glob("*.py"))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(CACHE_ENV, "/some/cache")
    assert enable_compile_cache() == "/some/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(
    monkeypatch, restore_cache_dir
):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    assert DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def _main_calls(path: pathlib.Path) -> set[str] | None:
    tree = ast.parse(path.read_text())
    for node in tree.body:
        if isinstance(node, ast.FunctionDef) and node.name == "main":
            return {
                c.func.id
                for c in ast.walk(node)
                if isinstance(c, ast.Call) and isinstance(c.func, ast.Name)
            }
    return None


@pytest.mark.parametrize(
    "path",
    [p for p in LAUNCHERS if _main_calls(p) is not None]
    + [ROOT / "chip_smoke.py"],
    ids=lambda p: p.stem,
)
def test_every_entry_point_enables_the_cache(path):
    assert "enable_compile_cache" in _main_calls(path)


def test_library_import_leaves_the_cache_alone():
    import repro.api  # noqa: F401
    import repro.core  # noqa: F401

    assert jax.config.jax_compilation_cache_dir == os.environ.get(CACHE_ENV)
