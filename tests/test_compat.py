"""Tests for the launch/compat process-setup helpers.

``force_host_device_count`` rewrites ``XLA_FLAGS`` so that XLA's CPU backend
exposes a host mesh; XLA honours the LAST occurrence of a flag, so stale
inherited values must be dropped, not shadowed.  ``enable_compile_cache``
places JAX's persistent compilation cache: where the environment says, or
at one fixed path in the repository -- the path is part of the cache key.
"""
from __future__ import annotations

import os
import pathlib

import jax
import pytest

from repro.launch.compat import enable_compile_cache, force_host_device_count

COUNT8 = "--xla_force_host_platform_device_count=8"
REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def clean_env(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    return monkeypatch


class TestForceHostDeviceCountComposition:
    """The rewrite composes with whatever XLA_FLAGS the process inherits."""

    def test_inherited_count_is_replaced_not_shadowed(self, clean_env):
        """XLA honours the LAST occurrence of the flag; stale inherited
        values must be dropped, not merely appended after."""
        clean_env.setenv(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=2")
        force_host_device_count(8)
        flags = os.environ["XLA_FLAGS"].split()
        assert COUNT8 in flags
        assert "--xla_force_host_platform_device_count=2" not in flags

    def test_idempotent(self, clean_env):
        force_host_device_count(8)
        first = os.environ["XLA_FLAGS"]
        force_host_device_count(8)
        assert set(os.environ["XLA_FLAGS"].split()) == set(first.split())
        assert os.environ["XLA_FLAGS"].split().count(COUNT8) == 1


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def restore_cache_dir(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_dir_is_used(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)

    def test_unset_env_uses_fixed_repo_path(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path          # same on every call
