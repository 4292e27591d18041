"""Backend helpers (acg_tpu/utils/backend.py): no CPU fallback where a
TPU is required, and a compile cache placed from outside."""

import os

import jax
import pytest

from acg_tpu.utils.backend import (REPO_ROOT, NoTpuError,
                                   enable_compile_cache, require_tpu)


def test_require_tpu_raises_on_cpu():
    with pytest.raises(NoTpuError, match="cpu"):
        require_tpu()


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, tmp_path,
                                   restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_fixed_path_without_env(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert enable_compile_cache() == want
    assert enable_compile_cache() == want       # stable across calls
    assert jax.config.jax_compilation_cache_dir == want
