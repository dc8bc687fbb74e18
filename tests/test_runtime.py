"""The device-facing seams (alphafold2_tpu/runtime.py and the scripts that
hang on it): one platform predicate, one placeable compile cache, and no
script that reports a result without a TPU."""

import os
import subprocess
import sys

import jax
import pytest

from alphafold2_tpu import runtime

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_platform_predicate_is_false_on_cpu():
    assert jax.default_backend() == "cpu"
    assert runtime.on_tpu() is False


def test_kernel_dispatch_follows_the_predicate(monkeypatch):
    from alphafold2_tpu.model import attention_variants
    from alphafold2_tpu.model.attention_variants import BlockSparseAttention

    sparse = BlockSparseAttention(dim=32, heads=2, dim_head=16)
    assert sparse._kernel_available() is False
    monkeypatch.setattr(attention_variants, "on_tpu", lambda: True)
    assert sparse._kernel_available() is True


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_compile_cache_owner(env_dir, monkeypatch, restore_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set the owner sets NO directory
    (JAX's own handling of the variable is all there is); unset, the cache
    is the fixed <checkout>/.jax_cache."""
    sentinel = "/left/alone"
    jax.config.update("jax_compilation_cache_dir", sentinel)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    used = runtime.enable_compile_cache()
    expect = sentinel if env_dir else os.path.join(_REPO, ".jax_cache")
    assert used == expect == jax.config.jax_compilation_cache_dir
    assert runtime.DEFAULT_COMPILE_CACHE_DIR == os.path.join(
        _REPO, ".jax_cache")


def _run(argv):
    return subprocess.run([sys.executable] + argv, cwd=_REPO,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_no_result_without_a_tpu(script):
    """In this sandbox both exit non-zero, print no `"ok": true` and no
    timing under a device metric's name."""
    proc = _run([script])
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert '"value": null' in proc.stdout or not proc.stdout.strip()


def test_loadtest_refuses_cpu_replicas_named_as_the_chip():
    proc = _run(["tools/serve_loadtest.py", "--procs", "2",
                 "--platform", "ambient"])
    assert proc.returncode == 2
    assert "refused" in proc.stderr and not proc.stdout.strip()
