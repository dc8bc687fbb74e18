"""Test harness: run everything on a virtual 8-device CPU platform so
multi-chip sharding is exercised without a TPU pod (SURVEY.md §4).

The suite never touches an accelerator: this file names the CPU platform
(overriding, not defaulting, whatever the environment says) before jax
initializes a backend. The chip is reached only through the chip tool,
with `python chip_smoke.py` as the command (README "Tests").
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from alphafold2_tpu.runtime import enable_compile_cache  # noqa: E402

# an entry-point plugin may have imported jax before this file ran, in
# which case jax's config captured the environment's platform already;
# backends are not initialized yet at conftest time, so forcing the
# config value directly covers that case too (round-3 VERDICT weak #5)
jax.config.update("jax_platforms", "cpu")

jax.config.update("jax_default_matmul_precision", "float32")

# persistent compilation cache: the suite is compile-dominated (many tiny
# model configs); caching across runs cuts wall-clock dramatically
enable_compile_cache()


def perturb_params(params, key, scale=0.05):
    """Add noise to every leaf — moves zero-init output projections off
    zero so backend/path-parity comparisons are not trivially 0 == 0."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten(
        [l + scale * jax.random.normal(k, l.shape, l.dtype)
         for l, k in zip(leaves, keys)])


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "quick: fast smoke tier (one representative test per subsystem, "
        "~4-5 min on 1 CPU core): python -m pytest -m quick")
    config.addinivalue_line(
        "markers",
        "slow: heavyweight tier excluded from tier-1 (-m 'not slow'): "
        "multi-process fleets, real kill/partition chaos "
        "(tests/test_frontdoor.py's procfleet class, serve_smoke.sh "
        "phase 6 in miniature)")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop in-process compiled executables after each test module: a
    monolithic 285-test process accumulated compiler state that
    segfaulted XLA:CPU compiling the pp train step ~57% in (r05, twice:
    once in cache deserialization, once in backend_compile_and_load).
    The persistent disk cache keeps recompiles cheap."""
    yield
    jax.clear_caches()
