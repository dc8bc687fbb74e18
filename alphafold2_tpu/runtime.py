"""What this process runs on: the platform predicate, the persistent
compile cache, and the explicit CPU rehearsal switch.

One owner for each, inside the package, so scripts, tools, tests and
replica processes agree. Importing this module (and jax) never
initializes a backend.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Fixed on purpose: the directory is part of the cache's key, so a path
# that moved (a pid, a time, a platform or flag hash in the name) would
# never hit. JAX's own key already covers backend and compiler flags.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def on_tpu() -> bool:
    """THE is-this-a-TPU predicate: kernel-vs-interpreter-vs-masked-dense
    dispatch and every measurement gate hang on it."""
    return jax.default_backend() == "tpu"


def device_info() -> dict:
    """platform / device_kind / device_count as JAX reports them — what
    every measurement line names, so a number can never pass for another
    device's."""
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns the directory
    in use. Where JAX_COMPILATION_CACHE_DIR is set, JAX's own handling of
    it is all there is — no directory is set here. Otherwise the cache
    lives at `<checkout>/.jax_cache` (git-ignored)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def use_cpu_platform() -> None:
    """Run this process on the CPU platform — an explicit choice
    (`--platform cpu` rehearsals, the virtual-device dry run), never a
    fallback. Call before the first backend use: the platform cannot be
    switched afterwards."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
