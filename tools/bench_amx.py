"""Microbenchmark the native AMX GEMM against XLA:CPU's dot.

Times the four FFI entry points (plain, transposed-B, and the two
natural-layout attention ops) at the model's Dense and attention shapes,
next to the matching XLA contraction. One JSON line per shape.

Caveat on this host: sustained AMX load power-licenses the core, so
absolute GFLOP/s swing ~25% run to run — compare the paired ours/xla
numbers within one invocation, not across invocations.

Usage: python tools/bench_amx.py [--iters 10]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from alphafold2_tpu.ops import cpu_gemm  # noqa: E402


def _time(fn, *args, iters=10):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    cpu_gemm.use_amx_dense(True)
    if not cpu_gemm.amx_dense_enabled():
        print(json.dumps({"error": "AMX unavailable on this host"}))
        return 1

    key = jax.random.PRNGKey(0)

    # Dense shapes at the bench full config (dim 256, 256res: 65536 pair
    # tokens) and the attention shapes (256 rows x 8 heads, 256 keys, 64)
    shapes = [
        ("dense_qkv", "gemm", (65536, 256, 512)),
        ("dense_ff", "gemm", (65536, 256, 2048)),
        ("attn_qk", "attn", (256, 256, 256, 8, 64)),
    ]
    for name, kind, dims in shapes:
        if kind == "gemm":
            m, k, n = dims
            a = jax.random.normal(key, (m, k), jnp.float32)
            b = jax.random.normal(key, (k, n), jnp.float32)
            t_amx = _time(jax.jit(cpu_gemm.amx_matmul), a, b,
                          iters=args.iters)
            t_xla = _time(jax.jit(jnp.matmul), a, b, iters=args.iters)
            flops = 2.0 * m * k * n
        else:
            b_, n, m, h, d = dims
            q = jax.random.normal(key, (b_ // h, n, h, d), jnp.float32)
            kk = jax.random.normal(key, (b_ // h, m, h, d), jnp.float32)
            t_amx = _time(jax.jit(cpu_gemm.amx_attn_qk), q, kk,
                          iters=args.iters)
            t_xla = _time(
                jax.jit(lambda q, k: jnp.einsum("bnhd,bmhd->bhnm", q, k)),
                q, kk, iters=args.iters)
            flops = 2.0 * (b_ // h) * h * n * m * d
        print(json.dumps({
            "shape": name, "dims": dims,
            "amx_gflops": round(flops / t_amx / 1e9, 1),
            "xla_gflops": round(flops / t_xla / 1e9, 1),
            "speedup": round(t_xla / t_amx, 2)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
