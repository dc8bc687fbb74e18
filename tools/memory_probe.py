"""Peak-memory probe for the depth-48 activation story (BASELINE.md
config #5; round-1 VERDICT #8: prove O(1)-in-depth activations, don't
just claim them).

AOT-compiles one full training step (loss + grads + adam update) at a
sweep of depths and reports XLA's own memory analysis (argument/output/
temp/generated-code bytes). Compile-only: nothing executes, so a config
that would OOM at runtime still yields its planned peak. With scan+remat
the temp (activation) bytes must stay ~flat in depth; without remat they
grow linearly.

Usage:
  python tools/memory_probe.py [--depths 2,8,48] [--len 384] [--dim 256]
                               [--reversible] [--run]
`--run` additionally executes one step at the largest depth and prints
live device memory stats (jax.local_devices()[0].memory_stats()).

Same rule as bench.py: the default backend must be a TPU (exit 2
otherwise) — another backend's buffer plan says nothing about HBM — and
every line names platform, device_kind and device count. To plan for a
chip that is not attached, compile for a described topology instead
(tests/test_chip_compile.py shows how).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from alphafold2_tpu.runtime import (device_info, enable_compile_cache,  # noqa: E402
                                    on_tpu)


def analyze(depth: int, seq_len: int, dim: int, reversible: bool,
            use_scan: bool = True, run: bool = False):
    from alphafold2_tpu import Alphafold2
    from alphafold2_tpu.data.synthetic import synthetic_batch
    from alphafold2_tpu.train import TrainState, adam, make_train_step

    model = Alphafold2(dim=dim, depth=depth, heads=8, dim_head=64,
                       dtype=jnp.bfloat16, reversible=reversible,
                       use_scan=use_scan)
    batch = synthetic_batch(jax.random.PRNGKey(0), batch=1, seq_len=seq_len,
                            msa_depth=5, with_coords=True)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(1), batch["seq"],
                           msa=batch["msa"], mask=batch["mask"],
                           msa_mask=batch["msa_mask"]))
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), params)
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=adam(3e-4), rng=jax.random.PRNGKey(2))
    step = jax.jit(make_train_step(model), donate_argnums=(0,))
    compiled = step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    out = {
        "depth": depth, "seq_len": seq_len, "dim": dim,
        "reversible": reversible, "use_scan": use_scan, **device_info(),
    }
    if mem is not None:
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                out[k.replace("_in_bytes", "_mb")] = round(v / 2**20, 1)
    if run:
        # reuse the AOT-compiled executable; calling `step` would
        # re-trace and re-compile (jit's call cache is separate)
        state, metrics = compiled(state, batch)
        jax.block_until_ready(metrics["loss"])
        out["loss"] = float(metrics["loss"])
        stats = jax.local_devices()[0].memory_stats() or {}
        for k in ("bytes_in_use", "peak_bytes_in_use"):
            if k in stats:
                out[k.replace("bytes", "mb")] = round(stats[k] / 2**20, 1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--depths", default="2,8,48")
    ap.add_argument("--len", dest="seq_len", type=int, default=384)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--reversible", action="store_true")
    ap.add_argument("--no-scan", action="store_true",
                    help="disable scan+remat (linear-memory comparison)")
    ap.add_argument("--run", action="store_true")
    args = ap.parse_args()

    if not on_tpu():
        print(json.dumps({**device_info(),
                          "error": "memory_probe plans for a TPU only"}),
              flush=True)
        return 2
    enable_compile_cache()
    depths = [int(d) for d in args.depths.split(",")]
    for i, d in enumerate(depths):
        res = analyze(d, args.seq_len, args.dim, args.reversible,
                      use_scan=not args.no_scan,
                      run=args.run and d == max(depths))
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
