"""Multi-config benchmark suite over the BASELINE.json configs.

`bench.py` covers the north-star metric (config-1-shaped train step at
256res). This tool fills the rest of the BASELINE table: one JSON line
per config with train-step ms and, where the config folds structures,
folds/hour/chip (inference with recycling).

Configs (BASELINE.md "Benchmark configs to measure"):
  1 distogram-only dim256/depth2 trunk, 128-res
  2 trRosetta-mode: predict_angles trunk with anglegram CE targets
    (the ESM seq-embed preprocessing is host-side and not timed here)
  3 EGNN structure module end-to-end, 64-res, backbone coords
  4 SE3-style refiner, refinement_iters=4, reversible trunk
  5 flagship: depth-48 trunk, 384-res, 3x recycling, pair-sharded mesh
  fold: folds/hour/chip at 256-res with 3 recycles (predict_coords IPA)

Usage:
  python tools/bench_suite.py [--configs 1,2,3,4,fold] [--iters 5]
                              [--tiny]   # smoke sizes

Same rule as bench.py: one process on the default backend, every line
names platform, device_kind and device count, and a backend that is not
a TPU is an error (exit 2) — these are device metrics. Send it through
the chip tool, alone: one process per chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from alphafold2_tpu.runtime import (device_info, enable_compile_cache,  # noqa: E402
                                    on_tpu)

# BENCH_DTYPE override, mirroring bench.py: the production dtype is bf16
# (TPU MXU)
_DTYPE = jnp.dtype(os.environ.get("BENCH_DTYPE", "bfloat16"))

from alphafold2_tpu import Alphafold2  # noqa: E402
from alphafold2_tpu.data.synthetic import synthetic_batch  # noqa: E402
from alphafold2_tpu.predict import fold  # noqa: E402
from alphafold2_tpu.train import TrainState, adam, make_train_step  # noqa: E402


def _train_step_ms(model, batch, iters, warmup=1):
    params = model.init(
        {"params": jax.random.PRNGKey(1), "mlm": jax.random.PRNGKey(2)},
        batch["seq"], msa=batch["msa"], mask=batch["mask"],
        msa_mask=batch["msa_mask"], train=True)
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=adam(3e-4), rng=jax.random.PRNGKey(3))
    step = jax.jit(make_train_step(model), donate_argnums=(0,))
    # the steps chain through `state`; a dependent fetch of the loss
    # closes the window
    for _ in range(warmup):
        state, metrics = step(state, batch)
    float(jax.device_get(metrics["loss"]))
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, batch)
    float(jax.device_get(metrics["loss"]))
    return (time.perf_counter() - t0) / iters * 1e3


def config_1(tiny, iters):
    l = 32 if tiny else 128
    model = Alphafold2(dim=64 if tiny else 256, depth=2, heads=8,
                       dim_head=64, dtype=_DTYPE)
    batch = synthetic_batch(jax.random.PRNGKey(0), batch=1, seq_len=l,
                            msa_depth=5, with_coords=True)
    return {"config": "1_distogram_128res",
            "train_step_ms": round(_train_step_ms(model, batch, iters), 2)}


def config_2(tiny, iters):
    l = 32 if tiny else 128
    dim = 64 if tiny else 256
    model = Alphafold2(dim=dim, depth=2, heads=8, dim_head=64,
                       predict_angles=True, dtype=_DTYPE)
    # with_angles: theta/phi/omega bucket targets so the anglegram CE
    # loss (and its backward) is actually part of the timed step
    batch = synthetic_batch(jax.random.PRNGKey(0), batch=1, seq_len=l,
                            msa_depth=5, with_coords=True,
                            with_angles=True)
    return {"config": "2_trrosetta_angles",
            "train_step_ms": round(_train_step_ms(model, batch, iters), 2)}


def config_3(tiny, iters):
    l = 16 if tiny else 64
    model = Alphafold2(dim=32 if tiny else 128, depth=2, heads=8,
                       dim_head=64, predict_coords=True,
                       structure_module_type="egnn",
                       structure_module_depth=2, dtype=_DTYPE)
    batch = synthetic_batch(jax.random.PRNGKey(0), batch=1, seq_len=l,
                            msa_depth=5, with_coords=True)
    return {"config": "3_egnn_end2end_64res",
            "train_step_ms": round(_train_step_ms(model, batch, iters), 2)}


def config_4(tiny, iters):
    l = 16 if tiny else 64
    model = Alphafold2(dim=32 if tiny else 128, depth=2, heads=8,
                       dim_head=64, predict_coords=True,
                       structure_module_type="se3",
                       structure_module_depth=2,
                       structure_module_refinement_iters=4,
                       reversible=True, dtype=_DTYPE)
    batch = synthetic_batch(jax.random.PRNGKey(0), batch=1, seq_len=l,
                            msa_depth=5, with_coords=True)
    return {"config": "4_se3_refine_reversible",
            "train_step_ms": round(_train_step_ms(model, batch, iters), 2)}


def config_fold(tiny, iters):
    l = 32 if tiny else 256
    model = Alphafold2(dim=64 if tiny else 256, depth=2, heads=8,
                       dim_head=64, predict_coords=True,
                       structure_module_depth=2, dtype=_DTYPE)
    batch = synthetic_batch(jax.random.PRNGKey(0), batch=1, seq_len=l,
                            msa_depth=5, with_coords=False)
    params = model.init(jax.random.PRNGKey(1), batch["seq"],
                        msa=batch["msa"], mask=batch["mask"],
                        msa_mask=batch["msa_mask"])

    import functools
    run = jax.jit(functools.partial(fold, model,
                                    num_recycles=3))
    res = run(params, batch["seq"], msa=batch["msa"], mask=batch["mask"],
              msa_mask=batch["msa_mask"])
    jax.device_get(res.coords)
    t0 = time.perf_counter()
    for _ in range(iters):
        res = run(params, batch["seq"], msa=batch["msa"],
                  mask=batch["mask"], msa_mask=batch["msa_mask"])
    jax.device_get(res.coords)
    sec = (time.perf_counter() - t0) / iters
    return {"config": f"fold_{l}res_3recycles",
            "fold_seconds": round(sec, 4),
            "folds_per_hour_per_chip": round(3600.0 / sec, 1)}


def config_5(tiny, iters):
    """BASELINE config 5 — the flagship: depth-48 Evoformer, 384-res,
    3x recycling, pair representation sharded over the mesh's (i, j)
    axes when the platform offers >1 device (the v4-32 row of
    BASELINE.md, scaled to whatever is attached).

    Emits train-step time, AOT peak-memory analysis of the compiled
    step (pairs with tools/memory_probe.py's depth sweep), and the
    3-recycle fold time.
    """
    import contextlib

    from alphafold2_tpu.parallel import make_mesh, use_mesh

    l = 32 if tiny else 384
    depth = 4 if tiny else 48
    dim = 64 if tiny else 256
    model = Alphafold2(dim=dim, depth=depth, heads=8, dim_head=64,
                       predict_coords=True, structure_module_depth=2,
                       dtype=_DTYPE)
    batch = synthetic_batch(jax.random.PRNGKey(0), batch=1, seq_len=l,
                            msa_depth=5, with_coords=True)

    ndev = len(jax.devices())
    mesh = None
    if ndev >= 4 and ndev % 2 == 0:
        mesh = make_mesh(1, 2, ndev // 2)   # (i=2, j=ndev/2) pair grid
    elif ndev == 2:
        mesh = make_mesh(1, 2, 1)
    ctx = use_mesh(mesh) if mesh is not None else contextlib.nullcontext()

    entry = {"config": f"5_flagship_depth{depth}_{l}res",
             "mesh": None if mesh is None else
             {k: int(v) for k, v in mesh.shape.items()}}
    with ctx:
        params = model.init(
            {"params": jax.random.PRNGKey(1), "mlm": jax.random.PRNGKey(2)},
            batch["seq"], msa=batch["msa"], mask=batch["mask"],
            msa_mask=batch["msa_mask"], train=True)
        state = TrainState.create(apply_fn=model.apply, params=params,
                                  tx=adam(3e-4), rng=jax.random.PRNGKey(3))
        step = jax.jit(make_train_step(model), donate_argnums=(0,))
        compiled = step.lower(state, batch).compile()
        mem = compiled.memory_analysis()
        if mem is not None:
            for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    entry[k.replace("_in_bytes", "_gb")] = round(
                        v / 2**30, 3)

        # time with the ALREADY-compiled step/state — a second init +
        # re-jit of the largest model in the suite would double its
        # dominant cost
        st = state
        for _ in range(1):
            st, metrics = step(st, batch)
        float(jax.device_get(metrics["loss"]))
        t0 = time.perf_counter()
        for _ in range(iters):
            st, metrics = step(st, batch)
        float(jax.device_get(metrics["loss"]))
        entry["train_step_ms"] = round(
            (time.perf_counter() - t0) / iters * 1e3, 2)

        import functools
        run = jax.jit(functools.partial(fold, model, num_recycles=3))
        # st.params, not params: the donated train step above consumed
        # the original param buffers
        fparams = st.params
        res = run(fparams, batch["seq"], msa=batch["msa"],
                  mask=batch["mask"], msa_mask=batch["msa_mask"])
        jax.device_get(res.coords if hasattr(res, "coords")
                       else res.distogram)
        t0 = time.perf_counter()
        for _ in range(max(1, iters // 2)):
            res = run(fparams, batch["seq"], msa=batch["msa"],
                      mask=batch["mask"], msa_mask=batch["msa_mask"])
        jax.device_get(res.coords if hasattr(res, "coords")
                       else res.distogram)
        entry["fold_3recycle_seconds"] = round(
            (time.perf_counter() - t0) / max(1, iters // 2), 3)
    return entry


CONFIGS = {"1": config_1, "2": config_2, "3": config_3, "4": config_4,
           "5": config_5, "fold": config_fold}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1,2,3,4,5,fold")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    device = device_info()
    if not on_tpu():
        print(json.dumps({**device, "error": "bench_suite measures on a "
                          "TPU only"}), flush=True)
        return 2
    enable_compile_cache()
    for key in args.configs.split(","):
        res = CONFIGS[key](args.tiny, args.iters)
        res.update(device)
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
