"""Benchmark: Evoformer training-step time @ 256-res crop (BASELINE.json
metric). One process measures on the default backend and names
`platform`, `device_kind` and the device count in its line. It measures
only on a TPU: on any other backend it prints an error line with no value
and exits non-zero — a CPU timing is never filed under this metric.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "ms", "vs_baseline": N, ...}

`vs_baseline` is the speedup ratio vs the reference implementation's
matched-config training step (torch-CPU, as recorded in
tools/reference_baseline.json — the reference publishes no numbers of its
own, see BASELINE.md).

The line also reports achieved TFLOP/s and MFU vs the chip's bf16 peak
(SURVEY.md §6). FLOPs are ANALYTIC (3x the forward contraction count from
alphafold2_tpu/utils/flops.py, custom kernels disabled during the
counting trace) — NOT XLA cost_analysis, which cannot see through
pallas_call custom calls and so under-reports exactly when the fast path
is engaged; cost_analysis is still emitted as a diagnostic field
(`xla_cost_analysis_tflops`).

Run it through the chip tool, alone: one process per chip. The benchmark
PR (ROADMAP Speed 1) replaces this file.
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

MSA, B = 5, 1

_FULL = dict(dim=256, depth=2, seq_len=256, warmup=2, iters=10)

# bf16 peak FLOP/s of one chip, keyed by `device_kind`, for MFU. A device
# that is not in the table is an error, not a default.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).
_PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def _cfg_from_env() -> dict:
    return dict(
        dim=int(os.environ.get("BENCH_DIM", _FULL["dim"])),
        depth=int(os.environ.get("BENCH_DEPTH", _FULL["depth"])),
        seq_len=int(os.environ.get("BENCH_LEN", _FULL["seq_len"])),
        warmup=max(1, int(os.environ.get("BENCH_WARMUP", _FULL["warmup"]))),
        iters=max(1, int(os.environ.get("BENCH_ITERS", _FULL["iters"]))),
    )


def _metric_name(cfg: dict) -> str:
    return (f"evoformer_distogram_train_step@{cfg['seq_len']}res"
            f"(dim{cfg['dim']},depth{cfg['depth']},msa{MSA},b{B})")


def _lookup_baseline(cfg: dict):
    """Matched-config reference step-time (seconds) or None."""
    path = os.path.join(_REPO, "tools", "reference_baseline.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        ref = json.load(f)
    # `entries` is the canonical list; a file from the original
    # single-config schema has only top-level keys
    entries = list(ref.get("entries", []))
    if not entries and "config" in ref:
        entries = [{"config": ref["config"],
                    "train_step_seconds": ref.get("train_step_seconds")}]
    for e in entries:
        c = e.get("config", {})
        if (c.get("dim"), c.get("depth"), c.get("seq_len"),
                c.get("msa_depth"), c.get("batch")) == \
                (cfg["dim"], cfg["depth"], cfg["seq_len"], MSA, B):
            return e.get("train_step_seconds")
    return None


def _xla_flops_of(compiled) -> float | None:
    """XLA cost_analysis flops — DIAGNOSTIC ONLY. It cannot see through
    custom calls (pallas_call), so it under-reports exactly when the
    fast path is engaged. The number of record is the analytic count
    from alphafold2_tpu.utils.flops (round-4 VERDICT #2)."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


def main() -> int:
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.runtime import (device_info, enable_compile_cache,
                                        on_tpu)

    cfg = _cfg_from_env()
    metric = _metric_name(cfg)
    device = device_info()
    error = None
    if not on_tpu():
        error = ("bench.py measures on a TPU only; the default backend is "
                 f"{jax.default_backend()!r}")
    elif device["device_kind"] not in _PEAK_BF16_FLOPS:
        error = (f"no bf16 peak recorded for {device['device_kind']!r}; "
                 "add it to _PEAK_BF16_FLOPS with its source")
    if error:
        print(json.dumps({"metric": metric, "value": None, "unit": "ms",
                          "vs_baseline": None, **device, "error": error}),
              flush=True)
        return 2
    enable_compile_cache()

    # a training step is a differentiated trace: its attention is the XLA
    # one (the fused kernel is forward-only, ops/attention.py)
    backend = "xla"

    from alphafold2_tpu import Alphafold2
    from alphafold2_tpu.data.synthetic import synthetic_batch
    from alphafold2_tpu.train import TrainState, adam, make_train_step

    # default bf16 — the production dtype on the TPU MXU
    dtype = jnp.dtype(os.environ.get("BENCH_DTYPE", "bfloat16"))
    # Shallow trunks unroll without scan+remat: the remat recompute (~1
    # extra trunk forward in the backward) costs more than the activation
    # memory it saves on a 16 GB chip (92.4 ms scan+remat -> 75.9 ms
    # unrolled on a v5e, recorded before PR 1 with BENCH_r05.json). Deep
    # trunks (the depth-48 flagship) need scan+remat to fit.
    # BENCH_SCAN=1/0 overrides.
    if os.environ.get("BENCH_SCAN") in ("0", "1"):
        use_scan = os.environ.get("BENCH_SCAN") == "1"
    else:
        use_scan = cfg["depth"] > 4
    model = Alphafold2(dim=cfg["dim"], depth=cfg["depth"], heads=8,
                       dim_head=64, dtype=dtype, use_scan=use_scan)
    batch = synthetic_batch(jax.random.PRNGKey(0), batch=B,
                            seq_len=cfg["seq_len"], msa_depth=MSA,
                            with_coords=True)
    params = model.init(jax.random.PRNGKey(1), batch["seq"],
                        msa=batch["msa"], mask=batch["mask"],
                        msa_mask=batch["msa_mask"])
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=adam(3e-4), rng=jax.random.PRNGKey(2))
    step = jax.jit(make_train_step(model), donate_argnums=(0,))
    compiled = step.lower(state, batch).compile()
    # analytic model FLOPs (3x forward contraction count, custom kernels
    # disabled for the counting trace): identical across Pallas/XLA
    # runs of one config by construction — the MFU numerator
    from alphafold2_tpu.utils.flops import train_step_flops
    flops = train_step_flops(model, params, batch)
    xla_flops = _xla_flops_of(compiled)

    # Barrier discipline: the steps are chained through `state`, and a
    # device_get of the loss cannot complete before the computation that
    # produces it, so one final fetch closes the whole timed window.
    for _ in range(cfg["warmup"]):
        state, metrics = step(state, batch)
    float(jax.device_get(metrics["loss"]))

    t0 = time.perf_counter()
    for _ in range(cfg["iters"]):
        state, metrics = step(state, batch)
    loss_val = float(jax.device_get(metrics["loss"]))
    ms = (time.perf_counter() - t0) / cfg["iters"] * 1e3

    ref_s = _lookup_baseline(cfg)
    tflops = round(flops / (ms / 1e3) / 1e12, 3) if flops else None
    mfu = (round(flops / (ms / 1e3) / _PEAK_BF16_FLOPS[device["device_kind"]], 4)
           if flops else None)

    print(json.dumps({
        "metric": metric,
        "value": round(ms, 3),
        "unit": "ms",
        "vs_baseline": round(ref_s * 1e3 / ms, 3) if ref_s else None,
        "backend": backend,
        **device,
        "dtype": dtype.name,
        "use_scan": use_scan,
        "warmup": cfg["warmup"],
        "iters": cfg["iters"],
        "tflops": tflops,
        "loss": round(loss_val, 4),
        "flops_model": "analytic-3x-forward (utils/flops.py)",
        "xla_cost_analysis_tflops": (
            round(xla_flops / (ms / 1e3) / 1e12, 3) if xla_flops else None),
        "mfu": mfu,
        "config_scaled": (cfg["dim"], cfg["depth"], cfg["seq_len"]) !=
                         (_FULL["dim"], _FULL["depth"], _FULL["seq_len"]),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
