#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one chip, no arguments (send it through the chip tool):
    python chip_smoke.py

drives the main path once through the entry points a user calls, at the
published widths of the model the repo's on-chip record used (dim 256,
8 heads x 64, IPA structure module depth 2, bf16; trunk depth 2 is the
only cut), 256-residue bucket, MSA depth 5, 3 recycles, random weights
from `--seed`:

- kernels: one COMPILED (never interpreted) call each of `fused_attention`
  and `block_sparse_attention` against the masked-dense XLA path;
- server:  `serve.FoldExecutor` -> `serve.Scheduler`, `warmup()`, a few
  requests of different lengths, both ways the scheduler can run a fold
  (opaque `lax.scan` fold, host-driven step loop), compared with each
  other and with a direct `jax.jit(predict.fold)` on the same padded
  inputs;
- trainer: `train.fit` for three steps of `make_train_step`.

`--four-chips` runs ONLY the path across chips and what it is compared
with, in one process that drives all four chips: the same request through
`Scheduler(mesh_policy=MeshPolicy({256: 4}))` against the one-chip fold on
device 0, and one `make_train_step` under `make_mesh(1, 2, 2)` (ring
attention off and on) against the one-chip loss.

Every phase prints one JSON line of observations (not benchmark records).
The LAST line of stdout is the verdict and nothing more:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

A failed phase makes `ok` false and the exit code 1. Without a TPU the
script exits 2 before building a model and prints no verdict.

Sizes are arguments of the phase functions so a scratch script can drive
them tiny on the CPU; the program itself has no option that weakens the
device check.
"""

from __future__ import annotations

import argparse
import functools
import importlib.metadata
import itertools
import json
import math
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from alphafold2_tpu import Alphafold2, constants, predict, serve
from alphafold2_tpu.data.synthetic import synthetic_batch
from alphafold2_tpu.ops.attention import (MASK_VALUE, attention_reference,
                                          fused_attention)
from alphafold2_tpu.ops.block_sparse import (banded_block_pattern,
                                             block_sparse_attention)
from alphafold2_tpu.parallel import make_mesh, shard_pytree_tp_zero, use_mesh
from alphafold2_tpu.runtime import enable_compile_cache, on_tpu
from alphafold2_tpu.serve.meshpolicy import factor_chips
from alphafold2_tpu.train import (TrainState, adam, fit, make_train_step,
                                  shard_batch)

# the model of the repo's only on-chip record, at its published widths
FULL_MODEL = dict(dim=256, depth=2, heads=8, dim_head=64,
                  structure_module_depth=2)
BUCKET, MSA_DEPTH, NUM_RECYCLES = 256, 5, 3
LENGTHS = (96, 200, 256)
TRAIN_STEPS = 3

# Stated tolerances. Activations are bf16 (8 mantissa bits, eps 2^-8):
# - kernels: max |pallas - masked-dense| on O(1) attention outputs whose
#   logits the XLA path rounds to bf16 and the kernel keeps in f32;
# - folds: ||a - b|| / ||b|| of the coords (and distogram logits) after
#   (1 + 3) trunk+structure passes, and max |a - b| of the confidence.
#   Scan fold, step loop and the direct jit trace the same pass but are
#   different compiled programs (the direct jit and the executor's scan
#   fold are the same one); the four-chip program computes the same
#   function (1e-6 apart in f32 on virtual devices) and reorders every
#   reduction. Each recycle amplifies bf16 rounding: a 2^-9 relative
#   jitter of every weight moves the coords by 2.4% (CPU probe at full
#   width, PR 22). On the v5e the step loop was 1.3e-2 from the scan fold,
#   the four-chip fold 1.3e-2 from the one-chip fold, and the same fold
#   with its attention in the fused kernel against XLA's (two programs
#   that differ only in rounding) 1.3e-2 (my chip runs, PR 22; since PR 27
#   every fold on a TPU takes the kernel by shape and no switch is left to
#   make that pair). A wrong program is O(1) away;
# - loss: relative difference of one train step's loss.
KERNEL_TOL = 3e-2
FOLD_TOL = 1e-1
MESH_LOSS_TOL = 2e-2

_COMPILES = []      # (fun_name, seconds), fed by jax.monitoring


def emit(obj):
    print(json.dumps(obj), flush=True)


def require(cond, *detail):
    """A check that survives `python -O` (an `assert` would not)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {detail}")


def require_tpu(min_count: int):
    """Exit 2, verdict-less, unless JAX's default backend is a TPU with at
    least `min_count` devices. Runs before any model is built."""
    devs = jax.devices()
    if not on_tpu() or len(devs) < min_count:
        print(f"chip_smoke: needs {min_count} TPU device(s); JAX gives "
              f"{len(devs)} x {devs[0].platform!r} — send this script "
              "through the chip tool", file=sys.stderr, flush=True)
        sys.exit(2)
    return devs


def device_report():
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_report(devices=None):
    """bytes_limit / peak_bytes_in_use of each device (what the
    `hbm_gb=16.0` default of serve/meshpolicy.py is checked against)."""
    out = []
    for d in devices or jax.devices()[:1]:
        stats = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_limit": stats.get("bytes_limit"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def rel_err(a, ref) -> float:
    """max |a - ref| over max |ref|."""
    a, ref = np.asarray(a, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(a - ref)) / (np.max(np.abs(ref)) + 1e-6))


def rel_l2(a, ref) -> float:
    """||a - ref|| over ||ref||."""
    a, ref = np.asarray(a, np.float32), np.asarray(ref, np.float32)
    return float(np.linalg.norm(a - ref) / (np.linalg.norm(ref) + 1e-6))


def _finite(x) -> bool:
    return bool(np.isfinite(np.asarray(x, np.float32)).all())


# -- model ------------------------------------------------------------------

def build_model(seed: int, seq_len: int, msa_depth: int, **model_kw):
    """(model, params): random weights from `seed`. Every leaf is nudged
    off its initializer so zero-initialized output projections cannot make
    a comparison trivially 0 == 0 — matrices by half a LeCun init
    (0.5 / sqrt(fan_in)), vectors by 0.05. A flat 0.05 on every leaf is
    ~1.6x a LeCun init at fan-in 1024 and tips this width into chaos: a
    2^-9 relative jitter of the weights then moves the 3-recycle coords by
    49% (CPU probe, PR 22), against 2% with this recipe."""
    model = Alphafold2(predict_coords=True, dtype=jnp.bfloat16, **model_kw)
    seq = jnp.zeros((1, seq_len), jnp.int32)
    msa = jnp.zeros((1, msa_depth, seq_len), jnp.int32)

    @jax.jit
    def init(key):
        k_init, k_noise = jax.random.split(key)
        params = model.init(k_init, seq, msa=msa,
                            mask=jnp.ones(seq.shape, bool),
                            msa_mask=jnp.ones(msa.shape, bool))
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(k_noise, len(leaves))
        return treedef.unflatten(
            [l + (0.5 * l.shape[-2] ** -0.5 if l.ndim >= 2 else 0.05)
             * jax.random.normal(k, l.shape, l.dtype)
             for l, k in zip(leaves, keys)])

    params = jax.block_until_ready(init(jax.random.PRNGKey(seed)))
    return model, params


def make_request(seed: int, length: int, msa_depth: int):
    rng = np.random.default_rng(seed)
    return serve.FoldRequest(
        rng.integers(0, constants.NUM_AMINO_ACIDS, size=(length,)),
        msa=rng.integers(0, constants.NUM_AMINO_ACIDS,
                         size=(msa_depth, length)))


# -- kernels ----------------------------------------------------------------

def kernel_diffs(*, n: int, d: int, block: int, heads: int, seed: int,
                 interpret: bool) -> dict:
    """Both Pallas kernels against the masked-dense XLA path on the same
    bf16 inputs, with an f32 pair bias (unrepeated, replayed over a folded
    axis of 2) and a key mask that hides the tail. `interpret` is what the
    caller's platform demands; chip_smoke itself only ever passes False."""
    fold_axis = 2
    b = fold_axis * heads
    kq, kk, kv, kb = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = (jax.random.normal(kq, (b, n, d)) * d ** -0.5).astype(jnp.bfloat16)
    k = jax.random.normal(kk, (b, n, d)).astype(jnp.bfloat16)
    v = jax.random.normal(kv, (b, n, d)).astype(jnp.bfloat16)
    bias = jax.random.normal(kb, (heads, n, n), jnp.float32)
    k_mask = jnp.broadcast_to(jnp.arange(n) < n - n // 8, (fold_axis, n))

    # a pattern with dead blocks: the diagonal plus the first block row and
    # column; the reference takes it as a bias over the tokens
    pattern = banded_block_pattern(n // block, window=0, num_global=1)
    live = np.repeat(np.repeat(pattern, block, 0), block, 1)
    fill = jnp.where(jnp.asarray(live), 0.0,
                     MASK_VALUE).astype(jnp.float32)[None]

    dense = jax.jit(functools.partial(
        attention_reference, heads=heads, bias_repeat=fold_axis))
    fused = jax.jit(functools.partial(
        fused_attention, heads=heads, bias_repeat=fold_axis,
        interpret=interpret))
    sparse = jax.jit(functools.partial(
        block_sparse_attention, pattern=pattern, bias_repeat=fold_axis,
        heads=heads, scale=1.0, block=block, interpret=interpret))

    out = {}
    fused_txt = fused.lower(q, k, v, bias=bias, k_mask=k_mask).as_text()
    sparse_txt = sparse.lower(q, k, v, bias=bias, k_mask=k_mask).as_text()
    out["tpu_custom_call"] = {"fused": "tpu_custom_call" in fused_txt,
                              "block_sparse": "tpu_custom_call" in sparse_txt}
    got_f = fused(q, k, v, bias=bias, k_mask=k_mask)
    ref_f = dense(q, k, v, bias=bias, k_mask=k_mask)
    got_s = sparse(q, k, v, bias=bias, k_mask=k_mask)
    ref_s = dense(q, k, v, bias=bias + fill, k_mask=k_mask)
    for name, got, ref in (("fused", got_f, ref_f),
                           ("block_sparse", got_s, ref_s)):
        got, ref = (np.asarray(x, np.float32) for x in (got, ref))
        require(got.shape == (b, n, d) and np.isfinite(got).all(), name)
        out[f"{name}_max_abs_diff"] = float(np.max(np.abs(got - ref)))
    out["live_block_fraction"] = float(pattern.mean())
    return out


def phase_kernels(*, n: int, d: int, block: int, heads: int, seed: int,
                  tol: float) -> dict:
    out = kernel_diffs(n=n, d=d, block=block, heads=heads, seed=seed,
                       interpret=False)
    require(all(out["tpu_custom_call"].values()), out["tpu_custom_call"])
    problems = [f"{name} kernel is {out[f'{name}_max_abs_diff']:.4g} from "
                f"masked-dense, tol {tol}"
                for name in ("fused", "block_sparse")
                if out[f"{name}_max_abs_diff"] > tol]
    return dict(out, n=n, d=d, block=block, tol=tol, problems=problems)


# -- server -----------------------------------------------------------------

_BAD_COUNTERS = ("shed", "errors", "cancelled", "rejected", "degraded",
                 "poisoned", "retried", "too_large")


def serve_requests(model, params, requests, *, bucket: int, msa_depth: int,
                   num_recycles: int, recycle_policy=None, mesh_policy=None):
    """The README's serving recipe: FoldExecutor -> Scheduler, warmup(),
    submit, result. Returns (responses, observations, executor)."""
    executor = serve.FoldExecutor(model, params, max_entries=8)
    scheduler = serve.Scheduler(
        executor, serve.BucketPolicy((bucket,)),
        serve.SchedulerConfig(max_batch_size=1, num_recycles=num_recycles,
                              msa_depth=msa_depth),
        recycle_policy=recycle_policy, mesh_policy=mesh_policy)
    with scheduler:
        t0 = time.perf_counter()
        fresh = scheduler.warmup()
        warmup_s = time.perf_counter() - t0
        responses = [scheduler.submit(r).result(timeout=600)
                     for r in requests]
        stats = scheduler.serve_stats()
    for req, resp in zip(requests, responses):
        require(resp.status == "ok", resp.request_id, resp.status, resp.error)
        require(resp.coords.shape == (req.length, 3), resp.coords.shape)
        require(_finite(resp.coords) and _finite(resp.confidence), "finite")
    bad = {k: stats[k] for k in _BAD_COUNTERS if stats.get(k)}
    require(not bad and stats["served"] == len(requests), bad, stats["served"])
    obs = {"fresh_compiles": fresh, "warmup_s": round(warmup_s, 3),
           "served": stats["served"],
           "latency_s": [round(r.latency_s, 5) for r in responses],
           "recycles": [r.recycles for r in responses],
           "executor": {k: stats["executor"][k]
                        for k in ("hits", "misses", "resident")}}
    return responses, obs, executor


def time_fold_two_ways(fn, args, reps: int = 3) -> dict:
    """Per-fold wall time closed by `jax.block_until_ready` (what
    serve/executor.py relies on) and by a dependent `device_get`, plus how
    long a fetch still takes once block_until_ready has returned — if the
    barrier returned early, that residual is where the fold would hide."""
    block, residual, fetch = [], [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        t1 = time.perf_counter()
        jax.device_get(out.coords)
        t2 = time.perf_counter()
        block.append(round(t1 - t0, 5))
        residual.append(round(t2 - t1, 5))
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.device_get(fn(*args).coords)
        fetch.append(round(time.perf_counter() - t0, 5))
    return {"block_until_ready_s": block, "fetch_after_block_s": residual,
            "dependent_device_get_s": fetch}


def phase_server(model, params, *, bucket: int, lengths, msa_depth: int,
                 num_recycles: int, seed: int, tol: float) -> dict:
    requests = [make_request(seed + 1 + i, n, msa_depth)
                for i, n in enumerate(lengths)]
    kw = dict(bucket=bucket, msa_depth=msa_depth, num_recycles=num_recycles)
    scan, scan_obs, _ = serve_requests(model, params, requests, **kw)
    step, step_obs, _ = serve_requests(
        model, params, requests, **kw,
        recycle_policy=serve.RecyclePolicy(converge_tol=0.0))
    require(all(r.recycles == num_recycles for r in step), step_obs)

    # the same padded inputs through a direct jit of predict.fold
    @jax.jit
    def direct_fold(params, seq, msa, mask, msa_mask):
        return predict.fold(model, params, seq, msa=msa, mask=mask,
                            msa_mask=msa_mask, num_recycles=num_recycles)

    policy = serve.BucketPolicy((bucket,))
    diffs = {"step_vs_scan": [], "scan_vs_direct": [], "confidence": []}
    for req, a, b in zip(requests, scan, step):
        batch, _ = policy.assemble([req], bucket, 1, msa_depth)
        args = (params, batch["seq"], batch["msa"], batch["mask"],
                batch["msa_mask"])
        ref = direct_fold(*args)
        ref_coords = np.asarray(ref.coords, np.float32)[0, :req.length]
        diffs["step_vs_scan"].append(rel_l2(b.coords, a.coords))
        diffs["scan_vs_direct"].append(rel_l2(a.coords, ref_coords))
        diffs["confidence"].append(float(np.max(np.abs(
            np.asarray(b.confidence, np.float32)
            - np.asarray(a.confidence, np.float32)))))
    timing = time_fold_two_ways(direct_fold, args)
    problems = [f"{k}: {max(v):.4g} > tol {tol}" for k, v in diffs.items()
                if max(v) > tol]
    return {"scan": scan_obs, "step_loop": step_obs, "tol": tol,
            "problems": problems,
            "diff": {k: [round(x, 6) for x in v] for k, v in diffs.items()},
            "coords_max_abs": float(np.max(np.abs(ref_coords))),
            "direct_fold_timing": timing}


# -- trainer ----------------------------------------------------------------

def phase_trainer(model, params, *, seq_len: int, msa_depth: int, steps: int,
                  seed: int) -> dict:
    batch = synthetic_batch(jax.random.PRNGKey(seed + 10), batch=1,
                            seq_len=seq_len, msa_depth=msa_depth,
                            with_coords=True)
    # fit() donates the state: train on a copy, the params stay usable
    state = TrainState.create(
        apply_fn=model.apply, params=jax.tree.map(jnp.copy, params),
        tx=adam(3e-4), rng=jax.random.PRNGKey(seed + 11))
    t0 = time.perf_counter()
    state, history = fit(model, state, itertools.repeat(batch),
                         num_steps=steps, log_every=1)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in history]
    require(len(losses) == steps and all(math.isfinite(x) for x in losses),
            losses)
    require(int(state.step) == steps, int(state.step))
    return {"losses": [round(x, 5) for x in losses], "step": int(state.step),
            "wall_s_including_compile": round(wall, 3)}


# -- four chips -------------------------------------------------------------

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def count_collectives(hlo_text: str) -> dict:
    return {c: hlo_text.count(c + "(") + hlo_text.count(c + "-start(")
            for c in _COLLECTIVES
            if c + "(" in hlo_text or c + "-start(" in hlo_text}


def phase_mesh_serve(model, params, devices, *, bucket: int, msa_depth: int,
                     num_recycles: int, seed: int, tol: float) -> dict:
    """One full-bucket request through the mesh-aware Scheduler (the whole
    slice, 2-D pair-sharded) against the one-chip fold of the same padded
    inputs on device 0: coords and distogram within `tol`."""
    n_dev = len(devices)
    request = make_request(seed + 1, bucket, msa_depth)
    responses, obs, executor = serve_requests(
        model, params, [request], bucket=bucket, msa_depth=msa_depth,
        num_recycles=num_recycles,
        mesh_policy=serve.MeshPolicy({bucket: n_dev}, devices=devices))
    shape = factor_chips(n_dev)
    batch, _ = serve.BucketPolicy((bucket,)).assemble(
        [request], bucket, 1, msa_depth)
    sharded = executor.run(batch, num_recycles, devices=devices,
                           mesh_shape=shape)
    single = executor.run(batch, num_recycles, devices=devices[:1])
    holders = sorted(d.id for d in sharded.distogram.sharding.device_set)
    require(len(holders) == n_dev, holders)
    # the compiled mesh program, from the executor's own cache
    texts = [fn.as_text() for key, fn in executor._cache.items()
             if key[4] == shape and hasattr(fn, "as_text")]
    require(texts, executor.stats()["keys"])
    collectives = count_collectives(texts[0])
    require(collectives, "the mesh fold compiled without a collective")
    diffs = {
        "scheduler_vs_one_chip_coords_l2": rel_l2(
            responses[0].coords, single.coords[0, :request.length]),
        "coords_l2": rel_l2(sharded.coords, single.coords),
        "distogram_l2": rel_l2(sharded.distogram, single.distogram),
    }
    maxes = {"coords_max": rel_err(sharded.coords, single.coords),
             "distogram_max": rel_err(sharded.distogram, single.distogram)}
    problems = [f"{k}: {v:.4g} > tol {tol}" for k, v in diffs.items()
                if v > tol]
    return {"mesh_shape": list(shape), "serve": obs, "problems": problems,
            "devices_holding_distogram": holders,
            "collectives": collectives, "tol": tol,
            "rel_diff": {k: round(v, 6)
                         for k, v in {**diffs, **maxes}.items()}}


def phase_mesh_train(model_kw: dict, params, devices, *, seq_len: int,
                     msa_depth: int, seed: int, tol: float) -> dict:
    """One `make_train_step` under make_mesh(1, 2, 2) with TP+ZeRO params
    and a data-sharded batch — ring attention off and on — against the
    same step on device 0 alone (what __graft_entry__._dryrun_train does
    on virtual devices, here at full width with a reference)."""
    host_params = jax.device_get(params)
    batch = jax.device_get(synthetic_batch(
        jax.random.PRNGKey(seed + 10), batch=1, seq_len=seq_len,
        msa_depth=msa_depth, with_coords=True))

    def fresh_state(model):
        return TrainState.create(apply_fn=model.apply, params=host_params,
                                 tx=adam(3e-4),
                                 rng=jax.random.PRNGKey(seed + 11))

    def build(ring: bool):
        return Alphafold2(predict_coords=True, dtype=jnp.bfloat16,
                          ring_attention=ring, **model_kw)

    model = build(False)
    step = jax.jit(make_train_step(model), donate_argnums=(0,))
    state, metrics = step(jax.device_put(fresh_state(model), devices[0]),
                          jax.device_put(batch, devices[0]))
    ref_loss = float(jax.device_get(metrics["loss"]))
    require(math.isfinite(ref_loss) and int(state.step) == 1, ref_loss)

    mesh = make_mesh(1, *factor_chips(len(devices)), devices=devices)
    out = {"one_chip_loss": round(ref_loss, 5), "tol": tol, "problems": [],
           "mesh": {k: int(v) for k, v in mesh.shape.items()}}
    for ring in (False, True):
        model = build(ring)
        with use_mesh(mesh):
            state = shard_pytree_tp_zero(fresh_state(model), mesh)
            placed = shard_batch(batch, mesh)
            step = jax.jit(make_train_step(model), donate_argnums=(0,))
            compiled = step.lower(state, placed).compile()
            state, metrics = compiled(state, placed)
            loss = float(jax.device_get(metrics["loss"]))
        collectives = count_collectives(compiled.as_text())
        rel = abs(loss - ref_loss) / max(abs(ref_loss), 1e-6)
        name = "ring_on" if ring else "ring_off"
        out[name] = {"loss": round(loss, 5), "rel_diff": round(rel, 6),
                     "collectives": collectives}
        require(math.isfinite(loss) and int(state.step) == 1, name, loss)
        require(collectives, name, "train step compiled without collectives")
        if rel > tol:
            out["problems"].append(f"{name}: loss {rel:.4g} from the "
                                   f"one-chip loss, tol {tol}")
    return out


# -- driver -----------------------------------------------------------------

def run_phase(name: str, fn, verdicts: list, report_on=None, **kw):
    """Run one phase and print its line. A phase fails by raising or by
    returning `problems` (a comparison out of tolerance: its observations
    are still printed); either way the failure lands in the verdict."""
    n0, t0 = len(_COMPILES), time.perf_counter()
    line = {"phase": name}
    try:
        line.update(fn(**kw))
        line["ok"] = not line.get("problems")
    except Exception:
        line.update(ok=False, error=traceback.format_exc()[-3000:])
    line["seconds"] = round(time.perf_counter() - t0, 3)
    line["compile_s"] = [[f, round(s, 3)] for f, s in _COMPILES[n0:]
                         if s >= 0.2]
    line["memory"] = memory_report(report_on)
    emit(line)
    verdicts.append(line["ok"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the path across four chips and what it "
                         "is compared with (needs a four-chip host)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(4 if args.four_chips else 1)

    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: _COMPILES.append(
            (kw.get("fun_name", "?"), secs))
        if event.endswith("backend_compile_duration") else None)
    emit({"phase": "environment", "jax": jax.__version__,
          "jaxlib": importlib.metadata.version("jaxlib"), "libtpu": importlib.metadata.version("libtpu"),
          "device": device_report(),
          "device_coords": [list(getattr(d, "coords", ()))
                            for d in devices],
          "compile_cache_dir": cache_dir,
          "compile_cache_entries_at_start":
              len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
          "seed": args.seed,
          "memory": memory_report(devices)})

    verdicts: list = []
    t0 = time.perf_counter()
    model, params = build_model(args.seed, BUCKET, MSA_DEPTH, **FULL_MODEL)
    emit({"phase": "model", "ok": True, **FULL_MODEL,
          "params": sum(int(x.size) for x in jax.tree.leaves(params)),
          "seconds": round(time.perf_counter() - t0, 3)})

    if args.four_chips:
        four = devices[:4]
        run_phase("mesh_serve", phase_mesh_serve, verdicts, four,
                  model=model, params=params, devices=four, bucket=BUCKET,
                  msa_depth=MSA_DEPTH, num_recycles=NUM_RECYCLES,
                  seed=args.seed, tol=FOLD_TOL)
        run_phase("mesh_train", phase_mesh_train, verdicts, four,
                  model_kw=FULL_MODEL, params=params, devices=four,
                  seq_len=BUCKET, msa_depth=MSA_DEPTH, seed=args.seed,
                  tol=MESH_LOSS_TOL)
        spread = memory_report(four)
        if not all(m["peak_bytes_in_use"] for m in spread):
            emit({"phase": "spread", "ok": False, "memory": spread})
            verdicts.append(False)
    else:
        run_phase("kernels", phase_kernels, verdicts, n=BUCKET, d=64,
                  block=128, heads=FULL_MODEL["heads"], seed=args.seed,
                  tol=KERNEL_TOL)
        run_phase("server", phase_server, verdicts, model=model,
                  params=params, bucket=BUCKET, lengths=LENGTHS,
                  msa_depth=MSA_DEPTH, num_recycles=NUM_RECYCLES,
                  seed=args.seed, tol=FOLD_TOL)
        run_phase("trainer", phase_trainer, verdicts, model=model,
                  params=params, seq_len=BUCKET, msa_depth=MSA_DEPTH,
                  steps=TRAIN_STEPS, seed=args.seed)

    ok = bool(verdicts) and all(verdicts)
    emit({"ok": ok, "device": device_report()})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
