"""Inference API: fold sequences with recycling and confidence.

The reference leaves the recycling loop to user code (its tests do two
manual passes, test_attention.py:344-385) and has no inference entry
point at all. `fold()` packages it: N recycling iterations under one jit
(`lax.scan` over the recycle axis — static, compile-once), returning
coordinates, per-residue confidence, and the trunk outputs.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from alphafold2_tpu.model.alphafold2 import Recyclables


class FoldResult(NamedTuple):
    coords: jnp.ndarray          # (b, n, 3)
    confidence: jnp.ndarray      # (b, n) in [0, 1]
    distogram: jnp.ndarray       # (b, n, n, buckets)
    recyclables: Recyclables


class FoldStepState(NamedTuple):
    """One recycle iteration's full output — the carry of the
    scheduler-owned step loop (serve/recycle.py). Identical fields to
    FoldResult on purpose: after the LAST step the state IS the fold
    result, and `recyclables` is the only part the next step consumes.
    `confidence` is already sigmoided to [0, 1] (the same
    `sigmoid(raw[..., 0])` fold() applies once at the end — applying it
    per step changes nothing for the final state and gives every
    intermediate state a client-meaningful confidence for progressive
    results)."""

    coords: jnp.ndarray          # (b, n, 3)
    confidence: jnp.ndarray      # (b, n) in [0, 1]
    distogram: jnp.ndarray       # (b, n, n, buckets)
    recyclables: Recyclables


# single source of truth for the recycling default: fold_and_write's
# cache keys hash the effective value, so a drifting duplicate literal
# would silently serve results computed under one default as another
DEFAULT_NUM_RECYCLES = 3


def fold(
    model,
    params,
    seq: jnp.ndarray,
    msa: Optional[jnp.ndarray] = None,
    mask: Optional[jnp.ndarray] = None,
    msa_mask: Optional[jnp.ndarray] = None,
    num_recycles: int = DEFAULT_NUM_RECYCLES,
    **extra,
) -> FoldResult:
    """Run the model with `num_recycles` recycling iterations.

    `model` must be constructed with predict_coords=True. Jit-safe: wrap
    in jax.jit(partial(fold, model), static_argnames='num_recycles') or
    call under jit via a closure.
    """
    assert model.predict_coords, "fold() needs predict_coords=True"

    def one_pass(recyclables):
        # delegates to the SAME _one_pass the step-mode entry points
        # (fold_init/fold_step) trace, so the step-loop == scan
        # exactness contract cannot drift between two call sites
        return _one_pass(model, params, seq, msa, mask, msa_mask,
                         recyclables, extra)

    # first pass has no recyclables (params cover both traces via the
    # init-time branch coverage)
    coords, ret = one_pass(None)

    if num_recycles > 0:
        # carry the latest outputs instead of stacking per-iteration ys:
        # keeps one copy of the O(n^2) distogram live, not num_recycles
        # the scan body is no flax module: `recycle` is the name its
        # instructions carry for the profiler's reader (obs/device.py)
        @jax.named_scope("recycle")
        def body(carry, _):
            recyclables, *_ = carry
            coords, ret = one_pass(recyclables)
            return (ret.recyclables, coords, ret.distance,
                    ret.confidence), None

        (recyclables, coords, distance, confidence), _ = jax.lax.scan(
            body, (ret.recyclables, coords, ret.distance, ret.confidence),
            None, length=num_recycles)
    else:
        distance = ret.distance
        confidence = ret.confidence
        recyclables = ret.recyclables

    conf = jax.nn.sigmoid(confidence[..., 0].astype(jnp.float32))
    return FoldResult(coords, conf, distance, recyclables)


def _one_pass(model, params, seq, msa, mask, msa_mask, recyclables,
              extra):
    """One trunk+structure pass — THE call fold()'s closure and the
    step-mode entry points (fold_init/fold_step) all trace, so the
    step-loop == scan exactness contract cannot drift between call
    sites. The deterministic 'performer' rng: under the trunk scan its
    split_rngs give each layer an INDEPENDENT FAVOR+ projection at
    inference (per-layer estimator errors average out instead of
    adding coherently); unused collections are harmless for models
    without Performer layers."""
    return model.apply(
        params, seq, msa=msa, mask=mask, msa_mask=msa_mask,
        recyclables=recyclables, return_aux_logits=True,
        return_recyclables=True,
        rngs={"performer": jax.random.PRNGKey(0)}, **extra)


def _step_state(coords, ret) -> FoldStepState:
    conf = jax.nn.sigmoid(ret.confidence[..., 0].astype(jnp.float32))
    return FoldStepState(coords, conf, ret.distance, ret.recyclables)


def fold_init(model, params, seq, msa=None, mask=None, msa_mask=None,
              **extra) -> FoldStepState:
    """The embed+first-pass executable of step-mode folding: exactly
    fold(..., num_recycles=0), but returning a FoldStepState whose
    `recyclables` seed `fold_step`. Jit-safe the same way fold() is.

    Step-mode contract (tests/test_recycle.py pins it): for any R,
        state = fold_init(...); repeat R times: state = fold_step(state)
    produces coords/confidence/distogram numerically identical to
    `fold(..., num_recycles=R)` — the scan body and the step body are
    one function (`_one_pass`), so splitting the loop moves WHO owns
    the iteration (the scheduler instead of XLA), never what it
    computes. The identity holds between COMPILED programs (jit both
    sides — the serving executor always does); eager op-by-op
    execution rounds differently than the scan body's compiled HLO and
    is not covered."""
    assert model.predict_coords, "fold_init() needs predict_coords=True"
    coords, ret = _one_pass(model, params, seq, msa, mask, msa_mask,
                            None, extra)
    return _step_state(coords, ret)


def fold_init_rows(model, params, seq, row_mask, state: FoldStepState,
                   msa=None, mask=None, msa_mask=None,
                   **extra) -> FoldStepState:
    """Row-masked init: the continuous-batching admission program
    (ISSUE 11). Rows where `row_mask` is True are (re)initialized from
    the CURRENT batch tensors — exactly `fold_init`'s embed+first pass,
    recyclables=None — while rows where it is False pass the carried
    `state` through untouched, so survivor rows keep stepping from
    their own recycle depth while freed rows restart at iteration 0
    with a newly admitted request's content.

    The pass computes the init over the WHOLE batch (one fixed-shape
    executable, no data-dependent shapes) and selects per row; rows are
    independent through the model (regression-pinned by the repack
    tests), so an admitted row's init is byte-identical to folding that
    request alone at the same batch signature, and a survivor row's
    carried state is byte-identical through the `where` pass-through.

    row_mask: (b,) bool — True = initialize this row fresh.
    state: the carried FoldStepState whose non-admitted rows survive.
    """
    fresh = fold_init(model, params, seq, msa=msa, mask=mask,
                      msa_mask=msa_mask, **extra)

    def sel(new, old):
        m = jnp.reshape(row_mask, row_mask.shape
                        + (1,) * (new.ndim - row_mask.ndim))
        return jnp.where(m, new, old)

    return jax.tree_util.tree_map(sel, fresh, state)


def snapshot_step_state(state):
    """Host-side snapshot of a step-loop carry (ISSUE 14: the carry-
    checkpointing half of the scheduler's step-loop fault domain).
    Device leaves are fetched to numpy WITH their sharding recorded, so
    `restore_step_state` can re-upload a mesh-sharded carry back onto
    the exact slice it left; non-array leaves (custom test-executor
    states are opaque objects) are kept by reference — they are
    host-side already and step stubs mint fresh state objects per
    iteration, so the reference stays immutable. The snapshot survives
    an executor rebuild: nothing in it references the executor or its
    compiled programs."""
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(state)
    snap = []
    for leaf in leaves:
        if isinstance(leaf, jax.Array):
            snap.append(("dev", np.asarray(leaf),
                         getattr(leaf, "sharding", None)))
        else:
            snap.append(("ref", leaf, None))
    return treedef, snap


def restore_step_state(snapshot):
    """Re-upload a `snapshot_step_state` checkpoint: device leaves go
    back through their recorded sharding (falling back to a fresh
    default-device `jnp.array` when the sharding no longer applies —
    e.g. after an executor rebuild changed device objects), reference
    leaves pass through untouched. The restored carry is byte-equal to
    the snapshotted one — a resumed step loop continues exactly where
    the checkpoint left it."""
    treedef, snap = snapshot
    leaves = []
    for kind, val, sharding in snap:
        if kind != "dev":
            leaves.append(val)
            continue
        arr = None
        if sharding is not None:
            try:
                arr = jax.device_put(val, sharding)
            except Exception:
                arr = None       # stale sharding: default placement
        if arr is None:
            arr = jnp.array(val)
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def fold_step(model, params, seq, recyclables: Recyclables, msa=None,
              mask=None, msa_mask=None, **extra) -> FoldStepState:
    """One recycle iteration: the `lax.scan` body of fold() as its own
    executable. Feed it the previous state's `recyclables` (from
    fold_init or an earlier fold_step)."""
    assert model.predict_coords, "fold_step() needs predict_coords=True"
    coords, ret = _one_pass(model, params, seq, msa, mask, msa_mask,
                            recyclables, extra)
    return _step_state(coords, ret)


def fold_and_write(model, params, seq, out_path: str, cache=None,
                   model_tag: str = "", tracer=None, **kwargs) -> list:
    """fold() + PDB output of the CA trace (data/pdb_io.coords2pdb).

    Folds the whole (b, n) batch in ONE forward pass and writes one PDB
    per batch element: `out_path` for a batch of 1, `<stem>_k<ext>` for
    element k otherwise. Returns the list of written paths (length b).
    Pass `mask` to trim per-element padding from the written trace.

    cache: optional `alphafold2_tpu.cache.FoldCache` — the same
    content-addressed memoization the serving scheduler uses, so
    offline batch scripts re-running overlapping inputs skip the fold.
    Keys cover each element's unpadded (seq, msa, msa_mask,
    num_recycles) plus `model_tag` (identify your weights whenever the
    cache outlives this process) and any scalar extra model kwargs; a
    call with array-valued or un-hashable extras (e.g. batched
    per-element conditioning, which can't be attributed to one
    element's key) folds uncached rather than risk serving another
    call's result. With no extras and a
    trivial msa_mask the key matches the serving scheduler's
    (msa_depth=None config), so one shared FoldCache deduplicates
    across offline and served folds of the same content. The
    forward pass is skipped only when EVERY element hits (partial
    batches would mint a new compiled shape); partial hits still fold
    once but refresh the store. Off by default.

    tracer: optional `alphafold2_tpu.obs.Tracer` — the call gets one
    request-scoped trace (cache_lookup / fold / write spans, cache
    hit/miss events, source "cache" when the forward pass was skipped)
    in the same JSONL schema the serving scheduler emits, so offline
    batch folds land in the same `tools/obs_report.py` waterfall.
    """
    from alphafold2_tpu.obs.trace import NULL_TRACER

    trace = (tracer or NULL_TRACER).start_trace(out_path)
    try:
        return _fold_and_write_traced(model, params, seq, out_path, cache,
                                      model_tag, trace, **kwargs)
    except BaseException as exc:
        # every trace reaches exactly one terminal state, failures too
        trace.finish("error", error=repr(exc))
        raise


def _fold_and_write_traced(model, params, seq, out_path, cache,
                           model_tag, trace, **kwargs) -> list:
    import os

    import numpy as np

    from alphafold2_tpu.data.pdb_io import coords2pdb

    seq_np = np.asarray(seq)
    mask = kwargs.get("mask")
    mask_np = None if mask is None else np.asarray(mask)
    msa = kwargs.get("msa")
    msa_np = None if msa is None else np.asarray(msa)
    msa_mask = kwargs.get("msa_mask")
    msa_mask_np = None if msa_mask is None else np.asarray(msa_mask)
    b = seq_np.shape[0]

    def trim(k):
        return (slice(None) if mask_np is None
                else np.flatnonzero(mask_np[k]))

    keys = cached = None
    if cache is not None:
        from alphafold2_tpu.cache import fold_key
        num_recycles = kwargs.get("num_recycles", DEFAULT_NUM_RECYCLES)
        # everything fold() forwards beyond the keyed inputs must reach
        # the key too — two calls differing only in an extra conditioning
        # kwarg are different computations. Only SCALAR extras are
        # keyable: an array-valued extra (e.g. batched per-element
        # conditioning like embedds) can't be attributed to one element
        # of the per-element key, so it disables caching for the call
        # rather than risk serving another element's/call's result.
        # The no-extras case keys exactly like the serving scheduler
        # (extras=None), so offline and served folds of the same content
        # share entries when msa_depth semantics match (scheduler
        # msa_depth=None). An all-True msa_mask is content-equivalent
        # to no mask (the scheduler's own construction) and doesn't
        # split the key.
        extra = tuple(sorted(
            (k, v) for k, v in kwargs.items()
            if k not in ("msa", "mask", "msa_mask", "num_recycles")))
        scalar_ok = all(
            v is None or isinstance(v, (str, bytes, bool, int, float,
                                        np.integer, np.floating))
            for _, v in extra)
        if scalar_ok:
            try:
                with trace.span("cache_lookup", batch=b):
                    keys, cached = [], []
                    for k in range(b):
                        idx = trim(k)
                        mm = (None if msa_mask_np is None
                              else msa_mask_np[k][:, idx])
                        if mm is not None and mm.all():
                            mm = None
                        extras = None if not extra and mm is None \
                            else (extra, mm)
                        keys.append(fold_key(
                            seq_np[k][idx],
                            None if msa_np is None else msa_np[k][:, idx],
                            num_recycles=num_recycles,
                            model_tag=model_tag, extras=extras))
                        cached.append(cache.get(keys[k], trace=trace))
            except TypeError:
                # un-content-hashable extra kwarg: fold uncached rather
                # than risk serving another call's result
                keys = cached = None

    coords_np = confidence_np = None
    all_hit = cached is not None and all(c is not None for c in cached)
    if not all_hit:
        with trace.span("fold", batch=b):
            result = fold(model, params, seq, **kwargs)
            coords_np = np.asarray(result.coords)
            confidence_np = np.asarray(result.confidence)

    stem, ext = os.path.splitext(out_path)
    ext = ext or ".pdb"
    paths = []
    with trace.span("write", batch=b):
        for k in range(b):
            path = out_path if b == 1 else f"{stem}_{k}{ext}"
            idx = trim(k)
            if cached is not None and cached[k] is not None:
                coords_k = cached[k].coords
            else:
                coords_k = coords_np[k][idx]
                if keys is not None:
                    cache.put(keys[k], coords_k, confidence_np[k][idx])
            paths.append(coords2pdb(seq_np[k][idx], coords_k, name=path))
    trace.finish("ok", source="cache" if all_hit else "fold")
    return paths
