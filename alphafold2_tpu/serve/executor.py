"""Compiled-executable cache around `predict.fold`.

One compiled executable per (bucket_len, batch_size, msa_depth,
num_recycles, mesh_shape, model_tag, variant) key: because the scheduler feeds
each key exactly one shape signature, the executor compiles ahead-of-
time (`jax.jit(...).lower(args).compile()`) and caches the resulting
`Compiled` object — so LRU-evicting a key actually frees its executable
(a single shared jit fn would pin every shape it ever saw in its
internal cache — no eviction handle), and compilation is a separately
observable phase: `run(..., trace=)` records a `compile` span only when
a key is built fresh and a `fold` span for the device execution, which
is how a request trace attributes XLA time vs accelerator time
(obs/trace.py). On TPU the executables for big buckets are HBM-heavy;
`max_entries` bounds the resident set and `warmup()` pre-pays compiles
before traffic arrives instead of on the first unlucky request.

The key's mesh_shape/model_tag elements close two staleness holes
(ISSUE 7): `model_tag` means a weight rollout (the scheduler re-tags
the executor) can never serve an executable compiled against the
previous weights' identity, and `mesh_shape` keeps single-chip and
mesh-sharded executables for the same bucket coexisting in the LRU.

The `variant` element (ISSUE 9, see MIGRATING) names WHICH compiled
program serves the key: "fold" is the classic opaque executable (all
recycles inside one `lax.scan`), "init" is the embed+first-pass
executable and "step" the single-recycle executable of the
scheduler-owned recycle loop (`run_init`/`run_step`, driven by
`serve.recycle.RecyclePolicy`). init/step keys pin num_recycles to 0 —
the step program is recycle-count-independent by construction, so one
step executable serves every configured recycle depth.

Multi-chip execution (`run(..., devices=, mesh_shape=)` — driven by the
scheduler's `serve.meshpolicy.MeshPolicy`): the fold lowers under
`parallel.mesh.make_mesh` with the model's own `shard_pair/shard_msa`
constraints live (FastFold-style 2-D pair sharding at inference),
params placed once per (device slice, model_tag) via
`parallel.sharding.shard_pytree_tp` and reused across executables,
inputs placed per `parallel.sharding.fold_input_shardings`. A 1-device
slice skips the mesh entirely and just pins args to that device, so
several short folds run concurrently on disjoint chips. `devices=None`
(the default) is byte-for-byte the single-chip behavior this file
always had.

`stats()` exposes hits/misses/evictions; misses == distinct XLA
compilations triggered through this executor, the number the e2e test
pins to the bucket count.
"""

from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict
from typing import Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from alphafold2_tpu.obs import builds
from alphafold2_tpu.obs.trace import NULL_TRACE
from alphafold2_tpu.parallel.mesh import make_mesh
from alphafold2_tpu.parallel.sharding import (fold_input_shardings,
                                              shard_pytree_tp, use_mesh)
from alphafold2_tpu.predict import (FoldResult, FoldStepState, fold,
                                    fold_init, fold_init_rows, fold_step)
from alphafold2_tpu.serve.bucketing import msa_depth_of
from alphafold2_tpu.serve.meshpolicy import MeshShape, factor_chips, \
    mesh_label

# (bucket_len, batch_size, msa_depth, num_recycles, mesh_shape,
#  model_tag, variant) — variant in ("fold", "init", "step",
#  "init_rows"); init_rows (ISSUE 11) is the row-masked admission
#  program of the continuous batcher, warmed alongside the init+step
#  pair so a mid-loop row admission never pays a serving-path compile.
ExecKey = Tuple[int, int, int, int, MeshShape, str, str]

_SINGLE: MeshShape = (1, 1)
_BATCH_INPUTS = ("seq", "mask", "msa", "msa_mask")


def program_tag(cache_key: tuple) -> str:
    """The name the build records give a key's program: variant, bucket x
    batch, MSA depth, recycles (`fold/640x1/m128/r3`), and the mesh where it
    is not one chip (`/2x2`)."""
    bucket_len, batch_size, msa_depth, recycles, shape = cache_key[:5]
    tag = (f"{cache_key[6]}/{bucket_len}x{batch_size}/m{msa_depth}"
           f"/r{recycles}")
    return tag if tuple(shape) == _SINGLE else f"{tag}/{mesh_label(shape)}"


def _stage(name: str, trace):
    """One stage of a build: a child span of `trace`'s `compile` span, or,
    with no trace, a profiler annotation of the same name alone."""
    return trace.span(name) if trace.enabled else TraceAnnotation(name)


def _zero_batch(bucket_len: int, batch_size: int, msa_depth: int) -> dict:
    """An assembled batch of a signature's shapes, all zeros: what a
    signature is warmed (and profiled) on."""
    batch = {"seq": jnp.zeros((batch_size, bucket_len), jnp.int32),
             "mask": jnp.zeros((batch_size, bucket_len), bool),
             "msa": None, "msa_mask": None}
    if msa_depth:
        batch["msa"] = jnp.zeros((batch_size, msa_depth, bucket_len),
                                 jnp.int32)
        batch["msa_mask"] = jnp.zeros((batch_size, msa_depth, bucket_len),
                                      bool)
    return batch


class FoldExecutor:
    """LRU cache of compiled fold executables, keyed by shape signature.

    model_tag: weight identity baked into every ExecKey; reassigning it
        (the scheduler does on a rollout) makes every prior executable
        unreachable by construction — no stale compiled state can serve
        the new tag.
    faults: optional serve.faults.FaultPlan — chaos-injection hook
        (exceptions / latency spikes before the device call, NaN
        mutation after); None (default) costs nothing on the hot path.
    """

    def __init__(self, model, params, max_entries: int = 8, faults=None,
                 model_tag: str = ""):
        assert model.predict_coords, "serving needs predict_coords=True"
        self.model = model
        self.params = params
        self.max_entries = max(1, int(max_entries))
        self.faults = faults
        # executable cache key: ExecKey + concrete device ids (an
        # executable is bound to the devices it lowered for; two
        # disjoint 1-chip slices need two executables)
        self._cache: "OrderedDict[tuple, callable]" = OrderedDict()
        # (device_ids, model_tag) -> (mesh_or_None, placed_params):
        # params are transferred/sharded ONCE per slice and reused by
        # every executable compiled on that slice
        self._placed: dict = {}
        self._lock = threading.Lock()
        self.model_tag = model_tag
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def model_tag(self) -> str:
        return self._model_tag

    @model_tag.setter
    def model_tag(self, tag: str):
        """A rollout re-tags the executor (the scheduler's own
        model_tag setter forwards here): besides re-keying every
        future ExecKey, drop param placements minted under any OTHER
        tag NOW — a slice that sees no post-rollout traffic must not
        keep the rolled-out weights' copies pinned in device memory."""
        self._model_tag = tag
        with self._lock:
            for k in [k for k in self._placed if k[1] != tag]:
                del self._placed[k]

    def rebuild(self) -> "FoldExecutor":
        """Fresh executor over the same (model, params): empty
        executable cache, zeroed counters. The scheduler's watchdog
        swaps a hung executor for this — compiled state owned by a
        wedged device call is not trustworthy, and the zombie watchdog
        thread keeps the OLD instance alive until it dies, so its late
        result can never land in the serving path."""
        return FoldExecutor(self.model, self.params,
                            max_entries=self.max_entries,
                            faults=self.faults,
                            model_tag=self.model_tag)

    def _builder(self, variant: str, num_recycles: int):
        """The jitted callable for one ExecKey variant: "fold" is the
        opaque all-recycles program, "init"/"step" the two halves of
        the scheduler-owned recycle loop (predict.fold_init/fold_step —
        the scan body as its own executable, so step-mode numerics
        match the scan path exactly)."""
        if variant == "fold":
            def run(params, seq, mask, msa, msa_mask) -> FoldResult:
                return fold(self.model, params, seq, msa=msa, mask=mask,
                            msa_mask=msa_mask, num_recycles=num_recycles)

            return jax.jit(run)
        if variant == "init":
            def run_init(params, seq, mask, msa,
                         msa_mask) -> FoldStepState:
                return fold_init(self.model, params, seq, msa=msa,
                                 mask=mask, msa_mask=msa_mask)

            return jax.jit(run_init)
        if variant == "init_rows":
            def run_init_rows(params, seq, mask, msa, msa_mask,
                              row_mask, state) -> FoldStepState:
                return fold_init_rows(self.model, params, seq, row_mask,
                                      state, msa=msa, mask=mask,
                                      msa_mask=msa_mask)

            return jax.jit(run_init_rows)
        if variant != "step":
            raise ValueError(f"unknown executable variant {variant!r}")

        def run_step(params, seq, mask, msa, msa_mask,
                     recyclables) -> FoldStepState:
            return fold_step(self.model, params, seq, recyclables,
                             msa=msa, mask=mask, msa_mask=msa_mask)

        return jax.jit(run_step)

    def _compile(self, cache_key: tuple, num_recycles: int, args,
                 mesh=None, variant: str = "fold", trace=NULL_TRACE):
        """AOT-compile the key's executable OUTSIDE the cache lock (an
        XLA compile can take seconds; holding the lock would stall
        concurrent hit lookups) and insert it. Falls back to the lazily
        compiling jitted callable on JAX versions/paths where AOT
        lowering refuses the argument structure. `mesh` (multi-chip
        slices only) is entered during lowering so the model's sharding
        constraints bake into the executable. The three stages run one
        by one, each a child span of `trace` (`trace`, `lower`,
        `backend_compile`: the persistent cache is read inside the
        last), and the build records book them under the key's
        `program_tag`."""
        jitted = self._builder(variant, num_recycles)
        ctx = use_mesh(mesh) if mesh is not None \
            else contextlib.nullcontext()
        try:
            with ctx, builds.program(program_tag(cache_key)):
                with _stage("trace", trace):
                    traced = jitted.trace(*args)
                with _stage("lower", trace):
                    lowered = traced.lower()
                with _stage("backend_compile", trace):
                    fn = lowered.compile()
        except Exception:
            fn = jitted          # first call will compile lazily
        with self._lock:
            self.misses += 1
            existing = self._cache.get(cache_key)
            if existing is not None:
                # raced with another compiler of the same key: keep the
                # resident one (both are valid; counters stay honest)
                self._cache.move_to_end(cache_key)
                return existing
            self._cache[cache_key] = fn
            while len(self._cache) > self.max_entries:
                self._cache.popitem(last=False)
                self.evictions += 1
        return fn

    def _lookup(self, cache_key: tuple):
        with self._lock:
            fn = self._cache.get(cache_key)
            if fn is not None:
                self.hits += 1
                self._cache.move_to_end(cache_key)
            return fn

    def key_for(self, batch: dict, num_recycles: int,
                mesh_shape: Optional[MeshShape] = None,
                variant: str = "fold") -> ExecKey:
        b, n = batch["seq"].shape
        shape = _SINGLE if mesh_shape is None \
            else tuple(int(x) for x in mesh_shape)
        # init/step programs are recycle-count-independent: pinning the
        # recycles element to 0 means one step executable serves every
        # configured depth instead of minting one per config
        recycles = int(num_recycles) if variant == "fold" else 0
        return (int(n), int(b), msa_depth_of(batch), recycles,
                shape, self.model_tag, variant)

    def _normalize_key(self, key) -> ExecKey:
        """Accept legacy 4-tuple (len, batch, msa_depth, recycles),
        5-tuple (+ mesh_shape) and 6-tuple (+ model_tag) keys alongside
        the full 7-tuple: `warmup()` and `profile()` callers predate the
        mesh/model_tag/variant elements. Any other length is refused,
        not cut: an element this executor does not know named a program
        it does not build."""
        key = tuple(key)
        if not 4 <= len(key) <= 7:
            raise ValueError(
                f"an ExecKey has 4 to 7 elements (bucket_len, batch, "
                f"msa_depth, num_recycles[, mesh_shape[, model_tag[, "
                f"variant]]]), got {len(key)}: {key!r}")
        mesh_shape = tuple(key[4]) if len(key) > 4 else _SINGLE
        model_tag = key[5] if len(key) > 5 else self.model_tag
        variant = key[6] if len(key) > 6 else "fold"
        return key[:4] + (mesh_shape, model_tag, variant)

    # -- device-slice plumbing -------------------------------------------

    def _placed_params(self, devices: Sequence, mesh_shape: MeshShape):
        """(mesh_or_None, params placed on the slice), computed once per
        (device slice, model_tag). Placements for rolled-out tags are
        pruned eagerly by the model_tag setter."""
        dev_ids = tuple(int(d.id) for d in devices)
        cache_k = (dev_ids, self.model_tag)
        with self._lock:
            placed = self._placed.get(cache_k)
        if placed is not None:
            return placed
        if len(devices) == 1:
            mesh = None
            params = jax.device_put(self.params, devices[0])
        else:
            mesh = make_mesh(1, mesh_shape[0], mesh_shape[1],
                             devices=devices)
            params = shard_pytree_tp(self.params, mesh)
        with self._lock:
            existing = self._placed.get(cache_k)
            if existing is not None:
                return existing          # raced: keep the resident copy
            self._placed[cache_k] = (mesh, params)
        return mesh, params

    def _place_inputs(self, batch: dict, mesh, devices: Sequence):
        if mesh is None:
            dev = devices[0]
            return tuple(None if batch[k] is None
                         else jax.device_put(batch[k], dev)
                         for k in _BATCH_INPUTS)
        shardings = fold_input_shardings(mesh, batch)
        return tuple(None if batch[k] is None
                     else jax.device_put(batch[k], shardings[k])
                     for k in _BATCH_INPUTS)

    # -- execution -------------------------------------------------------

    def run(self, batch: dict, num_recycles: int,
            trace=NULL_TRACE, devices: Optional[Sequence] = None,
            mesh_shape: Optional[MeshShape] = None) -> FoldResult:
        """Fold one assembled batch; blocks until device results land so
        the caller's latency measurement is honest. `trace` (a Trace /
        MultiTrace; NULL_TRACE default is zero-cost) gets a `compile`
        span when this signature is built fresh and a `fold` span for
        the execution itself.

        devices: optional device slice (a SliceLease's devices). None —
        the default — is the single-chip path, unchanged. With a slice,
        `mesh_shape` (i, j) factorizes it (default: squarest face); the
        trace additionally gets a `shard` span covering params/input
        placement and the fold span is tagged with the mesh label.
        """
        if devices:
            return self._run_on_slice(batch, num_recycles, trace,
                                      list(devices), mesh_shape)
        key = self.key_for(batch, num_recycles)
        args = (self.params, batch["seq"], batch["mask"], batch["msa"],
                batch["msa_mask"])
        cache_key = key + ((),)
        fn, first = self._lookup(cache_key), None
        if fn is None:
            with trace.span("compile", bucket_len=key[0],
                            batch_size=key[1], msa_depth=key[2],
                            num_recycles=key[3]):
                fn = self._compile(cache_key, key[3], args, trace=trace)
            first = cache_key
        with trace.span("fold", bucket_len=key[0]):
            return self._invoke(fn, args, batch, trace=trace,
                                first_run=first)

    def _run_on_slice(self, batch: dict, num_recycles: int, trace,
                      devices, mesh_shape) -> FoldResult:
        if mesh_shape is None:
            mesh_shape = factor_chips(len(devices))
        mesh_shape = tuple(int(x) for x in mesh_shape)
        label = mesh_label(mesh_shape)
        key = self.key_for(batch, num_recycles, mesh_shape=mesh_shape)
        dev_ids = tuple(int(d.id) for d in devices)
        cache_key = key + (dev_ids,)
        with trace.span("shard", mesh=label, devices=len(devices)):
            mesh, params = self._placed_params(devices, mesh_shape)
            args = (params,) + self._place_inputs(batch, mesh, devices)
        fn, first = self._lookup(cache_key), None
        if fn is None:
            with trace.span("compile", bucket_len=key[0],
                            batch_size=key[1], msa_depth=key[2],
                            num_recycles=key[3], mesh=label):
                fn = self._compile(cache_key, key[3], args, mesh=mesh,
                                   trace=trace)
            first = cache_key
        with trace.span("fold", bucket_len=key[0], mesh=label):
            # the lazy-compile fallback traces on first call, so the
            # mesh context must be live during invocation too
            ctx = use_mesh(mesh) if mesh is not None \
                else contextlib.nullcontext()
            with ctx:
                return self._invoke(fn, args, batch, trace=trace,
                                    first_run=first)

    # -- step-mode execution (scheduler-owned recycle loop) --------------

    def run_init(self, batch: dict, trace=NULL_TRACE,
                 devices: Optional[Sequence] = None,
                 mesh_shape: Optional[MeshShape] = None) -> FoldStepState:
        """The embed+first-pass executable: recycle iteration 0 of the
        scheduler-owned loop (`serve.recycle.RecyclePolicy`). Blocks
        until the device result lands. Spans: `compile` when the
        init-variant signature is built fresh, `fold` for the execution
        itself (the obs checker's accelerator-time rule keys off a
        non-zero fold span, and this IS the fold's first pass)."""
        return self._run_stepmode("init", batch, (), trace, devices,
                                  mesh_shape, span="fold", attrs={})

    def run_init_rows(self, batch: dict, state: FoldStepState,
                      row_mask, trace=NULL_TRACE,
                      devices: Optional[Sequence] = None,
                      mesh_shape: Optional[MeshShape] = None,
                      span_attrs: Optional[dict] = None) -> FoldStepState:
        """Row-masked admission init (continuous batching, ISSUE 11):
        rows where `row_mask` is True restart at iteration 0 from the
        batch tensors (which the scheduler just rewrote with newly
        admitted requests), rows where it is False pass the carried
        `state` through untouched — survivors keep stepping, nothing
        recompiles mid-loop because this variant was warmed with the
        init+step pair. Span: `admit` (the admission cost is its own
        waterfall stage — it is neither a fold nor a recycle).
        `span_attrs` merges extra attributes into the admit span (the
        cross-bucket scheduler tags the admitted rows' native buckets,
        ISSUE 13)."""
        mask_arr = jnp.asarray(row_mask, bool)
        attrs = {"rows": int(mask_arr.sum())}
        if span_attrs:
            attrs.update(span_attrs)
        return self._run_stepmode(
            "init_rows", batch, (mask_arr, state), trace, devices,
            mesh_shape, span="admit", attrs=attrs)

    def run_step(self, batch: dict, state: FoldStepState,
                 recycle_index: int, trace=NULL_TRACE,
                 devices: Optional[Sequence] = None,
                 mesh_shape: Optional[MeshShape] = None,
                 span_attrs: Optional[dict] = None) -> FoldStepState:
        """One recycle iteration: feeds `state.recyclables` (from
        run_init or a previous run_step on the same slice) through the
        step executable. Span: `recycle`, tagged with the iteration
        index (and mesh label on a slice). `span_attrs` merges extra
        attributes into the recycle span (the continuous scheduler tags
        per-step row occupancy for the obs_report occupancy line)."""
        attrs = {"recycle": int(recycle_index)}
        if span_attrs:
            attrs.update(span_attrs)
        return self._run_stepmode(
            "step", batch, (state.recyclables,), trace, devices,
            mesh_shape, span="recycle", attrs=attrs)

    def _run_stepmode(self, variant: str, batch: dict, extra_args,
                      trace, devices, mesh_shape, span: str,
                      attrs: dict):
        """Shared lookup/compile/execute path for the init/step
        variants, covering both the single-chip and device-slice
        cases. `extra_args` (the step's carried recyclables) ride after
        the placed batch inputs; they are prior outputs of this very
        slice, so they are already resident where the executable
        expects them."""
        if devices:
            devices = list(devices)
            if mesh_shape is None:
                mesh_shape = factor_chips(len(devices))
            mesh_shape = tuple(int(x) for x in mesh_shape)
            label = mesh_label(mesh_shape)
            key = self.key_for(batch, 0, mesh_shape=mesh_shape,
                               variant=variant)
            dev_ids = tuple(int(d.id) for d in devices)
            cache_key = key + (dev_ids,)
            # the batch inputs are identical across a step loop's
            # iterations, so their device placement is cached ON the
            # batch dict (keyed by slice identity): one host-to-slice
            # transfer + one `shard` span per loop, not one per step.
            # A repack mints a fresh batch dict (repack_batch copies
            # only the canonical keys), which drops the stale cache.
            place_key = ("_placed", dev_ids)
            mesh, params = self._placed_params(devices, mesh_shape)
            placed = batch.get(place_key)
            if placed is None:
                with trace.span("shard", mesh=label,
                                devices=len(devices)):
                    placed = self._place_inputs(batch, mesh, devices)
                batch[place_key] = placed
            args = (params,) + placed + tuple(extra_args)
            attrs = dict(attrs, mesh=label)
        else:
            mesh = None
            key = self.key_for(batch, 0, variant=variant)
            cache_key = key + ((),)
            args = (self.params, batch["seq"], batch["mask"],
                    batch["msa"], batch["msa_mask"]) + tuple(extra_args)
        fn, first = self._lookup(cache_key), None
        if fn is None:
            with trace.span("compile", bucket_len=key[0],
                            batch_size=key[1], msa_depth=key[2],
                            variant=variant,
                            **({"mesh": attrs["mesh"]} if "mesh" in attrs
                               else {})):
                fn = self._compile(cache_key, 0, args, mesh=mesh,
                                   variant=variant, trace=trace)
            first = cache_key
        with trace.span(span, bucket_len=key[0], **attrs):
            ctx = use_mesh(mesh) if mesh is not None \
                else contextlib.nullcontext()
            with ctx:
                return self._invoke(fn, args, batch, variant=variant,
                                    recycle=attrs.get("recycle"),
                                    trace=trace, first_run=first)

    def _invoke(self, fn, args, batch, variant: str = "fold",
                recycle=None, trace=NULL_TRACE,
                first_run=None) -> FoldResult:
        """One execution, inside the caller's `fold` (or `recycle` /
        `admit`) span, which it splits in two for `trace`: `dispatch`,
        the host's share (inputs to the device, the call enqueued), and
        `device_wait`, blocked until the results have landed.
        `first_run`: the cache key of a program built for this call, whose
        first execution the build records book."""
        if self.faults is not None:
            # injected exceptions/latency fire BEFORE the device
            # call (a chaos fault must not waste real accelerator
            # time); NaN-poison rows are patched in after. The fault
            # hook is step-aware (ISSUE 14): the variant + recycle
            # index let a chaos plan hit a SPECIFIC recycle depth
            self.faults.on_executor_run(batch, variant=variant,
                                        recycle=recycle)
        with builds.first_run(program_tag(first_run)) \
                if first_run is not None else contextlib.nullcontext():
            with trace.span("dispatch"):
                result = fn(*args)
            with trace.span("device_wait"):
                result = jax.block_until_ready(result)
        if self.faults is not None:
            result = self.faults.mutate_result(batch, result)
        return result

    def warmup(self, keys: Iterable,
               devices: Optional[Sequence] = None,
               mesh_shape: Optional[MeshShape] = None,
               step_mode: bool = False,
               continuous: bool = False) -> int:
        """Compile (and discard) each key's signature with a zero batch.
        Keys may be legacy 4-tuples (len, batch, msa_depth, recycles) or
        full ExecKeys; `devices`/`mesh_shape` warm the slice-bound
        executable the scheduler will actually run (the mesh-aware
        scheduler warms per bucket with the bucket's own lease).
        `step_mode` warms the init+step executable PAIR instead of the
        opaque fold — what a scheduler driving the recycle loop
        (recycle_policy set) will actually execute. `continuous`
        (step_mode only) additionally warms the row-masked `init_rows`
        admission program, so a continuous batcher's first mid-loop row
        admission never triggers a mid-serving compile (ISSUE 11).
        Returns the number of fresh compiles. Each build's stages and its
        first run are in the build records (`obs.builds`), under the
        key's `program_tag`."""
        fresh = 0
        for key in keys:
            bucket_len, batch_size, msa_depth, num_recycles = \
                self._normalize_key(key)[:4]
            before = self.misses
            batch = _zero_batch(bucket_len, batch_size, msa_depth)
            if step_mode:
                state = self.run_init(batch, devices=devices,
                                      mesh_shape=mesh_shape)
                if continuous:
                    # shape-only warm: the mask values never change
                    # the compiled program, only which rows reinit
                    mask0 = jnp.zeros((batch_size,), bool)
                    state = self.run_init_rows(
                        batch, state, mask0, devices=devices,
                        mesh_shape=mesh_shape)
                self.run_step(batch, state, 0, devices=devices,
                              mesh_shape=mesh_shape)
            else:
                self.run(batch, num_recycles, devices=devices,
                         mesh_shape=mesh_shape)
            fresh += self.misses - before
        return fresh

    def profile(self, key, repeats: int = 3) -> dict:
        """Where the executable of `key` spends the chip: device seconds
        per execution by kernel (`obs.device.profile`; its result). `key`
        as `warmup` takes it, legacy 4-tuple (len, batch, msa_depth,
        recycles) or full ExecKey; the opaque fold on the default device,
        on a zero batch as `warmup` uses. Compiles the executable if it
        is not resident. Needs a device plane: raises on the CPU."""
        from alphafold2_tpu.obs import device

        key = self._normalize_key(key)
        bucket_len, batch_size, msa_depth, num_recycles = key[:4]
        batch = _zero_batch(bucket_len, batch_size, msa_depth)
        args = (self.params, batch["seq"], batch["mask"], batch["msa"],
                batch["msa_mask"])
        cache_key = self.key_for(batch, num_recycles) + ((),)
        fn = self._lookup(cache_key) \
            or self._compile(cache_key, num_recycles, args)
        if not hasattr(fn, "as_text"):
            raise RuntimeError(
                "FoldExecutor.profile: no compiled executable for "
                f"{key[:4]} (ahead-of-time lowering was refused)")
        return device.profile(fn, lambda: self._invoke(fn, args, batch),
                              repeats=repeats)

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "resident": len(self._cache),
                    "max_entries": self.max_entries,
                    "keys": [k[:-1] for k in self._cache.keys()],
                    "placed_param_slices": len(self._placed)}
