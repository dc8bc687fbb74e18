"""Sharding rules and in-model constraint helpers.

The model calls `shard_pair` / `shard_msa` / `shard_seq` at block boundaries;
under an active mesh these lower to `with_sharding_constraint`
(GSPMD placement hints), outside a mesh they are no-ops — the same model
code runs single-chip and multi-chip. This replaces the reference's absent
distributed layer (SURVEY.md §2.5, §5.8) without invading model code.

Tensor contracts (axes -> PartitionSpec):
- pair  (b, i, j, d)      -> P(data, i, j, None)
- msa   (b, m, n, d)      -> P(data, None, i, None)
- seq   (b, n, d)         -> P(data, None, None)
- coords(b, n, 3)         -> P(data, None, None)
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from alphafold2_tpu.parallel.mesh import DATA_AXIS, PAIR_I_AXIS, PAIR_J_AXIS

_state = threading.local()


def active_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def shard_map_compat(f, mesh: Mesh, in_specs, out_specs,
                     manual_axes: Optional[frozenset] = None,
                     check: Optional[bool] = None):
    """`jax.shard_map` under the call shape this package uses:
    `manual_axes=None` means fully manual, `check=None` keeps the
    default replication typing (`check_vma`)."""
    kw = {}
    if manual_axes is not None:
        kw["axis_names"] = frozenset(manual_axes)
    if check is not None:
        kw["check_vma"] = check
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], manual_axes: frozenset = frozenset()):
    """Activate a mesh for model-internal sharding constraints.

    Also enters `jax.set_mesh` so closures under jit see the mesh.

    `manual_axes`: axis names the caller has already made manual via
    `shard_map` (e.g. the pipeline's `pipe`/`data` axes). Constraints
    inside the mapped body may only mention the remaining auto axes, so
    `_constraint` drops manual names from its specs — this is how the
    2-D pair sharding stays live INSIDE a pipeline stage (VERDICT r4 #4).
    """
    prev = getattr(_state, "mesh", None)
    prev_manual = getattr(_state, "manual", frozenset())
    _state.mesh = mesh
    _state.manual = frozenset(manual_axes)
    try:
        if mesh is not None and not manual_axes:
            with jax.set_mesh(mesh):
                yield mesh
        else:
            # inside a shard_map body the ambient mesh is already manual;
            # entering jax.set_mesh again is neither needed (constraints
            # name their mesh explicitly) nor allowed mid-trace
            yield mesh
    finally:
        _state.mesh = prev
        _state.manual = prev_manual


def _constraint(x, spec: P):
    mesh = active_mesh()
    if mesh is None:
        return x
    manual = getattr(_state, "manual", frozenset())
    # drop axis names the mesh doesn't have, can't divide the dim, or
    # that are manual in the enclosing shard_map
    cleaned = []
    for dim, axis in zip(x.shape, spec):
        if axis is None or axis not in mesh.axis_names or axis in manual:
            cleaned.append(None)
        elif dim % mesh.shape[axis] != 0:
            cleaned.append(None)
        else:
            cleaned.append(axis)
    if all(a is None for a in cleaned):
        return x
    # pad spec to rank
    cleaned += [None] * (x.ndim - len(cleaned))
    if manual:
        # inside a shard_map body the constraint must name the mesh view
        # whose axis types carry the enclosing Manual axes — that is the
        # trace-time abstract mesh, not the concrete one we stored
        amesh = jax.sharding.get_abstract_mesh()
        if amesh.axis_names:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(amesh, P(*cleaned)))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*cleaned)))


def pair_spec() -> P:
    return P(DATA_AXIS, PAIR_I_AXIS, PAIR_J_AXIS, None)


def msa_spec() -> P:
    return P(DATA_AXIS, None, PAIR_I_AXIS, None)


def seq_spec() -> P:
    return P(DATA_AXIS, None, None)


def shard_pair(x):
    """(b, i, j, d) pair activations: 2-D shard the residue axes."""
    return _constraint(x, pair_spec())


def shard_msa(x):
    """(b, m, n, d) MSA activations: shard the sequence axis."""
    return _constraint(x, msa_spec())


def shard_seq(x):
    """(b, n, d) single-track activations: data-parallel only."""
    return _constraint(x, seq_spec())


def fold_input_specs() -> dict:
    """PartitionSpecs for the serving executor's fold INPUTS (the
    inference-side seam `serve.FoldExecutor` lowers under — training
    goes through `shard_*` constraints instead).

    Token inputs are tiny next to the in-model pair tensor, so seq/mask
    replicate; the MSA tokens shard their sequence axis over `i` (same
    contract as `msa_spec`, one rank lower — no feature dim yet) so the
    msa embedding materializes already distributed:

    - seq      (b, n)    -> P()
    - mask     (b, n)    -> P()
    - msa      (b, m, n) -> P(None, None, i)
    - msa_mask (b, m, n) -> P(None, None, i)
    """
    return {"seq": P(), "mask": P(),
            "msa": P(None, None, PAIR_I_AXIS),
            "msa_mask": P(None, None, PAIR_I_AXIS)}


def fold_input_shardings(mesh: Mesh, batch: dict) -> dict:
    """NamedShardings for one assembled serving batch on `mesh`.
    A spec axis that cannot divide the actual dim (or is missing from
    the mesh) degrades to replication for that tensor — placement is a
    performance hint, never a shape constraint."""
    out = {}
    for name, spec in fold_input_specs().items():
        x = batch.get(name)
        if x is None:
            out[name] = None
            continue
        cleaned = []
        for dim, axis in zip(x.shape, spec):
            if axis is None or axis not in mesh.axis_names \
                    or dim % mesh.shape[axis] != 0:
                cleaned.append(None)
            else:
                cleaned.append(axis)
        out[name] = NamedSharding(mesh, P(*cleaned))
    return out


# ---------------------------------------------------------------------------
# ZeRO-style parameter / optimizer-state sharding
# ---------------------------------------------------------------------------
#
# The reference gestures at this with an empty DeepSpeed stub
# (training_scripts/deepspeed.py, 0 LoC). The GSPMD equivalent needs no
# runtime machinery: give each parameter leaf a sharded placement over the
# data axis and the optimizer state (same-shaped moments) inherits it, so
# per-device optimizer bytes drop ~n_data-fold. XLA re-gathers shards where
# the computation needs full parameters.


def zero_param_specs(params, mesh: Mesh, axis: str = DATA_AXIS):
    """PartitionSpec tree for ZeRO-style sharding: each leaf's largest
    mesh-divisible dimension is sharded over `axis`; leaves with no
    divisible dimension (scalars, odd shapes) stay replicated."""
    n = mesh.shape[axis]

    def spec_for(leaf):
        shape = getattr(leaf, "shape", ())
        best = None
        for d, s in enumerate(shape):
            if s % n == 0 and s >= n and (best is None or s > shape[best]):
                best = d
        if best is None or n <= 1:
            return P()
        spec = [None] * len(shape)
        spec[best] = axis
        return P(*spec)

    return jax.tree.map(spec_for, params)


def shard_pytree_zero(tree, mesh: Mesh, axis: str = DATA_AXIS):
    """Place a pytree (params, opt_state, or a whole TrainState) with
    ZeRO sharding: array leaves get `zero_param_specs` placements. The
    shape-based rule lands optimizer moments on exactly their parameter's
    sharding (same shapes -> same spec). One batched device_put for the
    whole tree, not a transfer per leaf."""
    shardings = jax.tree.map(
        lambda leaf: NamedSharding(mesh, zero_param_specs(leaf, mesh, axis))
        if hasattr(leaf, "shape") else None,
        tree)
    placed = jax.device_put(
        [l for l in jax.tree.leaves(tree) if hasattr(l, "shape")],
        [s for s in jax.tree.leaves(shardings) if s is not None])
    it = iter(placed)
    return jax.tree.map(
        lambda leaf: next(it) if hasattr(leaf, "shape") else leaf, tree)


def tp_param_specs(params, mesh: Mesh, axis: str = PAIR_J_AXIS):
    """Megatron-style tensor-parallel PartitionSpecs for the model's
    param tree, keyed by layer-name suffix (SURVEY §2.5 "tensor/model
    parallel"; the reference has no TP at all).

    Column-parallel (shard the output features): attention to_q/to_kv/
    gating, the first FF projection, triangle left/right projections —
    each head's / hidden unit's compute lands whole on one device.
    Row-parallel (shard the input features): to_out, the second FF
    projection, triangle proj_out — XLA inserts the one all-reduce at the
    block boundary. Under GSPMD these specs are placement policy only;
    outputs are bit-identical to the replicated run (tests/
    test_sharding.py::TestTensorParallel asserts both).
    """
    n = mesh.shape[axis]

    # The FF entries are anchored to the FeedForward module scope
    # ("ff/", "msa_ff/" — primitives.py FeedForward's flax auto-named
    # Dense_0/Dense_1) so unrelated Dense_0/Dense_1 elsewhere in the tree
    # (head MLPs, structure module) stay replicated by intent, not luck.
    COL = ("to_q/kernel", "to_kv/kernel", "gating/kernel",
           "left_proj/kernel", "right_proj/kernel", "ff/Dense_0/kernel")
    ROW = ("to_out/kernel", "proj_out/kernel", "ff/Dense_1/kernel")
    COL_BIAS = ("gating/bias", "left_proj/bias", "right_proj/bias",
                "ff/Dense_0/bias")

    def spec_for(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        shape = getattr(leaf, "shape", ())
        if n > 1 and shape:
            if name.endswith(COL) and shape[-1] % n == 0:
                return P(*([None] * (len(shape) - 1) + [axis]))
            if name.endswith(ROW) and len(shape) >= 2 and \
                    shape[-2] % n == 0:
                return P(*([None] * (len(shape) - 2) + [axis, None]))
            if name.endswith(COL_BIAS) and shape[-1] % n == 0:
                return P(*([None] * (len(shape) - 1) + [axis]))
        return P()

    specs = jax.tree_util.tree_map_with_path(spec_for, params)
    if n > 1:
        matched = sum(s != P() for s in jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P)))
        if matched == 0:
            import warnings
            warnings.warn(
                "tp_param_specs matched no parameters — the suffix table "
                "no longer lines up with the model's module names, so "
                "tensor parallelism silently degrades to replication",
                stacklevel=2)
    return specs


def shard_pytree_tp(params, mesh: Mesh, axis: str = PAIR_J_AXIS):
    """device_put the param tree with `tp_param_specs` placements."""
    specs = tp_param_specs(params, mesh, axis)
    return jax.device_put(
        params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P)))


def shard_pytree_tp_zero(tree, mesh: Mesh, tp_axis: str = PAIR_J_AXIS,
                         zero_axis: str = DATA_AXIS):
    """Combined placement: tensor-parallel specs where they apply (the
    attention/FF/triangle projection kernels and, via shape-matched
    suffixes, their optimizer moments), ZeRO over the data axis for every
    other array leaf. One batched device_put; non-array leaves pass
    through untouched."""
    tp = tp_param_specs(tree, mesh, tp_axis)
    zero = zero_param_specs(tree, mesh, zero_axis)
    merged = jax.tree.map(
        lambda t, z: t if t != P() else z, tp, zero,
        is_leaf=lambda x: isinstance(x, P))
    specs = jax.tree.leaves(merged, is_leaf=lambda x: isinstance(x, P))
    leaves = jax.tree.leaves(tree)
    assert len(leaves) == len(specs)
    arr = [(l, s) for l, s in zip(leaves, specs) if hasattr(l, "shape")]
    placed = jax.device_put([l for l, _ in arr],
                            [NamedSharding(mesh, s) for _, s in arr])
    it = iter(placed)
    return jax.tree.map(
        lambda leaf: next(it) if hasattr(leaf, "shape") else leaf, tree)


def pytree_bytes_per_device(tree) -> int:
    """Max per-device bytes across the addressable shards of `tree`'s
    array leaves (replicated leaves count fully on every device)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        if not hasattr(leaf, "sharding"):
            continue
        shard_shape = leaf.sharding.shard_shape(leaf.shape)
        n = 1
        for s in shard_shape:
            n *= s
        total += n * leaf.dtype.itemsize
    return total
