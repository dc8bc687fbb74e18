"""GPipe-style pipeline parallelism over a `pipe` mesh axis.

The last §2.5 parallelism family (SURVEY.md): the reference gestures at
DeepSpeed pipeline stages through its empty `training_scripts/` stubs;
the TPU-native equivalent is a static skew schedule compiled into one
XLA program — no runtime scheduler, no NCCL send/recv threads. Layers
are grouped into S stages; stage s's params live only on mesh ring
position s (1/S of layer memory per device); activations hop stage to
stage over ICI via `ppermute`.

Schedule (classic GPipe, M microbatches, S stages, T = M + S - 1 ticks):

  tick t: every device runs its stage on the activation it holds —
          device s legitimately holds microbatch m = t - s; bubble
          slots compute on zeros and their results are never read —
          then shifts its output to device s+1; device 0 ingests
          microbatch t+1; device S-1 banks microbatch t - (S-1).

All control flow is a `lax.scan` over ticks with `jnp.where` selects —
static shapes, no data-dependent branching, exactly what Mosaic/XLA
want. `ppermute`'s transpose is `ppermute` with the inverse ring, so
the whole pipeline is differentiable and trains under `jax.grad`.

Helpers:
- `stack_stage_params`: S per-stage param trees -> one tree with a
  leading stage axis (shard it P('pipe') so each device keeps 1/S);
- `microbatch` / `unmicrobatch`: split a batch axis into (M, b/M, ...).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from alphafold2_tpu.parallel.mesh import PIPE_AXIS
from alphafold2_tpu.parallel.sharding import shard_map_compat


def make_pipeline_mesh(pipe: int, data: int = 1, devices=None) -> Mesh:
    """A (pipe, data) mesh. On hardware, lay `pipe` along an ICI ring so
    the per-tick `ppermute` is a single-hop neighbor exchange."""
    import numpy as np
    devices = list(devices if devices is not None else jax.devices())
    if pipe * data != len(devices):
        raise ValueError(f"mesh {pipe}x{data} != #devices {len(devices)}")
    return Mesh(np.asarray(devices).reshape(pipe, data),
                (PIPE_AXIS, "data"))


def stack_stage_params(param_trees: Sequence[Any]):
    """[tree_0, ..., tree_{S-1}] (same structure) -> one tree whose
    leaves have a leading stage axis of size S."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *param_trees)


def microbatch(x: jnp.ndarray, n: int) -> jnp.ndarray:
    b = x.shape[0]
    assert b % n == 0, f"batch {b} not divisible into {n} microbatches"
    return x.reshape(n, b // n, *x.shape[1:])


def unmicrobatch(x: jnp.ndarray) -> jnp.ndarray:
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def _tree_where(pred, a, b):
    return jax.tree.map(
        lambda x, y: jnp.where(pred, x, y), a, b)


def pipeline_apply(
    stage_fn: Callable[[Any, Any], Any],
    stacked_params: Any,
    xs: Any,
    mesh: Mesh,
    *,
    axis_name: str = PIPE_AXIS,
    data_axis: Optional[str] = None,
) -> Any:
    """Run `stage_fn` as an S-stage pipeline over microbatched inputs.

    stage_fn: (stage_params, activation_tree) -> activation_tree, the
      SAME function for every stage (stage identity lives in the params,
      e.g. a scanned-layer slice). Activations must keep one shape/dtype
      across stages (true for Evoformer blocks: (x, m) in -> (x, m) out).
    stacked_params: tree with leading stage axis S == mesh.shape[axis].
    xs: activation tree with leading microbatch axis M (every leaf
      (M, ...)).
    data_axis: optional mesh axis to shard the per-microbatch batch dim
      (leaf axis 1) over — composes pp x dp in one shard_map; without it
      every pipe position computes the full microbatch. Falls back to
      replication for leaves whose batch dim does not tile.
    Returns the output tree (M, ...), sharded like the inputs.
    """
    s_count = mesh.shape[axis_name]
    m_count = jax.tree.leaves(xs)[0].shape[0]
    ticks = m_count + s_count - 1

    param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)

    def x_spec(leaf):
        if data_axis is not None and data_axis in mesh.axis_names and \
                leaf.ndim >= 2 and leaf.shape[1] % mesh.shape[data_axis] == 0:
            return P(None, data_axis)
        return P()

    x_specs = jax.tree.map(x_spec, xs)

    def spmd(params_local, xs):
        # shard_map hands each device its (1, ...) stage slice
        params_local = jax.tree.map(lambda p: p[0], params_local)
        idx = jax.lax.axis_index(axis_name)
        zero = jax.tree.map(lambda x: jnp.zeros_like(x[0]), xs)
        state0 = _tree_where(idx == 0,
                             jax.tree.map(lambda x: x[0], xs), zero)
        # the carry becomes device-varying after the first tick; mark the
        # init values as varying over the pipe axis so scan's carry types
        # line up (shard_map vma typing)
        outputs0 = jax.tree.map(
            lambda x: jax.lax.pcast(jnp.zeros_like(x), (axis_name,),
                                    to="varying"), xs)
        ring = [(s, (s + 1) % s_count) for s in range(s_count)]

        def tick(carry, t):
            state, outputs = carry
            y = stage_fn(params_local, state)
            # bank the finished microbatch (last stage only)
            out_t = t - (s_count - 1)
            safe = jnp.clip(out_t, 0, m_count - 1)
            write = (idx == s_count - 1) & (out_t >= 0)
            outputs = jax.tree.map(
                lambda o, v: o.at[safe].set(
                    jnp.where(write, v, o[safe])), outputs, y)
            # hop to the next stage; stage 0 ingests the next microbatch
            shifted = jax.tree.map(
                lambda v: jax.lax.ppermute(v, axis_name, ring), y)
            nxt = jnp.clip(t + 1, 0, m_count - 1)
            state = _tree_where(
                idx == 0, jax.tree.map(lambda x: x[nxt], xs), shifted)
            return (state, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (state0, outputs0), jnp.arange(ticks))
        # results live on the last ring position; replicate them. psum in
        # f32: XLA:CPU's AllReducePromotion pass crashes cloning bf16
        # all-reduces that reach it from the partial-auto lowering
        # ("Invalid binary instruction opcode copy", observed r05), and
        # a bf16 sum-of-one-nonzero loses nothing by running wider.
        def _replicate(o):
            of = o.astype(jnp.float32) if o.dtype == jnp.bfloat16 else o
            r = jax.lax.psum(
                jnp.where(idx == s_count - 1, of, jnp.zeros_like(of)),
                axis_name)
            return r.astype(o.dtype)

        outputs = jax.tree.map(_replicate, outputs)
        return outputs

    # Manual only over the pipe (and data) axes: any other mesh axes
    # (the pair tensor's `i`/`j`) stay AUTO, so GSPMD keeps honoring
    # in-stage `with_sharding_constraint`s — pipeline parallelism
    # composes with the 2-D pair sharding instead of collapsing it
    # (VERDICT r4 #4).
    manual = {axis_name}
    if data_axis is not None and data_axis in mesh.axis_names:
        manual.add(data_axis)
    fn = shard_map_compat(spmd, mesh, (param_specs, x_specs), x_specs,
                          manual_axes=frozenset(manual))
    return fn(stacked_params, xs)
