"""The benchmark's weights: every leaf drawn on the device, from the seed, in
one jitted call. The program's `model.init` is never executed (on the chip's
host it took 112-115 s, PR 22); only its shapes are read, by
`jax.eval_shape`.

The distribution keeps the random full-width model out of chaos, which is
what makes a comparison with a reference mean anything. Matrices are one LeCun
init wide (1 / sqrt(fan_in)); the projections that close a residual branch
and the gates' kernels a fifth of one (0.2 / sqrt(fan_in)); vectors 0.05
around the value their initializer gives them (1 for a LayerNorm scale and a
gate's bias, 0 otherwise). Nothing is left at zero, so no comparison is
trivially 0 == 0. PR 22's recipe (`chip_smoke.build_model`: branch closers at
half a LeCun init) held at depth 2; at depth 12 with 3 recycles it does not:
bf16 rounding alone then moved the coordinates by 29% and fp8 by 80%
(reference against reference, 64 residues, my CPU probe, PR 25), against
1-3% and 13-22% with closers at a fifth. The rule goes by a leaf's name only,
so a parameter a later PR adds still gets a sound draw (and shifts the values
of the leaves after it: the weights are the benchmark's, and any sound draw
serves).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_BRANCH_CLOSERS = ("to_out", "proj_out", "gating", "left_gate", "right_gate",
                   "out_gate", "Dense_1", "ff_2")
_MATRICES = ("kernel", "embedding")
_UNIT_VECTORS = ("scale",)
_GATES = ("gating", "left_gate", "right_gate", "out_gate")
_CLOSER_WIDTH = 0.2
_POINT_WEIGHT = 0.541324854612918      # softplus^-1(1), the IPA's own init


def _path_names(path) -> tuple:
    return tuple(str(getattr(k, "key", k)) for k in path)


def _centre_and_width(names: tuple, shape) -> tuple:
    leaf, owner = names[-1], names[-2] if len(names) > 1 else ""
    if leaf in _MATRICES:       # by name: a scanned trunk stacks its vectors
        width = _CLOSER_WIDTH if owner in _BRANCH_CLOSERS else 1.0
        return 0.0, width * shape[-2] ** -0.5
    if leaf in _UNIT_VECTORS or (leaf == "bias" and owner in _GATES):
        return 1.0, 0.05
    if leaf == "point_weights":
        return _POINT_WEIGHT, 0.05
    return 0.0, 0.05


def param_shapes(model, seq_len: int = 16, msa_depth: int = 4):
    """The parameter tree's shapes, traced and never run (they do not
    depend on the input's length)."""
    seq = jnp.zeros((1, seq_len), jnp.int32)
    msa = jnp.zeros((1, msa_depth, seq_len), jnp.int32)
    return jax.eval_shape(
        lambda k: model.init(k, seq, msa=msa, mask=jnp.ones(seq.shape, bool),
                             msa_mask=jnp.ones(msa.shape, bool)),
        jax.random.PRNGKey(0))


def make_params(model, seed: int):
    """The whole tree from `seed`, on the default device: one normal draw of
    every value, cut into the leaves (one small program, whatever the number
    of leaves)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(param_shapes(model))
    plan, offset = [], 0
    for path, s in flat:
        size = math.prod(s.shape)
        plan.append((offset, size, s.shape, s.dtype,
                     *_centre_and_width(_path_names(path), s.shape)))
        offset += size

    @jax.jit
    def draw(key):
        noise = jax.random.normal(key, (offset,), jnp.float32)
        return treedef.unflatten([
            (centre + width * noise[o:o + n].reshape(shape)).astype(dtype)
            for o, n, shape, dtype, centre, width in plan])

    # the seed may exceed 32 signed bits: fold its two halves into the key
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return jax.block_until_ready(draw(key))
