"""Grouped matmul over a buffer of STATIC rows: y[r] = x[r] @ w[group(r)].

The expert layer of the token decoder (`model/decoder.py`) lays the slots
routed to the experts it holds into one buffer, sorted by expert, every
expert's group padded to a whole number of row tiles. Which expert a tile
belongs to is DATA (`tile_group`, one int a tile, handed to the kernels as a
scalar prefetch); the grid, every block's shape and every trip count are
functions of the shapes alone, and every tile is computed, the padding too:
device time does not follow the routing. (The grouped matmul that ships with
JAX, `megablox`, sizes its grid from the group sizes.)

Three Pallas kernels on one layout, one `custom_vjp`:

    forward   y   = x  @ w[g]        (rows, k) x (groups, k, n) -> (rows, n)
    backward  dx  = dy @ w[g]^T      the same kernel, w read transposed
              dw[g] = sum over the tiles of g of x_tile^T @ dy_tile

Operands enter the MXU in their own dtype with float32 accumulation. The
weight gradient is float32 and is summed in VMEM over a group's consecutive
tiles (zeroed at the group's first tile, written back when the group
changes), so `tile_group` has to be sorted and every group needs a tile:
the caller pads an empty group to one tile of zero rows. Off the TPU the same
contract runs as an XLA gather of each tile's weights, or, behind
`ops.attention.use_pallas_attention` (the CPU tests' door), interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT_BYTES = 64 * 2**20


def grouped_matmul_reference(x, w, tile_group):
    """The contract in XLA: each tile's weights gathered, one batched matmul.
    x (rows, k); w (groups, k, n); tile_group (rows / tile,) -> (rows, n)."""
    tiles = tile_group.shape[0]
    out = jnp.einsum("trk,tkn->trn", x.reshape(tiles, -1, x.shape[-1]),
                     jnp.take(w, tile_group, axis=0).astype(x.dtype),
                     preferred_element_type=jnp.float32)
    return out.reshape(x.shape[0], w.shape[-1]).astype(x.dtype)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _forward_call(x, w, tile_group, transposed: bool, interpret: bool):
    """x @ w[g] (or x @ w[g]^T): one tile of rows a grid step against its
    group's whole matrix, which stays in VMEM while the group lasts."""
    rows, k = x.shape
    tiles = tile_group.shape[0]
    tile = rows // tiles
    n = w.shape[1] if transposed else w.shape[2]
    contract = (((1,), (1 if transposed else 0,)), ((), ()))

    def kernel(group_ref, x_ref, w_ref, o_ref):
        del group_ref
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], contract,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,),
            in_specs=[
                pl.BlockSpec((tile, k), lambda i, g: (i, 0)),
                pl.BlockSpec((1,) + w.shape[1:], lambda i, g: (g[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tile, n), lambda i, g: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=_params("arbitrary"),
        interpret=interpret, name="grouped_matmul",
    )(tile_group, x, w)


def _weight_gradient_call(x, dy, tile_group, groups: int, interpret: bool):
    """dw[g] = sum over g's tiles of x_tile^T @ dy_tile, float32: the output
    block of a group stays in VMEM over its consecutive tiles."""
    rows, k = x.shape
    n = dy.shape[1]
    tiles = tile_group.shape[0]
    tile = rows // tiles

    def kernel(group_ref, x_ref, dy_ref, o_ref):
        i = pl.program_id(0)
        first = jnp.logical_or(
            i == 0, group_ref[i] != group_ref[jnp.maximum(i - 1, 0)])

        @pl.when(first)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[0] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,),
            in_specs=[
                pl.BlockSpec((tile, k), lambda i, g: (i, 0)),
                pl.BlockSpec((tile, n), lambda i, g: (i, 0)),
            ],
            out_specs=pl.BlockSpec((1, k, n), lambda i, g: (g[i], 0, 0))),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        compiler_params=_params("arbitrary"),
        interpret=interpret, name="grouped_matmul_dw",
    )(tile_group, x, dy)


@functools.lru_cache(maxsize=None)
def _grouped_matmul_vjp(interpret: bool):
    @jax.custom_vjp
    def op(x, w, tile_group):
        return _forward_call(x, w, tile_group, False, interpret)

    def fwd(x, w, tile_group):
        return op(x, w, tile_group), (x, w, tile_group)

    def bwd(res, dy):
        x, w, tile_group = res
        dx = _forward_call(dy, w, tile_group, True, interpret)
        dw = _weight_gradient_call(x, dy, tile_group, w.shape[0], interpret)
        return dx, dw.astype(w.dtype), None

    op.defvjp(fwd, bwd)
    return op


def grouped_matmul(x, w, tile_group, *, interpret: bool = False):
    """y[r] = x[r] @ w[tile_group[r // tile]], tile = rows / len(tile_group).

    x (rows, k) and w (groups, k, n) in one dtype; `tile_group` int32, sorted,
    every group present; rows a multiple of len(tile_group), the tile a
    multiple of 8 (of 128 to fill the MXU). Differentiable in x and w."""
    if x.shape[0] % tile_group.shape[0] or x.dtype != w.dtype:
        raise ValueError("grouped_matmul: rows must be whole tiles and x and "
                         "w of one dtype")
    return _grouped_matmul_vjp(interpret)(x, w, tile_group)
