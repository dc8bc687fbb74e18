"""Grouped matmul over a buffer of STATIC rows: y[r] = x[r] @ w[group(r)].

The expert layer of the token decoder (`model/decoder.py`) lays the slots
routed to the experts it holds into one buffer, sorted by expert, every
expert's group padded to a whole number of row tiles. Which expert a tile
belongs to is DATA (`tile_group`, one int a tile, handed to the kernels as a
scalar prefetch); the grid, every block's shape and every trip count are
functions of the shapes alone. So is how many of the leading tiles hold rows
(`live_tiles`, a second scalar prefetch): the grid visits every tile, but a
tile past the live ones fetches no block, computes nothing and writes
nothing back (its index maps repeat the last live tile's block, its body is
skipped: the trick of `megablox`, the grouped matmul that ships with JAX,
which sizes its grid from the group sizes). Device time follows the routing
by the live tiles alone.

Three Pallas kernels on one layout, one `custom_vjp`:

    forward   y   = x  @ w[g]        (rows, k) x (groups, k, n) -> (rows, n)
    backward  dx  = dy @ w[g]^T      the same kernel, w read transposed
              dw[g] = sum over the live tiles of g of x_tile^T @ dy_tile

Rows of `y` and `dx` in tiles at or past `live_tiles` are UNSPECIFIED, and
those rows of `x` and `dy` are never read: `dw` is what the live rows give.
Operands enter the MXU in their own dtype with float32 accumulation. The
weight gradient is float32 and is summed in VMEM over a group's consecutive
tiles (zeroed at the group's first tile, written back when the group
changes), so `tile_group` has to be sorted and every group needs a tile:
the caller pads an empty group to one tile of zero rows. Off the TPU the same
contract runs as an XLA gather of each tile's weights (every tile computed,
which the contract allows), or, behind `ops.attention.use_pallas_attention`
(the CPU tests' door), interpreted.
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


def _last_live(i, live_ref):
    """Tile `i`, or the last live tile for a tile past them: the block a dead
    grid step names, so that the pipeline fetches and writes back nothing."""
    return jnp.minimum(i, live_ref[0] - 1)


def _row_block(i, group_ref, live_ref):
    return _last_live(i, live_ref), 0


def _forward_call(x, w, tile_group, live, transposed: bool, interpret: bool):
    """x @ w[g] (or x @ w[g]^T): one tile of rows a grid step against its
    group's whole matrix, which stays in VMEM while the group lasts; the
    tiles past `live` skipped."""
    rows, k = x.shape
    tiles = tile_group.shape[0]
    tile = rows // tiles
    n = w.shape[1] if transposed else w.shape[2]
    contract = (((1,), (1 if transposed else 0,)), ((), ()))

    def kernel(group_ref, live_ref, x_ref, w_ref, o_ref):
        del group_ref

        @pl.when(pl.program_id(0) < live_ref[0])
        def _():
            o_ref[...] = jax.lax.dot_general(
                x_ref[...], w_ref[0], contract,
                preferred_element_type=jnp.float32).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles,),
            in_specs=[
                pl.BlockSpec((tile, k), _row_block),
                pl.BlockSpec((1,) + w.shape[1:], lambda i, g, live: (
                    g[_last_live(i, live)], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tile, n), _row_block)),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=_params("arbitrary"),
        interpret=interpret, name="grouped_matmul",
    )(tile_group, live, x, w)


def _weight_gradient_call(x, dy, tile_group, live, groups: int,
                          interpret: bool):
    """dw[g] = sum over g's live tiles of x_tile^T @ dy_tile, float32: the
    output block of a group stays in VMEM over its consecutive tiles. Every
    group's block is zeroed at its first tile, live or not, so a group with
    no live tile reads zero."""
    rows, k = x.shape
    n = dy.shape[1]
    tiles = tile_group.shape[0]
    tile = rows // tiles

    def kernel(group_ref, live_ref, x_ref, dy_ref, o_ref):
        i = pl.program_id(0)
        first = jnp.logical_or(
            i == 0, group_ref[i] != group_ref[jnp.maximum(i - 1, 0)])

        @pl.when(first)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(i < live_ref[0])
        def _():
            o_ref[0] += jax.lax.dot_general(
                x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles,),
            in_specs=[pl.BlockSpec((tile, k), _row_block),
                      pl.BlockSpec((tile, n), _row_block)],
            out_specs=pl.BlockSpec((1, k, n), lambda i, g, live: (
                g[i], 0, 0))),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        compiler_params=_params("arbitrary"),
        interpret=interpret, name="grouped_matmul_dw",
    )(tile_group, live, x, dy)


@functools.lru_cache(maxsize=None)
def _grouped_matmul_vjp(interpret: bool):
    @jax.custom_vjp
    def op(x, w, tile_group, live):
        return _forward_call(x, w, tile_group, live, False, interpret)

    def fwd(x, w, tile_group, live):
        return op(x, w, tile_group, live), (x, w, tile_group, live)

    def bwd(res, dy):
        x, w, tile_group, live = res
        dx = _forward_call(dy, w, tile_group, live, True, interpret)
        dw = _weight_gradient_call(x, dy, tile_group, live, w.shape[0],
                                   interpret)
        return dx, dw.astype(w.dtype), None, None

    op.defvjp(fwd, bwd)
    return op


def grouped_matmul(x, w, tile_group, live_tiles=None, *,
                   interpret: bool = False):
    """y[r] = x[r] @ w[tile_group[r // tile]], tile = rows / len(tile_group).

    x (rows, k) and w (groups, k, n) in one dtype; `tile_group` int32, sorted,
    every group present; rows a multiple of len(tile_group), the tile a
    multiple of 8 (of 128 to fill the MXU). `live_tiles`: an int32 scalar,
    how many leading tiles hold rows (clipped to 1..len(tile_group); None:
    every tile); the rows of y past them are unspecified. Differentiable in
    x and w."""
    if x.shape[0] % tile_group.shape[0] or x.dtype != w.dtype:
        raise ValueError("grouped_matmul: rows must be whole tiles and x and "
                         "w of one dtype")
    tiles = tile_group.shape[0]
    live = jnp.clip(jnp.asarray(tiles if live_tiles is None else live_tiles,
                                jnp.int32), 1, tiles).reshape(1)
    return _grouped_matmul_vjp(interpret)(x, w, tile_group, live)
