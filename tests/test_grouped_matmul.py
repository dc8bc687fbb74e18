"""The grouped matmul of the decoder's expert layer (`ops/grouped_matmul.py`:
a static buffer, the tile -> group map as data) against XLA's gather of each
tile's weights, forward and gradient, interpreted on the CPU; with a count of
live tiles, under the TPU interpreter, whose unwritten memory reads NaN."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from alphafold2_tpu.ops.grouped_matmul import (grouped_matmul,
                                               grouped_matmul_reference)

TILE, K, N, GROUPS = 16, 32, 48, 4
# group 0 takes three tiles, group 2 one of padding only, the last the rest
TILE_GROUP = jnp.asarray([0, 0, 0, 1, 2, 3, 3], jnp.int32)


def _operands(dtype):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    rows = TILE * TILE_GROUP.shape[0]
    x = jax.random.normal(keys[0], (rows, K)).astype(dtype)
    w = (jax.random.normal(keys[1], (GROUPS, K, N)) * K ** -0.5).astype(dtype)
    dy = jax.random.normal(keys[2], (rows, N)).astype(dtype)
    return x, w, dy


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_grouped_matmul_matches_the_gathered_weights(dtype, tol):
    x, w, dy = _operands(dtype)

    f32 = lambda t: t.astype(jnp.float32)

    def run(fn, x, w, dy):
        out, vjp = jax.vjp(lambda x, w: fn(x, w, TILE_GROUP), x, w)
        return (out,) + vjp(dy)
    kernel = jax.jit(lambda: run(
        lambda *a: grouped_matmul(*a, interpret=True), x, w, dy))()
    want = run(grouped_matmul_reference, f32(x), f32(w), f32(dy))
    for name, got, ref in zip(("y", "dx", "dw"), kernel, want):
        assert got.shape == ref.shape and got.dtype == dtype, name
        scale = float(jnp.abs(ref).max())
        assert float(jnp.abs(f32(got) - f32(ref)).max()) <= tol * scale, name


def test_a_row_meets_its_own_groups_weights_only():
    x, w, _ = _operands(jnp.float32)
    out = grouped_matmul(x, w, TILE_GROUP, interpret=True)
    for tile, group in enumerate(np.asarray(TILE_GROUP)):
        rows = slice(tile * TILE, (tile + 1) * TILE)
        np.testing.assert_allclose(out[rows], x[rows] @ w[group], rtol=1e-5,
                                   atol=1e-5)


def test_rows_must_be_whole_tiles_of_one_dtype():
    x, w, _ = _operands(jnp.float32)
    with pytest.raises(ValueError, match="whole tiles"):
        grouped_matmul(x[:-1], w, TILE_GROUP, interpret=True)
    with pytest.raises(ValueError, match="one dtype"):
        grouped_matmul(x.astype(jnp.bfloat16), w, TILE_GROUP, interpret=True)


@pytest.mark.parametrize("live", [1, 4, 7, None],
                         ids=["one_tile", "four_tiles", "every_tile", "none"])
def test_only_the_live_tiles_are_read_and_written(live):
    """Rows of x and dy past the live tiles are NaN: the live rows of y and
    dx, and all of dw, are what the reference gives on those rows zeroed
    (groups 2 and 3 have no live tile at four: their dw is zero). `None` is
    every tile, the three-argument call."""
    x, w, dy = _operands(jnp.float32)
    rows = x.shape[0]
    cut = rows if live is None else live * TILE
    dead = (jnp.arange(rows) >= cut)[:, None]
    poison = lambda t: jnp.where(dead, jnp.nan, t)
    clean = lambda t: jnp.where(dead, 0.0, t)

    args = () if live is None else (jnp.int32(live),)
    y, vjp = jax.vjp(lambda x, w: grouped_matmul(
        x, w, TILE_GROUP, *args, interpret=pltpu.InterpretParams()),
        poison(x), w)
    dx, dw = vjp(poison(dy))
    want_y, want_vjp = jax.vjp(lambda x, w: grouped_matmul_reference(
        x, w, TILE_GROUP), clean(x), w)
    want_dx, want_dw = want_vjp(clean(dy))
    for name, got, ref in (("y", y[:cut], want_y[:cut]),
                           ("dx", dx[:cut], want_dx[:cut]),
                           ("dw", dw, want_dw)):
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5,
                                   err_msg=name)
    if live == 4:
        assert not np.any(want_dw[2:]) and not np.any(dw[2:])
