"""The expert layer's row moves between the tokens and the experts' buffer,
over the rows the routing filled alone.

The token decoder's expert layer (`model/decoder.py`) lays the slots routed
to its held experts into one buffer of STATIC rows, sorted by expert and,
inside an expert's group, by token; each group padded to whole row tiles,
the groups one after another from row 0, so the filled tiles are a prefix
(`live`, the grouped matmul's count). Two Pallas kernels move rows between
the tokens (tokens, d) and that buffer (rows, d), each the other's
transpose, on the static-grid idiom of `ops/grouped_matmul.py`: the grid is
a function of shapes alone, the counts are scalar prefetches, a grid step
past the filled ones fetches nothing, computes nothing and writes nothing
back (its index maps repeat the last live block), and no trip count or
extent depends on data (a loop runs to its static bound, its body skipped
past the count).

    dispatch  (row space, a grid step a row tile of the buffer)
        buf[r] = table[token_of_row[r]] (x scale[r] in float32), by one row
        DMA a filled row from the table in HBM, all of a tile's in flight
        before the first wait; a row of a live tile that holds no slot is
        written as ZEROS (the grouped matmul's `dw` sums every row of a live
        tile); tiles past `live` are unspecified
    combine   (token space, a grid step a tile of tokens)
        out[t] = sum over t's held slots of weight x buf[row], float32 in
        VMEM, one rounding to the output's dtype. A held expert's slots of a
        token tile are one contiguous range of its group (the group is in
        token order): the range is fetched as aligned chunks of `chunk`
        rows, and each of its rows is added to its token's row; slots of
        experts not held read nothing

Forward and backward, as one `custom_vjp` each (`dispatch_rows`,
`combine_rows`):

    buf    = dispatch(u)                du   = combine(g_buf, 1)
    routed = combine(out, w)            dout = dispatch(g, scale = w),
                                        dw[slot] = <g[token], out[row]>

The weights' gradient is taken per row by a second pass of the backward
dispatch, which holds g's row beside the row of `out` (apart from `dout`,
so that `dout` does not wait for `out`: XLA then fuses the experts' forward
made again with their backward), and reaches the (tokens, k) weights
through `row_of_slot`, a gather of scalars. A row DMA needs the row index on
an axis the layout does not tile: the table is first written (one pass,
`_pack_call`) as rows of 32-bit words, a bf16 row's columns j and j + d / 2
sharing a word. Off the TPU the same calls run under the TPU interpreter
(`pltpu.InterpretParams`: unwritten memory reads NaN).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the name scope around every kernel of this module (obs.device.FUSED_SCOPES)
SCOPE = "expert_rows"
# a 1-D int32 array in HBM is tiled by 1,024: SMEM blocks of the per-row and
# per-slot lists are whole multiples of it
_INDEX_BLOCK = 1024
_TOKEN_TILE = 256       # the combine's tokens a grid step, at most
_CHUNK = 16             # the combine's rows a DMA: a bf16 tile of sublanes


class RowPlan(NamedTuple):
    """Where the rows go, all int32, built by `plan_rows` from the routing:
    `token_of_row` (rows padded to whole index blocks; `tokens` where the
    row holds no slot), `filled` (rows of each tile that hold a slot, a
    prefix of it), `live` (1,), `row_of_slot` (slots; `rows` where the slot
    is not in the buffer), `slot_of_row` (rows; `slots` where none), and the
    combine's lists a token tile: `chunk_count`, `chunk_source` (each
    chunk's first row / chunk), `chunk_token` and `chunk_slot` (each chunk
    row's token and slot, counted from the tile's first; -1 where the row
    holds none of the tile's slots)."""
    token_of_row: jax.Array
    filled: jax.Array
    live: jax.Array
    row_of_slot: jax.Array
    slot_of_row: jax.Array
    chunk_count: jax.Array
    chunk_source: jax.Array
    chunk_token: jax.Array
    chunk_slot: jax.Array


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def combine_shape(tokens: int, k: int, held: int, tile: int) -> tuple:
    """(token tile, chunk rows, chunks a token tile at most, list entries a
    token tile): from the shapes alone. A held expert's range in a token
    tile spans at most ceil(n / chunk) + 1 aligned chunks; the ranges hold
    at most token tile x min(k, held) rows."""
    token_tile = math.gcd(tokens, _TOKEN_TILE)
    chunk = min(_CHUNK, tile)
    chunks = -(-token_tile * min(k, held) // chunk) + 2 * held
    return token_tile, chunk, chunks, _round_up(chunks * chunk, _INDEX_BLOCK)


# The plan and each kernel's call are `jax.jit`s of static sizes: the layers
# of a model share one trace of each, where a bare `pallas_call` traces its
# kernel anew at every call (seconds of a training step's set-up)
@functools.partial(jax.jit, static_argnames=("k", "tile"))
def plan_rows(token_of_row, slot_of_row, row_of_slot, expert_of_slot,
              group_start, live, *, k: int, tile: int) -> RowPlan:
    """The kernels' index lists from the routing (integer work over the
    slots and the held experts, no row of data moves). `expert_of_slot`
    (slots,): the held expert a slot goes to, `held` where none;
    `group_start` (held,): each group's first row; the group of a held
    expert lists its slots in slot (so token) order."""
    rows, slots, held = (token_of_row.shape[0], row_of_slot.shape[0],
                         group_start.shape[0])
    tokens = slots // k
    token_tile, chunk, chunks, entries = combine_shape(tokens, k, held, tile)
    tiles_of_tokens = tokens // token_tile

    # a held expert's slots in each token tile: a range [lo, hi) of its group
    by_tile = expert_of_slot.reshape(tiles_of_tokens, token_tile * k)
    count = (by_tile[:, :, None] == jnp.arange(held)[None, None, :]).sum(1)
    lo = group_start[None, :] + jnp.cumsum(count, 0) - count
    hi = jnp.minimum(lo + count, rows)              # an overflow is cut off
    lo = jnp.minimum(lo, rows)
    first = lo // chunk
    span = jnp.where(hi > lo, -(-hi // chunk) - first, 0)
    before = jnp.cumsum(span, 1) - span
    chunk_count = span.sum(1).astype(jnp.int32)
    q = jnp.arange(chunks)
    owner = jnp.clip((q[None, :, None] >= before[:, None, :]).sum(-1) - 1,
                     0, held - 1)
    pick = lambda a: jnp.take_along_axis(a, owner, axis=1)
    source = jnp.where(q[None, :] < chunk_count[:, None],
                       pick(first) + q[None, :] - pick(before), 0)
    # each chunk's rows' slots: `chunk` contiguous entries of `slot_of_row`
    # a chunk, so one gather of whole chunks (a scalar gather costs by the
    # element)
    slot = jnp.take(slot_of_row.reshape(rows // chunk, chunk), source,
                    axis=0).reshape(tiles_of_tokens, chunks * chunk)
    slot = jnp.pad(slot, ((0, 0), (0, entries - chunks * chunk)),
                   constant_values=slots)
    # the slot within its token tile: the tile's slots are token_tile x k
    # consecutive ones; a chunk's row of another tile, or of no slot, is none
    local = slot - (jnp.arange(tiles_of_tokens) * token_tile * k)[:, None]
    counted = jnp.repeat(q[None, :] < chunk_count[:, None], chunk, axis=1)
    counted = jnp.pad(counted, ((0, 0), (0, entries - chunks * chunk)))
    mine = counted & (local >= 0) & (local < token_tile * k)

    tiles = rows // tile
    index_rows = _round_up(rows, _INDEX_BLOCK)
    return RowPlan(
        token_of_row=jnp.pad(token_of_row, (0, index_rows - rows),
                             constant_values=tokens).astype(jnp.int32),
        filled=(token_of_row.reshape(tiles, tile) < tokens).sum(1).astype(
            jnp.int32),
        live=jnp.clip(jnp.asarray(live, jnp.int32), 1, tiles).reshape(1),
        row_of_slot=row_of_slot.astype(jnp.int32),
        slot_of_row=slot_of_row.astype(jnp.int32),
        chunk_count=chunk_count,
        chunk_source=source.reshape(-1).astype(jnp.int32),
        chunk_token=jnp.where(mine, local // k, -1).reshape(-1).astype(
            jnp.int32),
        chunk_slot=jnp.where(mine, local, -1).reshape(-1).astype(jnp.int32))


def _interpret(interpret: bool):
    # the DMAs and semaphores need the TPU interpreter off the chip
    return pltpu.InterpretParams() if interpret else False


def _params(semantics: str, vmem_bytes: int):
    return pltpu.CompilerParams(
        dimension_semantics=(semantics,),
        vmem_limit_bytes=min(max(32 << 20, int(vmem_bytes * 1.25) + (8 << 20)),
                             100 << 20))


def _words(d: int, dtype) -> int:
    """32-bit words of a row of `d` values of `dtype` in the packed table."""
    if jnp.dtype(dtype) == jnp.float32:
        return d
    if jnp.dtype(dtype) == jnp.bfloat16 and d % 2 == 0:
        return d // 2
    raise ValueError(f"expert_rows: rows of {d} x {jnp.dtype(dtype).name}")


def admits(d: int, dtype) -> bool:
    """Whether rows of `d` values of `dtype` can move by the kernels:
    float32 rows, or bf16 rows of an even width."""
    try:
        _words(d, dtype)
    except ValueError:
        return False
    return True


def _bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _float(x):
    return jax.lax.bitcast_convert_type(x, jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pack_call(table, interpret: bool):
    """(n, d) -> (n, 1, words) uint32: each row one run of words, the row
    index on an untiled axis (bf16: columns j and j + d / 2 in one word)."""
    n, d = table.shape
    words = _words(d, table.dtype)
    block = math.gcd(n, _TOKEN_TILE)

    def kernel(x_ref, o_ref):
        x = x_ref[...]
        if words == d:
            packed = _bits(x.astype(jnp.float32))
        else:
            x = x.astype(jnp.float32)
            packed = (_bits(x[:, words:]) >> 16 << 16) | (_bits(x[:, :words])
                                                       >> 16)
        o_ref[...] = packed.reshape(block, 1, words)

    return pl.pallas_call(
        kernel, grid=(n // block,),
        in_specs=[pl.BlockSpec((block, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, 1, words), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, words), jnp.uint32),
        compiler_params=_params("parallel", 4 * block * (d + words) * 4),
        interpret=_interpret(interpret), name="expert_rows_pack")(table)


def _unpack(words, d: int):
    """(n, words) uint32 -> the two float32 halves of the row, or the row."""
    if words.shape[-1] == d:
        return (_float(words),)
    return _float(words << 16), _float(words >> 16 << 16)


@functools.partial(jax.jit, static_argnames=("d", "dtype", "tile",
                                             "interpret"))
def _dispatch_call(packed, plan: RowPlan, d: int, dtype, tile: int,
                   interpret: bool, scale=None, partner=None):
    """buf[r] = table[token_of_row[r]] over the filled rows of the live
    tiles, zeros in the rest of a live tile. With `scale` (rows, 1) float32:
    each row times its scale. With `partner` (rows, d): no rows out, but
    each row's float32 dot with the partner's row, (rows, 1) (a call of its
    own, so that the scaled rows do not wait for the partner)."""
    words = packed.shape[-1]
    rows = plan.slot_of_row.shape[0]
    tiles = rows // tile
    per_block = _INDEX_BLOCK // tile
    extra = scale if partner is None else partner

    def kernel(live_ref, filled_ref, token_ref, table_ref, *refs):
        if extra is None:
            o_ref, sem, stage = refs
        else:
            extra_ref, o_ref, sem, stage = refs
        i = pl.program_id(0)
        n = filled_ref[i]
        base = (i % per_block) * tile

        def copy(r):
            return pltpu.make_async_copy(
                table_ref.at[token_ref[base + r]], stage.at[r], sem.at[0])

        def start(r, carry):
            @pl.when(r < n)
            def _():
                copy(r).start()
            return carry

        def wait(r, carry):
            @pl.when(r < n)
            def _():
                copy(r).wait()
            return carry

        @pl.when(i < live_ref[0])
        def _():
            jax.lax.fori_loop(0, tile, start, 0)
            jax.lax.fori_loop(0, tile, wait, 0)
            keep = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < n
            parts = [jnp.where(keep, h, 0.0) for h in
                     _unpack(stage[...].reshape(tile, words), d)]
            cuts = [slice(j * words, (j + 1) * words)
                    for j in range(len(parts))]
            if partner is not None:
                o_ref[...] = sum(
                    jnp.sum(h * extra_ref[:, c].astype(jnp.float32), axis=-1,
                            keepdims=True) for h, c in zip(parts, cuts))
                return
            for h, c in zip(parts, cuts):
                if scale is not None:
                    h = h * extra_ref[...]
                o_ref[:, c] = h.astype(o_ref.dtype)

    last = lambda i, live, filled: (jnp.minimum(i, live[0] - 1), 0)
    token_block = lambda i, live, filled: (
        jnp.minimum(i, live[0] - 1) // per_block,)
    in_specs = [pl.BlockSpec((_INDEX_BLOCK,), token_block,
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [plan.live, plan.filled, plan.token_of_row, packed]
    if extra is not None:
        in_specs.append(pl.BlockSpec((tile, extra.shape[1]), last))
        operands.append(extra)
    width = 1 if partner is not None else d
    vmem = tile * (words * 4 + 6 * d * 4)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles,), in_specs=in_specs,
            out_specs=pl.BlockSpec((tile, width), last),
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,)),
                            pltpu.VMEM((tile, 1, words), jnp.uint32)]),
        out_shape=jax.ShapeDtypeStruct(
            (rows, width), jnp.float32 if partner is not None else dtype),
        compiler_params=_params("arbitrary", vmem),
        interpret=_interpret(interpret), name="expert_dispatch",
    )(*operands)


@functools.partial(jax.jit, static_argnames=("tokens", "tile",
                                             "interpret"))
def _combine_call(buf, weights, plan: RowPlan, tokens: int, tile: int,
                  interpret: bool):
    """out[t] = sum over t's held slots of weight x buf[row], a float32 sum
    with one rounding; `weights` (tokens, k) float32, or None: every weight
    1."""
    rows, d = buf.shape
    token_tile, chunk = math.gcd(tokens, _TOKEN_TILE), min(_CHUNK, tile)
    tiles_of_tokens = tokens // token_tile
    chunks = plan.chunk_source.shape[0] // tiles_of_tokens
    entries = plan.chunk_token.shape[0] // tiles_of_tokens
    weighted = weights is not None
    if weighted:
        # a token tile's weights, whole index blocks of them
        per_tile = token_tile * weights.shape[1]
        dense = _round_up(per_tile, _INDEX_BLOCK)
        weights = jnp.pad(weights.reshape(tiles_of_tokens, per_tile),
                          ((0, 0), (0, dense - per_tile))).reshape(-1)

    def kernel(count_ref, source_ref, buf_ref, token_ref, slot_ref, *refs):
        if weighted:
            weight_ref, o_ref, stage, acc, sem = refs
        else:
            o_ref, stage, acc, sem = refs
        i = pl.program_id(0)
        n = count_ref[i]

        def copy(q):
            return pltpu.make_async_copy(
                buf_ref.at[pl.ds(pl.multiple_of(
                    source_ref[i * chunks + q] * chunk, chunk), chunk)],
                stage.at[pl.ds(pl.multiple_of(q * chunk, chunk), chunk)],
                sem.at[0])

        def start(q, carry):
            @pl.when(q < n)
            def _():
                copy(q).start()
            return carry

        def wait(q, carry):
            @pl.when(q < n)
            def _():
                copy(0).wait()
            return carry

        def add(q, carry):
            @pl.when(q < n)
            def _():
                block = stage[pl.ds(pl.multiple_of(q * chunk, chunk), chunk),
                              :].astype(jnp.float32)
                for j in range(chunk):
                    e = q * chunk + j
                    t = token_ref[e]

                    @pl.when(t >= 0)
                    def _():
                        row = block[j:j + 1]
                        if weighted:
                            row = weight_ref[slot_ref[e]] * row
                        acc[pl.ds(t, 1), :] += row
            return carry

        jax.lax.fori_loop(0, chunks, start, 0)
        acc[...] = jnp.zeros_like(acc)
        jax.lax.fori_loop(0, chunks, wait, 0)
        jax.lax.fori_loop(0, chunks, add, 0)
        o_ref[...] = acc[...].astype(o_ref.dtype)

    lists = lambda i, count, source: (i,)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((entries,), lists, memory_space=pltpu.SMEM),
                pl.BlockSpec((entries,), lists, memory_space=pltpu.SMEM)]
    operands = [plan.chunk_count, plan.chunk_source, buf, plan.chunk_token,
                plan.chunk_slot]
    if weighted:
        in_specs.append(pl.BlockSpec((dense,), lists,
                                     memory_space=pltpu.SMEM))
        operands.append(weights)
    itemsize = jnp.dtype(buf.dtype).itemsize
    vmem = chunks * chunk * d * itemsize + token_tile * d * (4 + 2 * itemsize)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles_of_tokens,), in_specs=in_specs,
            out_specs=pl.BlockSpec((token_tile, d),
                                   lambda i, count, source: (i, 0)),
            scratch_shapes=[pltpu.VMEM((chunks * chunk, d), buf.dtype),
                            pltpu.VMEM((token_tile, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((tokens, d), buf.dtype),
        compiler_params=_params("arbitrary", vmem),
        interpret=_interpret(interpret), name="expert_combine",
    )(*operands)


def _tile(plan: RowPlan) -> int:
    return plan.slot_of_row.shape[0] // plan.filled.shape[0]


@contextlib.contextmanager
def _scoped(outer):
    """The enclosing kernel's scope (a rule is traced with no scope around
    it), then this module's."""
    with contextlib.ExitStack() as stack:
        if outer:
            stack.enter_context(jax.named_scope(outer))
        stack.enter_context(jax.named_scope(SCOPE))
        yield


@functools.lru_cache(maxsize=None)
def _row_moves(interpret: bool, outer):
    """The two moves, each the other's transpose, as `custom_vjp`s."""
    scoped = functools.partial(_scoped, outer)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def dispatch(table, plan, tokens):
        with scoped():
            return _dispatch_call(_pack_call(table, interpret), plan,
                                  table.shape[1], table.dtype, _tile(plan),
                                  interpret)

    def dispatch_fwd(table, plan, tokens):
        return dispatch(table, plan, tokens), plan

    def dispatch_bwd(tokens, plan, g):
        with scoped():
            return _combine_call(g, None, plan, tokens, _tile(plan),
                                 interpret), None

    dispatch.defvjp(dispatch_fwd, dispatch_bwd)

    @jax.custom_vjp
    def combine(buf, weights, plan):
        with scoped():
            return _combine_call(buf, weights, plan, weights.shape[0],
                                 _tile(plan), interpret)

    def combine_fwd(buf, weights, plan):
        return combine(buf, weights, plan), (buf, weights, plan)

    def combine_bwd(res, g):
        buf, weights, plan = res
        rows, slots = buf.shape[0], plan.row_of_slot.shape[0]
        with scoped():
            scale = jnp.take(jnp.append(weights.reshape(slots), 0.0),
                             plan.slot_of_row)[:, None]
            packed = _pack_call(g, interpret)
            d_buf = _dispatch_call(packed, plan, buf.shape[1], buf.dtype,
                                   _tile(plan), interpret, scale=scale)
            dot = _dispatch_call(packed, plan, buf.shape[1], buf.dtype,
                                 _tile(plan), interpret, partner=buf)
            d_weights = jnp.where(
                plan.row_of_slot < rows,
                jnp.take(dot[:, 0], jnp.minimum(plan.row_of_slot, rows - 1)),
                0.0).reshape(weights.shape)
        return d_buf, d_weights.astype(weights.dtype), None

    combine.defvjp(combine_fwd, combine_bwd)
    return dispatch, combine


def dispatch_rows(table, plan: RowPlan, *, scope: Optional[str] = None,
                  interpret: bool = False):
    """(tokens, d) -> the buffer (rows, d): row r is table[token_of_row[r]]
    where it holds a slot, zeros elsewhere in a live tile, unspecified past
    the live tiles. Differentiable in `table` (its gradient: the combine,
    every weight 1). `scope`: the name scope of the kernel that calls it,
    put around the rules too."""
    return _row_moves(bool(interpret), scope)[0](table, plan, table.shape[0])


def combine_rows(buf, weights, plan: RowPlan, *, scope: Optional[str] = None,
                 interpret: bool = False):
    """(rows, d) buffer, (tokens, k) float32 weights -> (tokens, d) in the
    buffer's dtype: each token's held slots' rows times their weights,
    summed in float32. Reads the held slots' rows alone. Differentiable in
    `buf` and `weights`."""
    return _row_moves(bool(interpret), scope)[1](buf, weights, plan)
