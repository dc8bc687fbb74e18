"""The triangle multiplicative update with the pair tensor read once on the
way in and once on the way out.

Written as layer norm, five Dense layers, an einsum, a layer norm and a Dense
layer (reference alphafold2.py:257-317), XLA compiles one call at 640
residues to thirteen passes over a pair-sized tensor: `x` is read six times
(the statistics and the five projections) and four whole-tensor copies relay
operands round the contraction, 6.39 GB and 10.0 ms a call where the
mathematics needs 2.3 GB (PERF.md section 5, PR 36). Here the update is three
Pallas kernels that hand each other tensors in ONE layout, (b * i, hidden,
k): a row i of the map is a (hidden, k) tile, channels in the sublanes,
positions in the lanes. No XLA instruction of the pair's size is left
between them:

- `_project_pallas`: some rows of `x` are read once; layer-norm statistics
  in float32; ONE matmul against the five projection matrices side by side,
  W^T (5 h, c) . rows (positions, c)^T with both minor axes contracted (as
  the attention's q k^T), which puts the channels in the sublanes with no
  transpose; bias, mask, sigmoid and the two products in VMEM, in float32
  (XLA rounded to bf16 between a matmul and its gate); the gated left and
  right operands and the out gate written in that layout.
- `_contract_pallas`: a matmul a channel, `ik,jk->ij` (outgoing) or
  `ki,kj->ij` (ingoing). One channel's matrix is every sixteenth sublane of
  a block of sixteen channels: read and written with a sublane stride,
  32-bit words at a time (two bf16 channels to a word, split and joined in
  registers). This is where the relayout XLA spent four copies on happens:
  in VMEM, on the way to the MXU.
- `_finish_pallas`: rows of the product and of the out gate, layer norm over
  the sublanes in float32, x gate, `to_out` as a transposed-left matmul that
  brings the positions back to the rows, + bias + the residual, written
  (b, i, j, dim).

`triangle_multiply_xla` is today's `jax.numpy` formulation, in one place: the
path where the kernels do not apply, their reference in the tests, and the
differentiated path (see `_fused_update`).

Selection is `model/primitives.py:TriangleMultiplicativeModule`'s, by what
the trace can see: a TPU backend (or the CPU tests' door,
`ops.attention.use_pallas_attention`, interpreted), one device, a shape
`admits` accepts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import nn as jnn

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a Pallas call is no flax module: the scope is the one name its instructions
# carry of their own (obs/device.py books custom calls under it to `fused_s`)
FUSED_SCOPE = "fused_triangle_multiply"

EPS = 1e-5                      # `model/primitives.py:LayerNorm`'s
PROJECTIONS = ("left_proj", "left_gate", "right_proj", "right_gate",
               "out_gate")      # the order of the rows of the one matrix

_VMEM_LIMIT_BYTES = 100 * 2**20
# Positions (i, k) a grid step of the first and the last stage takes, in
# whole rows i of the map, and the fewest one of their matmuls takes.
_POSITIONS = 2048
_DOT_POSITIONS = 256
# Matmuls of the first and the last stage laid out as one basic block, so
# that one's MXU work overlaps the other's VPU work: the projections at 640
# 1.97 -> 1.83 ms, at 256 0.355 -> 0.306 (my chip runs, PR 36). Two channel
# pairs of the contraction a block bought 2% for three to five times the
# Mosaic compile (15-26 s at 640) and went.
_ROW_UNROLL = 2


def _compiler_params(grid_rank):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * grid_rank,
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


MIN_FUSED_LENGTH = 64
FUSED_LENGTH_MULTIPLE = 64
_LANES = 128
# A pair tensor (b, n, n, hidden) of up to this many bytes XLA holds in the
# chip's fast memory between its passes, where a Pallas call's operands and
# results lie in HBM: the 256 fold at batch 1 (32 MiB) took 64.6 ms in XLA's
# thirteen passes and 73.6 in the three kernels, the 384 fold (72 MiB) 241.4
# against 152.7, the online cell's 128 bucket at batch 8 (64 MiB) 34.2
# against 22.5 (my chip runs, PR 36; PERF.md section 5).
_MIN_PAIR_BYTES = 32 * 2**20


def admits(n: int, hidden: int, batch: int = 1, itemsize: int = 2) -> bool:
    """Whether the fused stages take `batch` maps of n x n with `hidden`
    channels between the stages: a side Mosaic tiles (every bucket of the
    benchmark's cells, 64 to 640, and 1,024), a hidden width of whole lane
    tiles, and a pair tensor too large for XLA to keep on the chip."""
    return (n >= MIN_FUSED_LENGTH and n % FUSED_LENGTH_MULTIPLE == 0
            and hidden % _LANES == 0
            and batch * n * n * hidden * itemsize > _MIN_PAIR_BYTES)


# -- the XLA formulation ------------------------------------------------------

def _layer_norm(p, x, dtype):
    """`nn.LayerNorm(epsilon=1e-5, dtype=dtype)` on its leaves, operation for
    operation (flax's fast variance, float32 statistics, the result in
    `dtype`): the training step differentiates THIS formulation, and another
    order of the same arithmetic compiles to another backward (the step read
    450.1 ms against 447.0 with `x * x` for `square`; PERF.md section 6)."""
    p = p["LayerNorm_0"]
    x32 = x.astype(jnp.float32)
    mean = jnp.expand_dims(x32.mean(-1), -1)
    mean2 = jnp.expand_dims(jax.lax.square(x32).mean(-1), -1)
    var = jnp.maximum(0.0, mean2 - jax.lax.square(mean))
    y = x - mean
    mul = jax.lax.rsqrt(var + EPS) * p["scale"].reshape(1, 1, 1, -1)
    y = y * mul + p["bias"].reshape(1, 1, 1, -1)
    return y.astype(dtype)


def _dense(p, name, x, dtype):
    with jax.named_scope(name):
        return jnp.dot(x, p[name]["kernel"].astype(dtype)) \
            + p[name]["bias"].astype(dtype)


def project_xla(p, x, mask, dtype):
    """Stage one as XLA runs it: (gated left, gated right, out gate), each
    (b, i, k, hidden) in `dtype`. mask: (b, i, k) or None."""
    x = _layer_norm(p["LayerNorm_0"], x, dtype)
    left = _dense(p, "left_proj", x, dtype)
    right = _dense(p, "right_proj", x, dtype)
    if mask is not None:
        mask = mask[..., None].astype(x.dtype)
        left, right = left * mask, right * mask
    # gates initialized to identity (reference alphafold2.py:280-282)
    gate = lambda name: jnn.sigmoid(_dense(p, name, x, dtype))
    return (left * gate("left_gate"), right * gate("right_gate"),
            gate("out_gate"))


def contract_xla(left, right, mix):
    """(b, i, k, hidden) operands: out[i, j] = sum_k left[i, k] right[j, k]
    (outgoing) or sum_k left[k, j] right[k, i] (ingoing)."""
    if mix == "outgoing":
        return jnp.einsum("bikd,bjkd->bijd", left, right)
    return jnp.einsum("bkjd,bkid->bijd", left, right)


def finish_xla(p, out, gate, dtype):
    """Stage three as XLA runs it, on (b, i, j, hidden) operands."""
    out = _layer_norm(p["LayerNorm_1"], out, dtype) * gate
    return _dense(p, "to_out", out, dtype)


def triangle_multiply_xla(p, x, mask=None, *, mix, dtype):
    """The update (reference alphafold2.py:257-317) as plain `jax.numpy` on
    the module's parameter leaves `p`: the ONE formulation beside the fused
    stages. x: (b, n, n, dim); mask: (b, n, n) or None."""
    left, right, gate = project_xla(p, x, mask, dtype)
    return finish_xla(p, contract_xla(left, right, mix), gate, dtype)


# -- the fused stages -----------------------------------------------------------
#
# Each stage is a `jax.jit` of its own: a program traces and lowers a stage
# once a shape, however many blocks and mixes call it (the unrolled trunk's
# two blocks, both mixes' projections and finish): a program's warm start
# pays for four Pallas lowerings, not twelve (`setup_s`; PERF.md section 6).
#
# Between the stages a tensor lies (b * i, hidden, k): one row i of the map is
# a (hidden, k) tile, the channels in the sublanes and the positions in the
# lanes. That is how a matmul hands the projections over (W^T . row^T) and how
# the last stage wants them (layer norm over the sublanes, then a
# transposed-left matmul back to (k, dim)); the contraction, which wants one
# channel's (i, k) matrix, reads and writes it with a sublane stride.

def _dot(dtype):
    # stated here so that no ambient `jax_default_matmul_precision` reaches
    # a kernel: bf16 operands take the MXU's one native pass, float32
    # operands are not narrowed
    return functools.partial(
        jax.lax.dot_general, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16
        else jax.lax.Precision.HIGHEST)


_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def _rows_a_step(n):
    """Rows i of the map a grid step of the first and the last stage takes:
    the most that divide n and keep the step under `_POSITIONS` positions."""
    return max(r for r in range(1, n + 1)
               if n % r == 0 and (r == 1 or r * n <= _POSITIONS))


def _rows_a_dot(n):
    """Rows i of the map one matmul of the first and the last stage takes:
    short rows go several at a time, so that the MXU's columns are full."""
    return max(1, _DOT_POSITIONS // n)


def _rows_loop(rows, n, body):
    """`body(first row, rows a matmul)` over the rows of a grid step,
    `_ROW_UNROLL` matmuls a loop iteration where that divides them."""
    group = _rows_a_dot(n)
    count = rows // group
    assert rows % group == 0, (rows, group)
    unroll = _ROW_UNROLL if count % _ROW_UNROLL == 0 else 1

    def step(i, carry):
        for j in range(unroll):
            body((i * unroll + j) * group, group)
        return carry

    jax.lax.fori_loop(0, count // unroll, step, 0)


def _project_kernel(*refs, hidden, rows, has_mask):
    refs = list(refs)
    x_ref, scale_ref, shift_ref, w_ref, b_ref = refs[:5]
    mask_ref = refs[5] if has_mask else None          # (rows, 1, n) float32
    left_ref, right_ref, gate_ref = refs[-3:]         # (rows, hidden, n)
    dtype = w_ref.dtype
    dot = _dot(dtype)
    n = x_ref.shape[1]

    def some_rows(first, count):
        x = x_ref[pl.ds(first, count)].astype(jnp.float32)
        x = x.reshape(count * n, x.shape[-1])         # (positions, c)
        x = x - jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        x = x * (jax.lax.rsqrt(var + EPS) * scale_ref[...]) + shift_ref[...]
        # W^T (5 h, c) . rows (positions, c)^T, both minor axes contracted
        # (as the attention's q k^T): a position's five projections in one
        # column, the channels in the sublanes with no transpose
        y = dot(w_ref[...], x.astype(dtype), _NT) + b_ref[...]
        part = lambda i: y[i * hidden:(i + 1) * hidden]     # (hidden, ..)
        left, right = part(0), part(2)
        if has_mask:
            mask = jnp.concatenate(
                [mask_ref[first + j] for j in range(count)], axis=-1)
            left, right = left * mask, right * mask
        left = left * jnn.sigmoid(part(1))
        right = right * jnn.sigmoid(part(3))
        gate = jnn.sigmoid(part(4))
        for j in range(count):
            row = slice(j * n, (j + 1) * n)
            left_ref[first + j] = left[:, row].astype(left_ref.dtype)
            right_ref[first + j] = right[:, row].astype(right_ref.dtype)
            gate_ref[first + j] = gate[:, row].astype(gate_ref.dtype)

    _rows_loop(rows, n, some_rows)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _project_pallas(p, x, mask, *, dtype, interpret):
    """(gated left, gated right, out gate), each (b * i, hidden, k)."""
    b, n, _, c = x.shape
    hidden = p["left_proj"]["kernel"].shape[-1]
    rows = _rows_a_step(n)
    row = lambda t: t.astype(jnp.float32).reshape(1, -1)
    norm = p["LayerNorm_0"]["LayerNorm_0"]
    # the five matrices side by side, made when the program is traced
    weights = jnp.concatenate(
        [p[name]["kernel"] for name in PROJECTIONS], axis=1).T.astype(dtype)
    biases = jnp.concatenate(
        [p[name]["bias"] for name in PROJECTIONS]).astype(
            jnp.float32).reshape(-1, 1)
    whole = lambda t: pl.BlockSpec(t.shape, lambda i: (0, 0))
    operands = [x.astype(dtype).reshape(b * n, n, c), row(norm["scale"]),
                row(norm["bias"]), weights, biases]
    in_specs = [pl.BlockSpec((rows, n, c), lambda i: (i, 0, 0))] \
        + [whole(t) for t in operands[1:]]
    if mask is not None:
        operands.append(mask.astype(jnp.float32).reshape(b * n, 1, n))
        in_specs.append(pl.BlockSpec((rows, 1, n), lambda i: (i, 0, 0)))
    tile = pl.BlockSpec((rows, hidden, n), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_project_kernel, hidden=hidden, rows=rows,
                          has_mask=mask is not None),
        out_shape=[jax.ShapeDtypeStruct((b * n, hidden, n), dtype)] * 3,
        grid=(b * n // rows,),
        in_specs=in_specs, out_specs=[tile] * 3,
        compiler_params=_compiler_params(1), interpret=interpret,
    )(*operands)


# Channels a grid step of the contraction takes: one tile of sublanes in
# bf16, whose 32-bit words hold two channels each.
_CHANNELS = 16
# The longest side of a block of the contraction's operands: whole maps up
# to 640 (three blocks of 13 MB, double-buffered), halves at 1,024.
_CONTRACT_BLOCK = 640


def _contract_kernel(l_ref, r_ref, o_ref, *, mix, packed):
    """out[i, d, j] = sum_k l[i, d, k] r[j, d, k] (outgoing: blocks
    (ti, C, n), (tj, C, n) -> (ti, C, tj)) or sum_k r[k, d, i] l[k, d, j]
    (ingoing: (n, C, tj), (n, C, ti) -> (ti, C, tj)): one channel's matrix
    is every C-th sublane of a block, read and written with that stride.
    `packed`: bf16 as the chip holds it, two channels to a 32-bit word, a
    word at a time through the strided access and split in registers."""
    dot = _dot(l_ref.dtype)

    def product(l, r):
        return dot(l, r, _NT) if mix == "outgoing" else dot(r, l, _TN)

    def loop(count, body):
        # Mosaic takes a traced sublane index only where the lanes are whole
        # tiles; the short maps' loop is unrolled (theirs are small bodies)
        if o_ref.shape[-1] % _LANES:
            for i in range(count):
                body(i)
        else:
            def step(i, carry):
                body(i)
                return carry

            jax.lax.fori_loop(0, count, step, 0)

    if not packed:
        def one_channel(d):
            o_ref[:, d, :] = product(l_ref[:, d, :], r_ref[:, d, :]).astype(
                o_ref.dtype)

        loop(l_ref.shape[1], one_channel)
        return

    l32, r32, o32 = (t.bitcast(jnp.uint32) for t in (l_ref, r_ref, o_ref))
    high = jnp.uint32(0xFFFF0000)

    def split(words):
        # a bf16 is the high half of the float32 of the same value
        return (pltpu.bitcast(words << 16, jnp.float32).astype(jnp.bfloat16),
                pltpu.bitcast(words & high, jnp.float32).astype(jnp.bfloat16))

    def rounded(t):
        return pltpu.bitcast(t.astype(jnp.bfloat16).astype(jnp.float32),
                             jnp.uint32)

    def two_channels(pair):
        (l0, l1), (r0, r1) = split(l32[:, pair, :]), split(r32[:, pair, :])
        o32[:, pair, :] = (rounded(product(l0, r0)) >> 16) \
            | (rounded(product(l1, r1)) & high)

    loop(l32.shape[1], two_channels)


@functools.partial(jax.jit, static_argnames=("mix", "interpret"))
def _contract_pallas(left, right, mix, *, interpret):
    """The contraction on (b * i, hidden, k) operands, to (b * i, hidden,
    j): a batched matmul with the channel as the batch."""
    rows, hidden, n = left.shape
    b = rows // n
    side = max(s for s in range(1, n + 1) if n % s == 0
               and s <= _CONTRACT_BLOCK and (s == n or s % _LANES == 0))
    per = n // side
    if mix == "outgoing":
        l_spec = pl.BlockSpec((side, _CHANNELS, n),
                              lambda bi, d, i, j: (bi * per + i, d, 0))
        r_spec = pl.BlockSpec((side, _CHANNELS, n),
                              lambda bi, d, i, j: (bi * per + j, d, 0))
    else:
        l_spec = pl.BlockSpec((n, _CHANNELS, side),
                              lambda bi, d, i, j: (bi, d, j))
        r_spec = pl.BlockSpec((n, _CHANNELS, side),
                              lambda bi, d, i, j: (bi, d, i))
    o_spec = pl.BlockSpec((side, _CHANNELS, side),
                          lambda bi, d, i, j: (bi * per + i, d, j))
    packed = left.dtype == jnp.bfloat16 and not interpret
    return pl.pallas_call(
        functools.partial(_contract_kernel, mix=mix, packed=packed),
        out_shape=jax.ShapeDtypeStruct((rows, hidden, n), left.dtype),
        grid=(b, hidden // _CHANNELS, per, per),
        in_specs=[l_spec, r_spec], out_specs=o_spec,
        compiler_params=_compiler_params(4), interpret=interpret,
    )(left, right)


def _finish_kernel(*refs, rows, has_residual):
    refs = list(refs)
    out_ref, gate_ref, scale_ref, shift_ref, w_ref, b_ref = refs[:6]
    x_ref = refs[6] if has_residual else None         # (rows, n, dim)
    y_ref = refs[-1]                                  # (rows, n, dim)
    dtype = w_ref.dtype
    dot = _dot(dtype)
    n = y_ref.shape[1]

    def some_rows(first, count):
        side_by_side = lambda ref: jnp.concatenate(
            [ref[first + j] for j in range(count)], axis=-1).astype(
                jnp.float32)                          # (hidden, positions)
        out = side_by_side(out_ref)
        out = out - jnp.mean(out, axis=0, keepdims=True)
        var = jnp.mean(out * out, axis=0, keepdims=True)
        out = out * (jax.lax.rsqrt(var + EPS) * scale_ref[...]) \
            + shift_ref[...]
        out = (out * side_by_side(gate_ref)).astype(dtype)
        # (hidden, positions)^T . W (hidden, dim): the positions back to
        # the rows
        y = dot(out, w_ref[...], _TN) + b_ref[...]
        y = y.reshape(count, n, y.shape[-1])
        if has_residual:
            y = y + x_ref[pl.ds(first, count)].astype(jnp.float32)
        y_ref[pl.ds(first, count)] = y.astype(y_ref.dtype)

    _rows_loop(rows, n, some_rows)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _finish_pallas(p, out, gate, residual, *, dtype, interpret):
    """out, gate: (b * i, hidden, j) -> (b, i, j, dim), + residual."""
    total, hidden, n = out.shape
    dim = p["to_out"]["kernel"].shape[-1]
    rows = _rows_a_step(n)
    column = lambda t: t.astype(jnp.float32).reshape(-1, 1)
    norm = p["LayerNorm_1"]["LayerNorm_0"]
    whole = lambda t: pl.BlockSpec(t.shape, lambda i: (0, 0))
    tile = pl.BlockSpec((rows, hidden, n), lambda i: (i, 0, 0))
    positions = pl.BlockSpec((rows, n, dim), lambda i: (i, 0, 0))
    operands = [out, gate, column(norm["scale"]), column(norm["bias"]),
                p["to_out"]["kernel"].astype(dtype),
                p["to_out"]["bias"].astype(jnp.float32).reshape(1, -1)]
    in_specs = [tile, tile] + [whole(t) for t in operands[2:]]
    out_dtype = dtype
    if residual is not None:
        operands.append(residual.reshape(total, n, dim))
        in_specs.append(positions)
        out_dtype = jnp.promote_types(dtype, residual.dtype)   # as `y + x`
    return pl.pallas_call(
        functools.partial(_finish_kernel, rows=rows,
                          has_residual=residual is not None),
        out_shape=jax.ShapeDtypeStruct((total, n, dim), out_dtype),
        grid=(total // rows,),
        in_specs=in_specs, out_specs=positions,
        compiler_params=_compiler_params(1), interpret=interpret,
    )(*operands).reshape(total // n, n, n, dim)


@functools.lru_cache(maxsize=None)
def _fused_update(mix, dtype, interpret):
    """The three stages as ONE `jax.custom_vjp`. The kernels have no
    backward. A differentiated trace never runs a `custom_vjp`'s primal: its
    first forward pass and the one `jax.checkpoint` makes again both run
    `fwd`, so `fwd` is `triangle_multiply_xla` under `jax.vjp`, its `vjp`
    the residual: the training step is the program it was before the
    kernels. (Each stage's forward fused with the `jax.vjp` of its XLA
    formulation for a backward runs the XLA forward a third time, inside
    `bwd`: measured slower on the crop-256 step; PERF.md section 6, PR 36.)"""
    dtype = jnp.dtype(dtype)

    def xla(p, x, mask, residual):
        y = triangle_multiply_xla(p, x, mask, mix=mix, dtype=dtype)
        return y if residual is None else y + residual

    @jax.custom_vjp
    def update(p, x, mask, residual):
        left, right, gate = _project_pallas(p, x, mask, dtype=dtype,
                                            interpret=interpret)
        out = _contract_pallas(left, right, mix, interpret=interpret)
        return _finish_pallas(p, out, gate, residual, dtype=dtype,
                              interpret=interpret)

    update.defvjp(lambda *args: jax.vjp(xla, *args),
                  lambda vjp, g: vjp(g))
    return update


@jax.named_scope(FUSED_SCOPE)
@functools.partial(jax.jit, static_argnames=("mix", "dtype", "interpret"))
def fused_triangle_multiply(p, x, mask=None, residual=None, *, mix, dtype,
                            interpret: bool = False):
    """The update through the fused stages, + `residual` where one is given:
    `triangle_multiply_xla(p, x, mask) + residual` with `x` read once and the
    result written once (differentiable: see `_fused_update`). x: (b, n, n,
    dim) with `admits(n, hidden)`; mask: (b, n, n) or None."""
    if mask is not None:
        mask = mask.astype(jnp.float32)
    return _fused_update(mix, jnp.dtype(dtype).name, interpret)(
        p, x, mask, residual)
