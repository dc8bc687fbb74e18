"""Fused axial attention for the Evoformer: logits that never reach HBM.

The hot loop of the trunk is gated axial attention over the rows or columns
of a 2-D map with an additive pair bias (reference Attention at
alphafold2.py:98-190). Written as einsum + softmax + einsum, XLA keeps the
(rows x heads, n, n) logits in HBM and passes over them four times (logits
contraction, `reduce_max`, `reduce_sum`, contraction with the values): at
n = 640 that is 4.2 GB a trip, 96 attentions a fold, a third of the 640 fold
(PERF.md section 5, PR 26). `fused_attention_merged` is one Pallas kernel
that holds a row's logits and probabilities in VMEM only: per attention HBM
carries q, k, v and the output once and the bias once per head.

Contract:

- q/k/v are (b, n, heads * d), the Dense projections' own layout with the
  heads side by side in the last axis, b = batch * bias_repeat rows; q is
  pre-scaled; the output has q's layout. Nothing is transposed or padded
  on the way in or out: a (rows x heads, n, 64) operand is stored 128 lanes
  wide on a TPU, so splitting the heads off doubled the kernel's HBM
  traffic and cost four relayout copies an attention, more device time
  than the kernel itself (PERF.md section 6, PR 27). A grid step takes a
  lane tile of heads (two of width 64); each head's logits are its lanes
  of q, the others zeroed, against all lanes of k, the contraction depth
  the MXU pads a 64-wide head to anyway.
- Operands enter the MXU in their own dtype (bf16 under the TPU policy)
  with float32 accumulation; max, exponential, sum and the division are
  float32; the probabilities are cast to v's dtype for the second dot.
- `bias` is passed *unrepeated*, (batch * heads, nq, nk) in the dtype
  `edges_to_attn_bias` produced, and cast inside the kernel. The grid is
  (batch, head group, query block, row group) with the row groups
  innermost, so a head's bias block is fetched once and stays resident
  while the rows that share it stream past.
- `q_mask`/`k_mask` are (b, n) vectors; masked keys are filled with
  `MASK_VALUE` in VMEM, and a masked query row reads the uniform average of
  v, which is what a row of equal `MASK_VALUE` logits softmaxes to.
- A grid step takes several rows: sized from n and VMEM by `_step_shape`,
  never by a caller's field or flag.

`fused_attention` is the same kernel for operands whose heads are folded
into the batch, (B, n, d): the layout of `attention_reference` and of the
block-sparse kernel.

Differentiation keeps the logits in VMEM too. `fused_attention_merged` is a
`jax.custom_vjp`: its `fwd` is the forward kernel, saving nothing but its own
inputs (a remat'd block keeps nothing new), and its `bwd` is one backward
kernel on the same layout and grid. Per row and head it makes the logits and
the probabilities p again from q, k, the bias and the key mask, then
dP = dO v^T, dS = p (dP - rowsum(p dP)), dq = dS k, dk = dS^T q, dv = p^T dO
(five contractions, operands in their own dtype, float32 accumulation; the
softmax and dS in float32). The bias cotangent is the sum of dS over the rows
that share the bias: an output block (heads of the group, n, n) in float32
whose index does not depend on the innermost, sequential row-group axis, so
it is zeroed by the first row group, added to by each, and written once; XLA
kept a (rows x heads, n, n) dS in HBM and reduced it. With two heads a lane
tile the other head's lanes of q and dO are zeroed before the contractions,
so dk and dv of the two heads land in their own lanes and are added; dq
takes its head's lanes by the forward's select. The loop body takes four rows
(`_BWD_UNROLL`), so that one row's contractions overlap another's softmax. The
query mask stays outside both kernels: the forward's `where` after the call
is transposed by hand in `bwd` (dO zeroed at the masked queries by a pass XLA
fuses into dO's producer; their share of dv, the average of v they read, a
row to a row that the kernel adds before it writes dv). The backward takes a
whole row of queries a grid step (`backward_admits`: every length up to 640);
where the forward blocks the queries (1,024) `fwd` and `bwd` are
`xla_attention`, the einsum + softmax path, and its own VJP.

Selection is `model/primitives.py:Attention.__call__`'s, by what the trace
can see: a TPU backend, self-attention, no tied rows, no active dropout and
a shape `admits` accepts. Off the chip the kernel runs in interpreter mode,
in its own tests and behind `use_pallas_attention`, the CPU tests' door.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Large-negative fill for masked logits (matches model/primitives.py).
MASK_VALUE = -1e9

_BACKEND = {"pallas": False}


def use_pallas_attention(enabled: bool = True):
    """Off the chip: route `Attention` onto the (interpreted) kernel. On a
    TPU nothing reads this: the shape rule decides."""
    _BACKEND["pallas"] = bool(enabled)


def pallas_attention_enabled() -> bool:
    return _BACKEND["pallas"]


@contextlib.contextmanager
def pallas_attention(enabled: bool = True):
    prev = _BACKEND["pallas"]
    use_pallas_attention(enabled)
    try:
        yield
    finally:
        _BACKEND["pallas"] = prev


# What the kernel may hold in VMEM. A v5e core has 128 MiB; Mosaic's default
# scoped limit is 16 MiB, which the 640 bucket's step (eight rows of q, k, v
# and the output double-buffered, two heads' bias, four (640, 640) float32
# temporaries a head) does not fit.
_VMEM_LIMIT_BYTES = 96 * 2**20
# One row's float32 logits are kept under this by blocking the queries
# (n = 640: 1.6 MB, unblocked; n = 1,024: two blocks of 512).
_LOGITS_BYTES = 2 * 2**20
# Bytes of one operand (q, k, v or the output) a grid step should move, so
# that its fixed cost (~0.35 us) and its DMA descriptors are small beside
# its work; and the most rows it takes however short they are. Between 2 and
# 64 rows a step the time is flat at every bucket (my chip run, PR 27).
_STEP_BYTES = 2 * 2**20
_MAX_ROWS = 64
_LANES = 128
# Rows the backward kernel's loop body takes together. A call of 8 heads of
# 64 in bf16, 1 / 2 / 4 / 8 rows a body (my chip runs, PR 32): 256 rows of
# 256: 2.52 / 2.43 / 2.35 / 2.32 ms; 256 rows of 128 without bias: 1.49 /
# 1.21 / 1.09 / 1.03; 640 of 640: 23.4 / 23.0 / 22.7 / 25.8, and Mosaic takes
# 4 / 10 / 24 s to compile the 640 kernel at 2 / 4 / 8.
_BWD_UNROLL = 4


def _head_group(heads, d):
    """Heads a grid step takes together: q, k, v and the output keep the
    projections' own layout, heads side by side in the lanes, so a block's
    lane width is a whole tile of 128 lanes (two heads of 64) or all of
    them."""
    return next(g for g in range(1, heads + 1)
                if heads % g == 0 and (g * d % _LANES == 0 or g == heads))


def _step_shape(n, nk, rows, width=_LANES):
    """(query block, rows per grid step) for an attention of `rows` rows of
    n queries by nk keys whose blocks are `width` lanes wide."""
    block_q = n
    while block_q * nk * 4 > _LOGITS_BYTES and block_q % (2 * _LANES) == 0:
        block_q //= 2
    target = max(1, min(rows, _MAX_ROWS, _STEP_BYTES // (nk * width * 2)))
    return block_q, 1 << (target.bit_length() - 1)   # row counts: mostly even


def _attn_kernel(*refs, has_bias, cast_bias, has_km, block_rows, group, d):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]        # (R, bq, W), (R, nk, W), (R, nk, W)
    idx = 3
    bias_ref = refs[idx] if has_bias else None        # (G, bq, nk)
    idx += int(has_bias)
    km_ref = refs[idx] if has_km else None            # (R, 1, nk) float32
    idx += int(has_km)
    o_ref = refs[idx]                                 # (R, bq, W)
    if cast_bias:
        # the heads' bias in float32, cast once when their first row group
        # arrives and read by every later one (the row-group axis is the
        # innermost, sequential grid axis)
        bias_f32 = refs[idx + 1]

        @pl.when(pl.program_id(3) == 0)
        def _():
            bias_f32[...] = bias_ref[...].astype(jnp.float32)
        bias_ref = bias_f32

    # stated here so that no ambient `jax_default_matmul_precision` reaches
    # the kernel: bf16 operands take the MXU's one native pass, float32
    # operands are not narrowed
    low = q_ref.dtype == jnp.bfloat16
    dot = functools.partial(
        jax.lax.dot_general, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT if low
        else jax.lax.Precision.HIGHEST)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, q_ref.shape[-1]), 1)

    def one_row(r, carry):
        q, k, v = q_ref[r], k_ref[r], v_ref[r]
        out = None
        for a in range(group):   # independent heads: MXU and VPU may overlap
            qa = q
            if group > 1:
                # head a's lanes of q against ALL lanes of k contract to its
                # logits: the other heads' lanes of q are zero, and the MXU
                # pads a 64-deep contraction to these 128 lanes anyway
                mine = (lane >= a * d) & (lane < (a + 1) * d)
                qa = jnp.where(mine, q, jnp.zeros_like(q))
            logits = dot(qa, k, (((1,), (1,)), ((), ())))   # (bq, nk)
            if has_bias:
                logits = logits + bias_ref[a]
            if has_km:
                logits = jnp.where(km_ref[r] > 0, logits, MASK_VALUE)
            m = jnp.max(logits, axis=-1, keepdims=True)
            p = jnp.exp(logits - m)
            denom = jnp.sum(p, axis=-1, keepdims=True)
            # (bq, W): head a's lanes hold its output, the rest are dropped
            res = dot(p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
            res = res * pl.reciprocal(denom, approx=True) if low \
                else res / denom
            out = res if out is None else jnp.where(mine, res, out)
        o_ref[r] = out.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_rows, one_row, 0)


def _step_layout(q, k, v, bias, k_mask, *, heads, bias_repeat, block_q,
                 block_rows):
    """What the forward and the backward call share: the grid, the operands
    folded to (batch, rows, ...) and their block specs, on the merged
    layout. Returns (grid, specs, args, shape): `specs`/`args` by operand
    name, `shape` the sizes a kernel needs."""
    b, n, width = q.shape
    nk, d = k.shape[1], width // heads
    if bias is None:
        batch, rows = 1, b
    else:
        assert bias.shape[0] * bias_repeat == b * heads, (
            bias.shape, bias_repeat, b, heads)
        batch, rows = bias.shape[0] // heads, bias_repeat
    group = _head_group(heads, d)
    auto_q, auto_rows = _step_shape(n, nk, rows, group * d)
    block_q, block_rows = block_q or auto_q, block_rows or auto_rows
    assert n % block_q == 0, (n, block_q)
    if v is None and group * d % _LANES:
        # heads that fill no lane tile go as one block of all their lanes,
        # which half of [k | v] is not
        k, v = jnp.split(k, 2, axis=-1)
    # v = None: k is the key-value projection's own output, the values in
    # its second half; the index map reads them there, and no copy splits
    # them off
    v_first = 0 if v is not None else heads // group
    assert k.shape[-1] == (width if v is not None else 2 * width), k.shape

    fold = lambda t: t.reshape(batch, rows, *t.shape[1:])
    specs = {
        "q": pl.BlockSpec((None, block_rows, block_q, group * d),
                          lambda bi, h, qi, g: (bi, g, qi, h)),
        "k": pl.BlockSpec((None, block_rows, nk, group * d),
                          lambda bi, h, qi, g: (bi, g, 0, h)),
        "v": pl.BlockSpec((None, block_rows, nk, group * d),
                          lambda bi, h, qi, g: (bi, g, 0, v_first + h))}
    args = {"q": fold(q), "k": fold(k), "v": fold(k if v is None else v)}
    if bias is not None:
        specs["bias"] = pl.BlockSpec(
            (None, group, block_q, nk), lambda bi, h, qi, g: (bi, h, qi, 0))
        args["bias"] = bias.reshape(batch, heads, n, nk)
    if k_mask is not None:
        assert k_mask.shape == (b, nk), (k_mask.shape, b, nk)
        specs["k_mask"] = pl.BlockSpec(
            (None, block_rows, 1, nk), lambda bi, h, qi, g: (bi, g, 0, 0))
        args["k_mask"] = k_mask.astype(jnp.float32).reshape(
            batch, rows, 1, nk)
    grid = (batch, heads // group, n // block_q, pl.cdiv(rows, block_rows))
    shape = dict(batch=batch, rows=rows, n=n, nk=nk, width=width, d=d,
                 group=group, block_q=block_q, block_rows=block_rows)
    return grid, specs, args, shape


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _fused_attention_pallas(q, k, v, bias, q_mask, k_mask, *, heads,
                            bias_repeat, block_q=None, block_rows=None,
                            interpret=False):
    """The forward pallas_call on the merged layout and the query mask after
    it (`fused_attention_merged` is the differentiable door)."""
    grid, specs, args, s = _step_layout(
        q, k, v, bias, k_mask, heads=heads, bias_repeat=bias_repeat,
        block_q=block_q, block_rows=block_rows)
    scratch = []
    cast_bias = bias is not None and bias.dtype != jnp.float32
    if cast_bias:
        scratch.append(
            pltpu.VMEM((s["group"], s["block_q"], s["nk"]), jnp.float32))
    kernel = functools.partial(
        _attn_kernel, has_bias=bias is not None, cast_bias=cast_bias,
        has_km=k_mask is not None, block_rows=s["block_rows"],
        group=s["group"], d=s["d"])
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (s["batch"], s["rows"], s["n"], s["width"]), q.dtype),
        grid=grid,
        in_specs=list(specs.values()),
        out_specs=specs["q"],
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*args.values()).reshape(q.shape)
    if q_mask is None:
        return out
    # a masked query's logits are all MASK_VALUE: uniform weights, the
    # average of v. An elementwise pass that XLA fuses into the gate that
    # reads the output; inside the kernel the (1, n) -> (n, 1) relayout of
    # the mask cost a third of the 128-long attention (PERF.md section 6)
    values = k[..., s["width"]:] if v is None else v
    uniform = jnp.mean(values.astype(jnp.float32), axis=1, keepdims=True)
    return jnp.where(q_mask.astype(bool)[..., None], out,
                     uniform.astype(out.dtype))


def _attn_bwd_kernel(*refs, has_bias, cast_bias, has_km, has_qm, block_rows,
                     rows, group, d):
    """One grid step of the backward: per row and head the logits and the
    probabilities again from q, k, the bias and the key mask (float32, in
    VMEM, as `_attn_kernel` makes them), then dP = dO v^T,
    dS = p (dP - rowsum(p dP)), dq = dS k, dk = dS^T q, dv = p^T dO; dS is
    added to the heads' bias cotangent, an output block that stays resident
    while the rows that share the bias stream past. With a query mask, dO
    comes zeroed at the masked queries and their share of dv (they read the
    average of v) comes as one row to a row, added before dv is written."""
    refs = list(refs)
    q_ref, k_ref, v_ref, do_ref = refs[:4]    # (R, bq, W), 2 x (R, nk, W), q's
    idx = 4
    bias_ref = refs[idx] if has_bias else None        # (G, bq, nk)
    idx += int(has_bias)
    km_ref = refs[idx] if has_km else None            # (R, 1, nk) float32
    idx += int(has_km)
    dv_mean_ref = refs[idx] if has_qm else None       # (R, 1, W) float32
    idx += int(has_qm)
    dq_ref, dk_ref, dv_ref = refs[idx:idx + 3]        # q's, k's, v's blocks
    idx += 3
    dbias_ref = refs[idx] if has_bias else None       # (G, bq, nk) float32
    idx += int(has_bias)
    first = pl.program_id(3) == 0
    if cast_bias:
        bias_f32 = refs[idx]

        @pl.when(first)
        def _():
            bias_f32[...] = bias_ref[...].astype(jnp.float32)
        bias_ref = bias_f32
    if has_bias:
        # the row-group axis is the innermost, sequential grid axis and the
        # block's index does not depend on it: zeroed by the heads' first
        # row group, added to by every one, written back after the last
        @pl.when(first)
        def _():
            dbias_ref[...] = jnp.zeros_like(dbias_ref)

    low = q_ref.dtype == jnp.bfloat16
    dot = functools.partial(
        jax.lax.dot_general, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT if low
        else jax.lax.Precision.HIGHEST)
    nt = (((1,), (1,)), ((), ()))     # a b^T
    nn = (((1,), (0,)), ((), ()))     # a b
    tn = (((0,), (0,)), ((), ()))     # a^T b
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, q_ref.shape[-1]), 1)

    def one_row(r, carry):
        q, k, v, do = q_ref[r], k_ref[r], v_ref[r], do_ref[r]
        if has_km:
            keys = km_ref[r] > 0
            # a row with no valid key: every logit is the fill, which no
            # operand reaches (the reference's `where` passes no cotangent):
            # p is uniform and dv has its share, dq, dk and dbias are zero
            any_key = jnp.max(km_ref[r], axis=-1, keepdims=True) > 0
        dq = dk = dv = None
        for a in range(group):
            qa, doa = q, do
            if group > 1:
                # the other heads' lanes of q and dO are zero: their
                # contractions over all lanes are head a's, and dk and dv of
                # head a come out in its own lanes, zero in the others
                mine = (lane >= a * d) & (lane < (a + 1) * d)
                qa = jnp.where(mine, q, jnp.zeros_like(q))
                doa = jnp.where(mine, do, jnp.zeros_like(do))
            logits = dot(qa, k, nt)                       # (bq, nk)
            if has_bias:
                logits = logits + bias_ref[a]
            if has_km:
                logits = jnp.where(keys, logits, MASK_VALUE)
            m = jnp.max(logits, axis=-1, keepdims=True)
            p = jnp.exp(logits - m)
            denom = jnp.sum(p, axis=-1, keepdims=True)
            p = p * pl.reciprocal(denom, approx=True) if low else p / denom
            dp = dot(jnp.where(any_key, doa, jnp.zeros_like(doa))
                     if has_km else doa, v, nt)           # (bq, nk)
            ds = p * (dp - jnp.sum(p * dp, axis=-1, keepdims=True))
            if has_bias:
                dbias_ref[a] += ds
            ds = ds.astype(k.dtype)
            dq_a = dot(ds, k, nn)                         # (bq, W)
            dk_a = dot(ds, qa, tn)                        # (nk, W)
            dv_a = dot(p.astype(do.dtype), doa, tn)       # (nk, W)
            dq = dq_a if dq is None else jnp.where(mine, dq_a, dq)
            dk = dk_a if dk is None else dk + dk_a
            dv = dv_a if dv is None else dv + dv_a
        if has_qm:
            dv = dv + dv_mean_ref[r]
        dq_ref[r] = dq.astype(dq_ref.dtype)
        dk_ref[r] = dk.astype(dk_ref.dtype)
        dv_ref[r] = dv.astype(dv_ref.dtype)
        return carry

    # Several rows a loop iteration: their chains of contractions and
    # softmax passes are independent, and one basic block lets the scheduler
    # overlap one row's MXU work with another's VPU work (`_BWD_UNROLL`).
    # The rows of a last, partial group that are not there hold anything:
    # they must not reach the bias cotangent, so the loops end at the last
    # row there is.
    unroll = next(u for u in (_BWD_UNROLL, 2, 1) if block_rows % u == 0)
    here = block_rows if rows % block_rows == 0 else jnp.minimum(
        block_rows, rows - pl.program_id(3) * block_rows)

    def some_rows(i, carry):
        for j in range(unroll):
            carry = one_row(unroll * i + j, carry)
        return carry

    jax.lax.fori_loop(0, here // unroll, some_rows, 0)
    if rows % block_rows and unroll > 1:
        jax.lax.fori_loop(here // unroll * unroll, here, one_row, 0)


def _fused_attention_bwd_pallas(q, k, v, bias, q_mask, k_mask, g, *, heads,
                                bias_repeat, block_rows=None,
                                interpret=False):
    """The backward pallas_call: (dq, dk, dv, dbias) on the layouts of q,
    k, v (dk is [dk | dv] and dv None where k was [k | v]) and the
    unrepeated bias. A whole row of queries a step (`backward_admits`)."""
    grid, specs, args, s = _step_layout(
        q, k, v, bias, k_mask, heads=heads, bias_repeat=bias_repeat,
        block_q=q.shape[1], block_rows=block_rows)
    lead, width = (s["batch"], s["rows"]), s["width"]
    names = [name for name in ("q", "k", "v", "bias", "k_mask")
             if name in specs]
    in_specs = [specs[name] for name in names]
    operands = [args[name] for name in names]
    if q_mask is not None:
        # the forward's `where` after the call, transposed by hand: no
        # cotangent reaches the kernel's output at a masked query, and the
        # average of v it read instead has the sum of theirs, a row to a row
        keep = q_mask.astype(bool)[..., None]
        in_specs.append(pl.BlockSpec(
            (None, s["block_rows"], 1, s["group"] * s["d"]),
            lambda bi, h, qi, g: (bi, g, 0, h)))
        operands.append((jnp.sum(
            jnp.where(keep, 0, g).astype(jnp.float32), axis=1, keepdims=True)
            / s["nk"]).reshape(*lead, 1, width))
        g = jnp.where(keep, g, jnp.zeros_like(g))
    in_specs.insert(3, specs["q"])            # dO, after q, k and v
    operands.insert(3, g.reshape(args["q"].shape))
    # the cotangents of k and v are blocks of two arrays even where k and v
    # came as one: an output has one block spec
    out_specs = [specs["q"], specs["k"], specs["k"]]
    out_shape = [jax.ShapeDtypeStruct((*lead, s["n"], width), q.dtype)] \
        + [jax.ShapeDtypeStruct((*lead, s["nk"], width), k.dtype)] * 2
    scratch = []
    cast_bias = bias is not None and bias.dtype != jnp.float32
    if bias is not None:
        out_specs.append(specs["bias"])
        out_shape.append(jax.ShapeDtypeStruct(args["bias"].shape,
                                              jnp.float32))
        if cast_bias:
            scratch.append(
                pltpu.VMEM((s["group"], s["n"], s["nk"]), jnp.float32))
    kernel = functools.partial(
        _attn_bwd_kernel, has_bias=bias is not None, cast_bias=cast_bias,
        has_km=k_mask is not None, has_qm=q_mask is not None,
        block_rows=s["block_rows"], rows=s["rows"], group=s["group"],
        d=s["d"])
    dq, dk, dv, *dbias = pl.pallas_call(
        kernel, out_shape=out_shape, grid=grid,
        in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS, interpret=interpret,
    )(*operands)
    dq = dq.reshape(q.shape)
    dk, dv = (t.reshape(k.shape[0], k.shape[1], width) for t in (dk, dv))
    dbias = dbias[0].reshape(bias.shape).astype(bias.dtype) if dbias \
        else None
    if v is None:
        return dq, jnp.concatenate([dk, dv], axis=-1), None, dbias
    return dq, dk, dv, dbias


def split_heads(t, heads):
    """(b, n, heads * d), heads side by side -> (b, heads, n, d)."""
    return jnp.moveaxis(t.reshape(*t.shape[:-1], heads, -1), -2, 1)


def merge_heads(t):
    """(b, heads, n, d) -> (b, n, heads * d)."""
    return jnp.moveaxis(t, 1, -2).reshape(t.shape[0], t.shape[2], -1)


def _zero_cotangent(x):
    if x is None:
        return None
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


@functools.lru_cache(maxsize=None)
def _fused_attention_vjp(heads, bias_repeat, block_q, block_rows, interpret):
    """The forward kernel as a `jax.custom_vjp`. Where `backward_admits` the
    shape, `fwd` is the same call, saving nothing but its own inputs, and
    `bwd` the backward kernel: a differentiated trace holds two custom calls
    an attention (three under remat, which runs the forward again) and no
    tensor of the logits' shape. Elsewhere `fwd`/`bwd` are `xla_attention`
    (on split heads) and its own VJP, the program such a trace always had.
    Grads flow to q/k/v and the (unrepeated) bias; masks get symbolic-zero
    cotangents."""
    step = dict(heads=heads, bias_repeat=bias_repeat, block_rows=block_rows,
                interpret=interpret)

    def xla(q, k, v, bias, q_mask, k_mask):
        if v is None:
            k, v = jnp.split(k, 2, axis=-1)
        if bias is not None:
            bias = bias.reshape(-1, heads, *bias.shape[1:])
        return merge_heads(xla_attention(
            split_heads(q, heads), split_heads(k, heads),
            split_heads(v, heads), bias, q_mask, k_mask,
            bias_repeat=bias_repeat))

    @jax.custom_vjp
    def f(q, k, v, bias, q_mask, k_mask):
        return _fused_attention_pallas(q, k, v, bias, q_mask, k_mask,
                                       block_q=block_q, **step)

    def fwd(q, k, v, bias, q_mask, k_mask):
        if backward_admits(q.shape[1], k.shape[1], block_q):
            return f(q, k, v, bias, q_mask, k_mask), dict(
                inputs=(q, k, v, bias), masks=(q_mask, k_mask))
        out, vjp = jax.vjp(
            lambda q, k, v, bias: xla(q, k, v, bias, q_mask, k_mask),
            q, k, v, bias)
        return out, dict(xla_vjp=vjp, masks=(q_mask, k_mask))

    def bwd(res, g):
        q_mask, k_mask = res["masks"]
        if "xla_vjp" in res:
            grads = res["xla_vjp"](g)
        else:
            q, k, v, bias = res["inputs"]
            grads = _fused_attention_bwd_pallas(q, k, v, bias, q_mask,
                                                k_mask, g, **step)
        return (*grads, _zero_cotangent(q_mask), _zero_cotangent(k_mask))

    f.defvjp(fwd, bwd)
    return f


# The shapes `Attention.__call__` sends to the kernel on a TPU: n is the
# attended length (queries = keys), d the head width. Measured on the v5e
# against the XLA attention of the same operands, 8 heads of 64, bf16 (my
# chip runs, PR 27; PERF.md section 6): the pair track's (rows = n) 640:
# 9.2 against 33.1 ms, 256: 1.24 against 2.02, 64 at batch 8: 0.89 against
# 1.65; the MSA row attention's (128 rows) 640: 1.86 against 4.81, 64 at
# batch 8: 1.76 against 3.76; the MSA column attention's (n = 128, no bias)
# 640 rows: 1.46 against 1.81, 256 rows: 0.60 against 0.50, its one loss.
# Nothing in the three cells' shapes is excluded; 64, half a lane tile, is
# the shortest length measured, and lengths are held to multiples of it.
MIN_FUSED_LENGTH = 64
FUSED_LENGTH_MULTIPLE = 64


def admits(n: int, d: int) -> bool:
    """Whether the fused kernel takes a self-attention over n positions
    with heads of width d: a length Mosaic tiles and at which the kernel
    was measured against the XLA path."""
    return (n >= MIN_FUSED_LENGTH and n % FUSED_LENGTH_MULTIPLE == 0
            and d % 8 == 0)


# The backward kernel against the XLA attention's backward, same operands (8
# heads of 64, bf16, both masks; my chip runs, PR 32; PERF.md section 6):
# 640 rows of 640: 22.7 against 104.5 ms, 384 of 384: 6.0 against 20.6, 256 of
# 256: 2.35 against 6.90, the MSA row attention's 128 rows of 256: 1.18
# against 2.88, the MSA column attention's 256 rows of 128 without bias: 1.09
# against 1.69, 128 of 128: 0.56 against 0.56, 64 of 64: 0.51 against 0.57.
# In the crop-256 training step a triangle attention's three calls (forward,
# remat's forward again, backward) take 0.875 + 0.875 + 1.615 ms where XLA's
# attention took 9.0; a predicate that kept XLA's backward for the column
# attention left the step where it was (486.0 against 485.0 ms) and went.
def backward_admits(n: int, nk: int, block_q=None) -> bool:
    """Whether a differentiated trace takes the backward kernel: a whole row
    of queries a grid step, which is every length up to 640. Where the
    forward blocks the queries (1,024: two blocks of 512) dk and dv would
    have to be accumulated over the query blocks too; no cell trains
    there."""
    return (block_q or _step_shape(n, nk, 1)[0]) == n


# a Pallas call is no flax module: the scope is the one name its
# instructions carry of their own (the enclosing module decides the kernel;
# obs/device.py books custom calls under this name to `fused_s`)
@jax.named_scope("fused_attention")
def fused_attention_merged(
    q: jnp.ndarray,              # (b, Nq, heads * D)
    k: jnp.ndarray,              # (b, Nk, heads * D), or [k | v] if v=None
    v=None,                      # (b, Nk, heads * D)
    bias=None,                   # (b // bias_repeat * heads, Nq, Nk)
    q_mask=None,                 # (b, Nq) bool/0-1, optional
    k_mask=None,                 # (b, Nk) bool/0-1, optional
    *,
    heads: int = 1,
    bias_repeat: int = 1,
    block_q=None,
    block_rows=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused bias+mask+softmax+matmul attention on the projections' own
    layout, heads side by side in the last axis, in and out: nothing is
    transposed or lane-padded on the way to the kernel or back
    (differentiable: see `_fused_attention_vjp`).

    b = batch * bias_repeat rows. With `v=None`, `k` is the key-value
    projection's whole output, (b, Nk, 2 * heads * D) with the values in
    its second half. `bias` covers (batch, heads) and is replayed over the
    folded middle axis via the index map; masks cover the rows and are
    shared across heads. `block_q` (a divisor of Nq) and
    `block_rows` override the step `_step_shape` chooses from the shape:
    for tests.

    Degenerate tiles (Nq or Nk < 8, e.g. the 1x1 pair maps the model's
    init-time branch coverage traces) take the XLA path: Mosaic lowers
    their dots to vector multi_reductions with loop-carried accumulators
    and refuses ("only constant accumulators supported", observed on-chip
    r05), and such shapes gain nothing from the kernel.
    """
    if q.shape[1] < 8 or k.shape[1] < 8:
        if v is None:
            k, v = jnp.split(k, 2, axis=-1)
        fold = lambda t: split_heads(t, heads).reshape(
            -1, t.shape[1], t.shape[2] // heads)
        out = attention_reference(fold(q), fold(k), fold(v), bias=bias,
                                  q_mask=q_mask, k_mask=k_mask, heads=heads,
                                  bias_repeat=bias_repeat)
        return merge_heads(out.reshape(q.shape[0], heads, *out.shape[1:]))
    return _fused_attention_vjp(heads, bias_repeat, block_q, block_rows,
                                interpret)(q, k, v, bias, q_mask, k_mask)


def fused_attention(q, k, v, bias=None, q_mask=None, k_mask=None, *,
                    heads: int = 1, **kw) -> jnp.ndarray:
    """`fused_attention_merged` for operands with the heads folded into
    the batch: q (B, Nq, D), k/v (B, Nk, D), B = batch * bias_repeat *
    heads with the head fastest, as `attention_reference` takes them."""
    merged = lambda t: merge_heads(t.reshape(-1, heads, *t.shape[1:]))
    out = fused_attention_merged(merged(q), merged(k), merged(v), bias,
                                 q_mask, k_mask, heads=heads, **kw)
    return split_heads(out, heads).reshape(q.shape)


def attention_weights(dots, bias=None, q_mask=None, k_mask=None, *,
                      bias_repeat=1):
    """softmax over the last axis of (b, h, n, m) logits, in their dtype,
    after the (b // bias_repeat, h, n, m) bias replayed over the folded
    axial axis (reference alphafold2.py:246-248) and the `MASK_VALUE` fill
    of every pair whose query or key is masked: the XLA attention's middle,
    shared by `xla_attention` and `Attention.__call__`'s inline branches."""
    if bias is not None:
        if bias_repeat != 1:
            bias = jnp.repeat(bias, bias_repeat, axis=0)
        dots = dots + bias.astype(dots.dtype)
    valid = None
    if q_mask is not None:
        valid = q_mask.astype(bool)[:, None, :, None]
    if k_mask is not None:
        keys = k_mask.astype(bool)[:, None, None, :]
        valid = keys if valid is None else valid & keys
    if valid is not None:
        dots = jnp.where(valid, dots, MASK_VALUE)
    return jax.nn.softmax(dots, axis=-1)


def xla_attention(q, k, v, bias=None, q_mask=None, k_mask=None, *,
                  bias_repeat=1):
    """The attention the model runs where the fused kernel does not apply,
    and under differentiation: (b, h, n, dh) operands, q pre-scaled,
    logits materialized in the activation dtype."""
    attn = attention_weights(jnp.einsum("bhid,bhjd->bhij", q, k), bias,
                             q_mask, k_mask, bias_repeat=bias_repeat)
    return jnp.einsum("bhij,bhjd->bhid", attn, v)


def attention_reference(q, k, v, bias=None, q_mask=None, k_mask=None,
                        *, heads=1, bias_repeat=1):
    """Float32-logits reference of the kernel's contract (tests, and the
    degenerate-tile fallback)."""
    logits = jnp.einsum("bnd,bmd->bnm", q, k).astype(jnp.float32)
    if bias is not None:
        logits = logits + jnp.repeat(
            bias.astype(jnp.float32).reshape(
                -1, heads, *bias.shape[1:]),
            bias_repeat, axis=0).reshape(logits.shape)
    valid = None
    if q_mask is not None:
        valid = (q_mask > 0)[:, :, None]
    if k_mask is not None:
        km = (k_mask > 0)[:, None, :]
        valid = km if valid is None else valid & km
    if valid is not None:
        valid = jnp.broadcast_to(
            valid, (valid.shape[0],) + logits.shape[1:])
        valid = jnp.repeat(valid, heads, axis=0)
        logits = jnp.where(valid, logits, MASK_VALUE)
    attn = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnm,bmd->bnd", attn.astype(q.dtype), v)


# -- causal attention for a token decoder -----------------------------------
#
# A decoder's self-attention over thousands of keys, with keys wider than
# values (latent attention: 192-wide q and k, 128-wide v), or with fewer key
# and value heads than query heads (grouped queries: query head h reads key
# head h // (heads / key heads)), under the causal mask or a causal band of
# `window` keys. Nothing above is touched by it: the axial kernel is
# non-causal, takes one head width and a whole row of keys. Here the blocked
# flash kernel that ships with JAX
# (`jax.experimental.pallas.ops.tpu.splash_attention`) does the work: it takes
# a key width and a value width of its own without padding v, a key head for
# each group of query heads without repeating it (its index maps read head
# h // group; the dk/dv kernel sums a group's query heads in VMEM), visits
# only the blocks the mask touches (the block mask is static and the grid
# shrinks to the band: it does not follow the data), and differentiates
# through a `custom_vjp` of two more Pallas kernels (dq; dk and dv) that make
# the logits again block by block. Logits never reach HBM, forward or
# backward.

CAUSAL_SCOPE = "causal_attention"
# the mark on the kernel's output and its log-sum-exp: what a rematerialised
# decoder layer keeps from its forward pass (`model/decoder.py`)
KEPT_CAUSAL = "causal_attention_out"
# query and key rows a grid step takes: the largest of these that divides n
_CAUSAL_BLOCKS = (1024, 512, 256, 128)
# the same under a band of keys: at a band of 512, 18 query heads over 2 key
# heads x 8,192 on the v5e, 512 rows took 0.76 ms forward and 2.69 with the
# backward, against 1.24 / 4.01 at 256, 3.05 / 8.64 at 128, 0.99 / 3.53 at
# 1,024 queries and 512 keys (PERF.md, PR 37); the whole triangle of the same
# heads 2.50 / 9.50
_WINDOW_BLOCKS = (512, 256, 128)


def causal_admits(n: int) -> bool:
    """Whether the blocked kernel takes n positions (a multiple of its
    smallest block)."""
    return n % _CAUSAL_BLOCKS[-1] == 0


def causal_attention_reference(q, k, v, window=None):
    """Masked dense causal attention, float32 logits: the kernel's contract
    (tests), and the path off the chip. q: (b, h, n, dk), q pre-scaled;
    k: (b, g, n, dk), v: (b, g, n, dv), g dividing h (query head i reads key
    head i // (h / g)); `window`: a query at i sees the keys j with
    i - window < j <= i."""
    b, h, n, _ = q.shape
    group = h // k.shape[1]
    q = q.reshape(b, h // group, group, n, q.shape[-1])
    logits = jnp.einsum("bgqid,bgjd->bgqij", q, k,
                        preferred_element_type=jnp.float32)
    offset = jnp.arange(n)[:, None] - jnp.arange(n)[None, :]
    visible = offset >= 0 if window is None else (offset >= 0) & (
        offset < window)
    attn = jax.nn.softmax(jnp.where(visible, logits, MASK_VALUE), axis=-1)
    out = jnp.einsum("bgqij,bgjd->bgqid", attn.astype(v.dtype), v)
    return out.reshape(b, h, n, v.shape[-1])


@functools.lru_cache(maxsize=None)
def _causal_kernel(heads: int, n: int, interpret: bool, window=None):
    """The splash kernel for `heads` query heads of n positions under one
    causal mask, or one causal band of `window` keys, built once a shape (its
    block tables are numpy, made on the host)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as masks,
    )
    if window is None:
        # 1,024 rows of queries and of keys a grid step, the keys computed
        # 512 at a time: the fastest of nine sets at 2 x 32 heads x 8,192 on
        # the v5e that keep dq a kernel of its own, 14.5 ms forward, 56.7
        # with the backward (PERF.md, PR 35). The fused dq/dk/dv kernel took
        # 49.3, but holds a float32 dq for every block of keys (3.2 GB
        # there); 2,048 rows do not fit VMEM
        blocks, mask = _CAUSAL_BLOCKS, masks.CausalMask((n, n))
    else:
        # the band's right side at 0 is the causal bound
        blocks = _WINDOW_BLOCKS
        mask = masks.LocalMask((n, n), (window - 1, 0), 0)
    block = next(b for b in blocks if n % b == 0)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=min(block, 512),
        block_q_dkv=block, block_kv_dkv=block,
        block_kv_dkv_compute=min(block, 512),
        block_q_dq=block, block_kv_dq=block)
    mask = masks.MultiHeadMask([mask] * heads)
    # built under `ensure_compile_time_eval`: the tables are constants of
    # whichever trace asks first, not tracers of it
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            mask, block_sizes=sizes, head_shards=1, q_seq_shards=1,
            residual_checkpoint_name=KEPT_CAUSAL, interpret=interpret)


@jax.named_scope(CAUSAL_SCOPE)
def causal_attention(q, k, v, *, interpret: bool = False, window=None):
    """softmax(q k^T + causal mask) v, blocked, forward and backward, logits
    in VMEM only. q: (b, h, n, dk), q pre-scaled; k: (b, g, n, dk) and v:
    (b, g, n, dv), g dividing h (grouped queries, as
    `causal_attention_reference`); `window`: the causal band of that many
    keys. The output has v's width: (b, h, n, dv). n has to be a multiple of
    128 (`causal_admits`)."""
    batch, heads, n, _ = q.shape
    if not causal_admits(n):
        raise ValueError(f"causal_attention: {n} positions are no multiple "
                         f"of {_CAUSAL_BLOCKS[-1]}")
    # the batch folded into the heads (all under one mask), not `vmap`ped:
    # a batched Pallas call loses its `op_name`, which the profile's reader
    # (obs/device.py) goes by. Row b * g + i of k is query rows b * h + i *
    # h / g onwards: the kernel's group map holds across the fold
    fold = lambda t: t.reshape(batch * t.shape[1], n, t.shape[-1])
    out = _causal_kernel(batch * heads, n, interpret, window)(
        fold(q), fold(k), fold(v))
    return out.reshape(batch, heads, n, v.shape[-1])
