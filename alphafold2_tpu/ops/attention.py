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

Forward-only and differentiated attention have different best programs
until a fused backward exists, so they are separate: the primal of the
`jax.custom_vjp` is the kernel, and its `fwd`/`bwd`, which run only under
differentiation, are `xla_attention`, the einsum + softmax path the model
has always trained with (PERF.md section 7 names the fused backward as the
next step).

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


def _fused_attention_pallas(q, k, v, bias, q_mask, k_mask, *, heads,
                            bias_repeat, block_q=None, block_rows=None,
                            interpret=False):
    """The pallas_call on the merged layout and the query mask after it
    (forward only; `fused_attention_merged` is the differentiable door)."""
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

    q_spec = pl.BlockSpec((None, block_rows, block_q, group * d),
                          lambda bi, h, qi, g: (bi, g, qi, h))
    k_spec = pl.BlockSpec((None, block_rows, nk, group * d),
                          lambda bi, h, qi, g: (bi, g, 0, h))
    v_spec = pl.BlockSpec((None, block_rows, nk, group * d),
                          lambda bi, h, qi, g: (bi, g, 0, v_first + h))
    fold = lambda t: t.reshape(batch, rows, *t.shape[1:])
    in_specs = [q_spec, k_spec, v_spec]
    args = [fold(q), fold(k), fold(k if v is None else v)]
    scratch = []
    cast_bias = bias is not None and bias.dtype != jnp.float32
    if bias is not None:
        in_specs.append(pl.BlockSpec(
            (None, group, block_q, nk), lambda bi, h, qi, g: (bi, h, qi, 0)))
        args.append(bias.reshape(batch, heads, n, nk))
        if cast_bias:
            scratch.append(pltpu.VMEM((group, block_q, nk), jnp.float32))
    if k_mask is not None:
        assert k_mask.shape == (b, nk), (k_mask.shape, b, nk)
        in_specs.append(pl.BlockSpec(
            (None, block_rows, 1, nk), lambda bi, h, qi, g: (bi, g, 0, 0)))
        args.append(k_mask.astype(jnp.float32).reshape(batch, rows, 1, nk))

    kernel = functools.partial(
        _attn_kernel, has_bias=bias is not None, cast_bias=cast_bias,
        has_km=k_mask is not None, block_rows=block_rows, group=group, d=d)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((batch, rows, n, width), q.dtype),
        grid=(batch, heads // group, n // block_q,
              pl.cdiv(rows, block_rows)),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*args).reshape(b, n, width)
    if q_mask is None:
        return out
    # a masked query's logits are all MASK_VALUE: uniform weights, the
    # average of v. An elementwise pass that XLA fuses into the gate that
    # reads the output; inside the kernel the (1, n) -> (n, 1) relayout of
    # the mask cost a third of the 128-long attention (PERF.md section 6)
    values = k[..., width:] if v is None else v
    uniform = jnp.mean(values.astype(jnp.float32), axis=1, keepdims=True)
    return jnp.where(q_mask.astype(bool)[..., None], out,
                     uniform.astype(out.dtype))


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
    """The kernel as the primal of a custom_vjp whose `fwd`/`bwd` are
    `xla_attention` (on split heads) and its own VJP. JAX runs `fwd` in
    place of the primal whenever the call is differentiated, so a training
    step compiles to the XLA attention it always had and holds no custom
    call; grads flow to q/k/v and the (unrepeated) bias, masks get
    symbolic-zero cotangents."""

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
        return _fused_attention_pallas(
            q, k, v, bias, q_mask, k_mask, heads=heads,
            bias_repeat=bias_repeat, block_q=block_q, block_rows=block_rows,
            interpret=interpret)

    def fwd(q, k, v, bias, q_mask, k_mask):
        out, vjp = jax.vjp(
            lambda q, k, v, bias: xla(q, k, v, bias, q_mask, k_mask),
            q, k, v, bias)
        return out, (vjp, q_mask, k_mask)

    def bwd(res, g):
        vjp, q_mask, k_mask = res
        return (*vjp(g), _zero_cotangent(q_mask), _zero_cotangent(k_mask))

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
    from alphafold2_tpu.ops.cpu_gemm import (amx_attention_dots,
                                             amx_attention_out)
    attn = attention_weights(amx_attention_dots(q, k), bias, q_mask, k_mask,
                             bias_repeat=bias_repeat)
    return amx_attention_out(attn, v)


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
