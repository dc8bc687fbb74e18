"""True block-sparse attention Pallas kernel (splash-style block skipping).

Round-1 VERDICT (§2.4 "DeepSpeed sparse attn"): the model-level
`BlockSparseAttention` is dense compute + additive mask — correct
semantics, zero FLOP savings. This kernel does the real thing, the TPU
way: the sparsity pattern is compressed host-side into a per-q-block
column list, the grid's innermost dimension runs only to the max live
block count T (<< n_blocks for banded/global patterns), and a scalar-
prefetched index map steers each step's k/v DMA straight to the t-th
live block. FLOPs and HBM traffic both scale with nnz blocks, not N².

Softmax is the online (flash) recurrence over visited blocks — running
row max / denominator in VMEM scratch, output written on the last step.
Equivalent to dense attention with the pattern applied as a -1e9
additive bias (tests/test_ops.py::TestBlockSparseKernel asserts this
against `attention_reference`).

No torch/CUDA counterpart is being translated here: DeepSpeed's sparse
attention is a Triton kernel stack; this is an independent Pallas
design following the public splash-attention pattern (scalar prefetch +
compressed column index).
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
MASK_VALUE = -1e9  # matches ops/attention.py and the dense model path


def banded_block_pattern(n_blocks: int, window: int = 1,
                         num_global: int = 1) -> np.ndarray:
    """(n_blocks, n_blocks) bool block pattern: attend within +-window
    blocks of the diagonal plus the first num_global global blocks.
    THE single source of the local+global semantics: the model-level
    attention_variants.block_sparse_block_pattern delegates here, so its
    dense mask and this kernel's plan cannot drift."""
    bi = np.arange(n_blocks)
    local = np.abs(bi[:, None] - bi[None, :]) <= window
    glob = (bi < num_global)[:, None] | (bi < num_global)[None, :]
    return local | glob


def plan_block_pattern(pattern: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Compress a (nqb, nkb) boolean block pattern into a padded column
    plan: cols[i, t] = index of the t-th live k-block of q-block i,
    valid[i, t] = 1 where the slot is real. Every q-block must keep at
    least one live k-block (softmax over an empty row is undefined)."""
    pattern = np.asarray(pattern, dtype=bool)
    counts = pattern.sum(axis=1)
    if counts.min() < 1:
        raise ValueError("every q block needs >= 1 live k block")
    t_max = int(counts.max())
    nqb = pattern.shape[0]
    cols = np.zeros((nqb, t_max), np.int32)
    valid = np.zeros((nqb, t_max), np.int32)
    for i in range(nqb):
        live = np.nonzero(pattern[i])[0]
        cols[i, :live.size] = live
        valid[i, :live.size] = 1
    return cols, valid


def _kernel(cols_ref, valid_ref, *refs, t_total, scale, has_bias,
            has_kmask):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    idx = 3
    bias_ref = refs[idx] if has_bias else None
    idx += int(has_bias)
    km_ref = refs[idx] if has_kmask else None
    idx += int(has_kmask)
    o_ref = refs[idx]
    acc_ref, m_ref, l_ref = refs[idx + 1:]

    qb = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(valid_ref[qb, t] == 1)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)          # (bk, d)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # (bq, bk)
        if has_bias:
            # (bq, bk) additive bias of THIS live block (the unrepeated
            # per-head pair bias, steered by the same compressed column
            # plan as k/v — dead blocks' bias is never even fetched)
            logits = logits + bias_ref[0].astype(jnp.float32)
        if has_kmask:
            # (1, bk) f32 row — stays >=2-D in VMEM, broadcasting over
            # the query dim (same mask recipe as ops/attention.py)
            logits = jnp.where(km_ref[0] > 0, logits, MASK_VALUE)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        # exp(-inf - m_new) == 0 covers the first live step cleanly
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(t == t_total - 1)
    def _finish():
        # l >= 1 always: every q-block has >= 1 live k-block
        # (plan_block_pattern), and even a fully-masked block contributes
        # p = exp(-1e9 - (-1e9)) = 1 per key — fully-masked rows yield a
        # mean of visited values (unspecified on every backend), never a
        # zero division
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


# a Pallas call is no flax module: the scope is the one name its
# instructions carry of their own (the enclosing module decides the kernel,
# obs/device.py)
@jax.named_scope("block_sparse_attention")
def block_sparse_attention(
    q: jnp.ndarray,                # (B, N, D)
    k: jnp.ndarray,                # (B, N, D)
    v: jnp.ndarray,                # (B, N, D)
    pattern: np.ndarray,           # (nqb, nkb) bool, STATIC
    *,
    bias: jnp.ndarray | None = None,     # (Bb, N, N) additive, unrepeated
    bias_repeat: int = 1,
    k_mask: jnp.ndarray | None = None,   # (B // heads, N) key validity
    heads: int = 1,
    scale: float | None = None,
    block: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Attention restricted to `pattern` with true block skipping.

    `scale` multiplies q inside the kernel; default 1/sqrt(D) (the
    standard softmax temperature). Pass scale=1.0 for pre-scaled q —
    e.g. when fed from Attention.project_qkv, which scales at projection
    time. `bias` is an optional additive logit bias (the Evoformer's
    pair-edge bias) with the SAME unrepeated-replay contract as
    ops/attention.py's fused_attention: shape (Bb, N, N) with
    B == Bb // heads * bias_repeat * heads (head fastest), replayed
    across the folded axial axis by the index map — and only LIVE
    blocks of it are ever DMA'd, so the bias read scales with nnz
    blocks like everything else. `k_mask` masks individual keys INSIDE
    live blocks (the padded tail of a crop, per-sequence gaps) with the
    dense path's -1e9 fill; it stays UNrepeated — shape (B // heads, N)
    with head folded innermost into B — and the BlockSpec index map
    replays it across heads at zero HBM cost. Query-side masking is not
    applied — masked-query rows are unspecified on every backend,
    matching the dense path's contract.

    The Mosaic compile path (PrefetchScalarGridSpec + scalar-prefetch
    index maps) is exactness-tested in interpreter mode
    (tests/test_ops.py), compiled for a described v5e in
    tests/test_chip_compile.py and run compiled on the chip by
    chip_smoke.py; it has not been timed on the chip in any cell.
    """
    b, n, d = q.shape
    assert n % block == 0, (n, block)
    nqb = n // block
    assert pattern.shape == (nqb, nqb), (pattern.shape, nqb)
    cols, valid = plan_block_pattern(pattern)
    t_total = cols.shape[1]
    if scale is None:
        scale = float(d) ** -0.5
    has_bias = bias is not None
    has_kmask = k_mask is not None

    qkv_spec = [
        pl.BlockSpec((1, block, d),
                     lambda bi, qb, t, cols, valid: (bi, qb, 0)),
        pl.BlockSpec((1, block, d),
                     lambda bi, qb, t, cols, valid:
                     (bi, cols[qb, t], 0)),
        pl.BlockSpec((1, block, d),
                     lambda bi, qb, t, cols, valid:
                     (bi, cols[qb, t], 0)),
    ]
    args = [jnp.asarray(cols), jnp.asarray(valid), q, k, v]
    if has_bias:
        assert bias.shape[0] * bias_repeat == b, \
            (bias.shape, bias_repeat, b)
        assert bias.shape[1:] == (n, n), (bias.shape, n)
        rh = bias_repeat * heads
        # fused_attention's replay contract: flat batch index
        # i = (batch * bias_repeat + fold) * heads + head, bias covers
        # (batch, heads) — only the live block (qb, cols[qb, t]) of the
        # (N, N) map is fetched per step
        qkv_spec.append(pl.BlockSpec(
            (1, block, block),
            lambda bi, qb, t, cols, valid:
            ((bi // rh) * heads + bi % heads, qb, cols[qb, t])))
        args.append(bias.astype(jnp.float32))
    if has_kmask:
        assert b % heads == 0, (b, heads)
        assert k_mask.shape == (b // heads, n), \
            (k_mask.shape, (b // heads, n))
        # 3-D (B//heads, 1, N) f32, sliced (1, 1, block) per live block
        # and replayed across the folded head axis by the index map —
        # mirrors fused_attention's mask recipe (stays >=2-D in VMEM;
        # Mosaic v5e cannot reshape 1-bit/1-D vectors on the minor dim)
        args.append(k_mask.astype(jnp.float32)
                    .reshape(b // heads, 1, n))
        qkv_spec.append(pl.BlockSpec(
            (1, 1, block),
            lambda bi, qb, t, cols, valid:
            (bi // heads, 0, cols[qb, t])))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nqb, t_total),
        in_specs=qkv_spec,
        out_specs=pl.BlockSpec((1, block, d),
                               lambda bi, qb, t, cols, valid: (bi, qb, 0)),
        scratch_shapes=[
            pltpu.VMEM((block, d), jnp.float32),   # acc
            pltpu.VMEM((block, 1), jnp.float32),   # running max
            pltpu.VMEM((block, 1), jnp.float32),   # denominator
        ],
    )
    kernel = functools.partial(_kernel, t_total=t_total, scale=scale,
                               has_bias=has_bias, has_kmask=has_kmask)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*args)

