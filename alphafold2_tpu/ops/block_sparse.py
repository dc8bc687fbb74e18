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

import contextlib
import functools
import hashlib
import threading
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from alphafold2_tpu.runtime import on_tpu

NEG_INF = float("-inf")
MASK_VALUE = -1e9  # matches ops/attention.py and the dense model path


def banded_block_pattern(n_blocks: int, window: int = 1,
                         num_global: int = 1) -> np.ndarray:
    """(n_blocks, n_blocks) bool block pattern: attend within +-window
    blocks of the diagonal plus the first num_global global blocks.
    THE single source of the local+global semantics — KernelSpec.banded,
    contact_block_pattern's floor, and the model-level
    attention_variants.block_sparse_block_pattern all delegate here, so
    the serving mask and the model mask cannot drift."""
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
    chip_smoke.py; on-chip timing vs the XLA dense path is
    `python tools/bench_blocksparse.py`.
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


# ---------------------------------------------------------------------------
# Serving-side kernel selection (ISSUE 12)
# ---------------------------------------------------------------------------
#
# A KernelSpec is the STATIC description of which attention kernel one
# compiled executable runs: the block pattern (banded+global, or a
# per-target contact-prior mask planned from recycle-1 pair
# activations), the block size, and the backend. It is hashable and
# cheap to label, so the serving executor can bake it into an ExecKey —
# flipping the policy (or re-planning the mask) re-lowers instead of
# serving a stale program.
#
# The spec reaches the model through a TRACE-TIME context
# (`kernel_context`), the same pattern as ops.attention's global
# use_pallas_attention flag but scoped and thread-local: the executor's
# jitted entry points wrap `predict.fold*` in the context, and
# `model.primitives.Attention` reads `active_kernel_spec()` while being
# traced, dispatching matching self-attention (attended-axis length ==
# spec.n) onto `block_sparse_attention` — one params tree, no module
# changes, the kernel choice lives entirely in which executable you
# compile.


@dataclass(frozen=True)
class KernelSpec:
    """One attention-kernel choice, static per compiled executable.

    pattern: (nqb, nkb) block pattern as a tuple of row tuples of bool
        (hashable; `pattern_array()` gives the numpy view the kernel
        plans from). Every row must keep >= 1 live block
        (plan_block_pattern's softmax guard).
    block: token block size. The spec covers attention whose attended
        axis has length n == block * nqb exactly.
    backend: "auto" (Pallas kernel on TPU, masked-dense fallback on
        CPU — tier-1 stays green without interpret-mode compile blowup),
        "pallas" (force the kernel; interpret mode off-TPU — tests),
        "masked" (dense compute + the pattern as a -1e9 additive mask:
        identical support, no FLOP skipping — the numerics reference).
    source: "static" (banded+global first-pass mask) or "contact"
        (planned from recycle-1 pair activations); observability only.
    """

    block: int
    pattern: Tuple[Tuple[bool, ...], ...]
    backend: str = "auto"
    source: str = "static"
    _label: str = field(default="", compare=False)

    def __post_init__(self):
        if self.backend not in ("auto", "pallas", "masked"):
            raise ValueError(f"unknown backend {self.backend!r}")
        nqb = len(self.pattern)
        if nqb == 0 or any(len(r) != nqb for r in self.pattern):
            raise ValueError("pattern must be square and non-empty")
        if any(not any(r) for r in self.pattern):
            raise ValueError("every q block needs >= 1 live k block")

    @classmethod
    def from_pattern(cls, pattern, block: int, backend: str = "auto",
                     source: str = "static") -> "KernelSpec":
        arr = np.asarray(pattern, dtype=bool)
        return cls(block=int(block),
                   pattern=tuple(tuple(bool(x) for x in row)
                                 for row in arr),
                   backend=backend, source=source)

    @classmethod
    def banded(cls, n: int, block: int, window: int = 1,
               num_global: int = 1, backend: str = "auto"
               ) -> "KernelSpec":
        """The static first-pass mask (banded_block_pattern — the one
        local+global source shared with the model-level menu)."""
        if n % block:
            raise ValueError(f"n={n} not divisible by block={block}")
        return cls.from_pattern(
            banded_block_pattern(n // block, window, num_global),
            block, backend=backend)

    @property
    def n(self) -> int:
        return self.block * len(self.pattern)

    @property
    def live_fraction(self) -> float:
        flat = [x for row in self.pattern for x in row]
        return sum(flat) / float(len(flat))

    @property
    def label(self) -> str:
        """Short stable identifier — the ExecKey element and the span/
        metric tag. Covers pattern content, block size, and backend, so
        two specs that would compile different programs never share a
        label."""
        lbl = object.__getattribute__(self, "_label")
        if not lbl:
            h = hashlib.blake2b(digest_size=4)
            h.update(np.packbits(self.pattern_array()).tobytes())
            h.update(f"|{self.block}|{self.backend}".encode())
            lbl = (f"bs{self.block}x{len(self.pattern)}-"
                   f"{self.source[0]}{h.hexdigest()}")
            object.__setattr__(self, "_label", lbl)
        return lbl

    def pattern_array(self) -> np.ndarray:
        return np.asarray(self.pattern, dtype=bool)

    def token_mask(self) -> np.ndarray:
        """(n, n) bool token-level view of the block pattern (the
        masked-dense backend's additive-mask support)."""
        p = self.pattern_array()
        return np.repeat(np.repeat(p, self.block, 0), self.block, 1)

    def covers(self, n: int) -> bool:
        return int(n) == self.n

    def resolve_backend(self) -> str:
        """The backend this trace actually runs: "auto" is the Pallas
        kernel when lowering for a TPU, the masked-dense fallback
        otherwise (CPU tier-1 must not pay interpret-mode tracing for
        every serving fold — interpret is opt-in via backend="pallas")."""
        if self.backend != "auto":
            return self.backend
        return "pallas" if on_tpu() else "masked"

    def interpret(self) -> bool:
        return not on_tpu()


_ACTIVE = threading.local()


def active_kernel_spec() -> Optional[KernelSpec]:
    """The KernelSpec governing the current trace, if any (thread-local
    — concurrent executor compiles on dispatch-pool threads each see
    their own)."""
    return getattr(_ACTIVE, "spec", None)


@contextlib.contextmanager
def kernel_context(spec: Optional[KernelSpec]):
    """Activate `spec` for the enclosed trace (None suppresses an outer
    context — e.g. the MSA column track, whose attended axis is
    alignment rows, must never inherit a residue-axis pattern)."""
    prev = getattr(_ACTIVE, "spec", None)
    _ACTIVE.spec = spec
    try:
        yield
    finally:
        _ACTIVE.spec = prev


# -- contact-prior mask planning (host-side, numpy) -------------------------


def contact_probs_from_distogram(distogram: np.ndarray,
                                 cutoff: float = 8.0,
                                 lengths=None) -> np.ndarray:
    """(n, n) contact probability from distogram logits: P(d < cutoff)
    via softmax over the distance buckets, max-reduced over the batch
    axis when given (b, n, n, buckets) — a batch shares one compiled
    pattern, so the mask must keep any block ANY element needs.

    `lengths` (optional, one per batch element) zeroes each element's
    contribution beyond its real residue count BEFORE the batch
    reduce: a padded row's distogram is garbage, and under continuous
    batching an admitted shorter fold's padding region (ISSUE 13) must
    plan as DEAD blocks — the sparse kernel must never DMA pair-bias
    garbage the mask would otherwise mark live. A length of 0 removes
    the element entirely (an unoccupied batch row).

    Bucket edges follow the distogram head's convention
    (constants.DISTOGRAM_MIN_DIST..MAX_DIST, linspace over
    DISTOGRAM_BUCKETS)."""
    from alphafold2_tpu import constants

    logits = np.asarray(distogram, np.float32)
    if logits.ndim == 3:
        logits = logits[None]
    b, n, n2, nb = logits.shape
    if lengths is not None and len(lengths) != b:
        raise ValueError(
            f"lengths has {len(lengths)} entries for batch of {b}")
    edges = np.linspace(constants.DISTOGRAM_MIN_DIST,
                        constants.DISTOGRAM_MAX_DIST, nb)
    # stable softmax over the bucket axis, ONE full-size temporary
    # (in-place exp; the normalized (..., nb) array is never
    # materialized): this runs host-side inside the serving step loop,
    # where a long bucket's (b, n, n, 37) map is GB-scale
    z = logits - logits.max(-1, keepdims=True)
    np.exp(z, out=z)
    close = edges <= cutoff
    probs = z[..., close].sum(-1)
    probs /= z.sum(-1)                       # (b, n, n)
    if lengths is not None:
        for i, ln in enumerate(lengths):
            ln = max(int(ln), 0)
            probs[i, ln:, :] = 0.0
            probs[i, :, ln:] = 0.0
    return probs.max(0)


def contact_block_pattern(contacts: np.ndarray, block: int, *,
                          threshold: float = 0.5,
                          live_frac: Optional[float] = None,
                          window: int = 1,
                          num_global: int = 1) -> np.ndarray:
    """Plan a (nqb, nkb) block pattern from an (n, n) contact-probability
    map: a block is live when its max cell probability clears
    `threshold` — or, with `live_frac` set, when it ranks inside the
    top live_frac of blocks (a data-independent FLOP budget). The
    banded window + global blocks are ALWAYS kept (the first-pass
    static mask is a floor, so the contact prior can only add support,
    never starve the diagonal) and the result is symmetrized —
    attention support should be, and it guarantees plan_block_pattern's
    min-1-live-block invariant via the diagonal."""
    c = np.asarray(contacts, np.float32)
    n = c.shape[0]
    if c.shape != (n, n):
        raise ValueError(f"contacts must be square, got {c.shape}")
    if n % block:
        raise ValueError(f"n={n} not divisible by block={block}")
    nb = n // block
    scores = c.reshape(nb, block, nb, block).max(axis=(1, 3))
    if live_frac is not None:
        live_frac = min(max(float(live_frac), 0.0), 1.0)
        k = max(1, int(round(live_frac * nb * nb)))
        cut = np.sort(scores.ravel())[::-1][k - 1]
        live = scores >= cut
    else:
        live = scores >= threshold
    live = live | banded_block_pattern(nb, window, num_global)
    return live | live.T
