"""Efficient-attention variants from the reference's README-era menu.

Capability parity with the reference's documented (pre-Evoformer) options
(/root/reference/README.md:271-487): DeepSpeed block-sparse self-attention,
Performer linear cross-attention, Kronecker-pooled cross-attention, and
memory-compressed (KV-downsampled) attention. The reference outsourced
these to CUDA packages (DeepSpeed+triton, performer-pytorch); here they
are small JAX modules sharing the package's gating/zero-init conventions
(primitives.attention_output_tail):

- `LinearAttention` — kernelized softmax-free attention, O(N d^2): the
  Performer role (README.md:419-449). Uses the elu+1 feature map
  (positive, monotone) rather than FAVOR+ random features — deterministic
  and TPU-friendly (two matmuls, no gather);
- `MemoryCompressedAttention` — KV mean-pooled by `compress_ratio`
  (README.md:475-487, "2-4 usually acceptable");
- `kronecker_pool_2d` + `KroneckerAttention` — axial-mean pooling of a
  2-D (pair) context into H + W tokens before cross-attention
  (README.md:451-468: attend to row means and column means, the
  Kronecker-structured O(H+W) compression);
- `block_sparse_mask` + `BlockSparseAttention` — fixed local+global
  block pattern as an additive mask (the DeepSpeed sparse-self-attn
  analog, README.md:388-417; a Pallas true-block-sparse kernel can reuse
  the same pattern).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import nn as jnn

from alphafold2_tpu.model.primitives import (
    MASK_VALUE,
    LayerNorm,
    attention_output_tail,
    zeros_init,
)
from alphafold2_tpu.runtime import on_tpu


def _dense_factory(module_dtype):
    return lambda f, name, use_bias=True, **kw: nn.Dense(
        f, use_bias=use_bias, dtype=module_dtype,
        param_dtype=jnp.float32, name=name, **kw)


def _qkv(dense, x, context, heads, dim_head):
    inner = heads * dim_head
    q = dense(inner, "to_q", use_bias=False)(x)
    kv = dense(inner * 2, "to_kv", use_bias=False)(context)
    k, v = jnp.split(kv, 2, axis=-1)
    split = lambda t: t.reshape(*t.shape[:-1], heads, dim_head
                               ).swapaxes(-2, -3)
    return split(q), split(k), split(v)


class LinearAttention(nn.Module):
    """Kernelized linear attention (Performer slot)."""

    dim: int
    heads: int = 8
    dim_head: int = 64
    gating: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, context=None, mask=None, context_mask=None):
        dense = _dense_factory(self.dtype)
        ctx = x if context is None else context
        q, k, v = _qkv(dense, x, ctx, self.heads, self.dim_head)

        phi = lambda t: jnn.elu(t) + 1.0
        q, k = phi(q), phi(k)

        kmask = context_mask if context is not None else mask
        if kmask is not None:
            k = k * kmask[:, None, :, None]
            v = v * kmask[:, None, :, None]

        kv = jnp.einsum("bhnd,bhne->bhde", k, v)
        z = jnp.einsum("bhnd,bhd->bhn", q, k.sum(-2))
        out = jnp.einsum("bhnd,bhde->bhne", q, kv) / \
            jnp.maximum(z[..., None], 1e-6)

        inner = self.heads * self.dim_head
        return attention_output_tail(dense, out, x, inner, self.gating,
                                     self.dim)


def orthogonal_random_features(key, nb_features: int, dim: int):
    """FAVOR+ projection matrix (nb_features, dim): rows are orthogonal
    within each dim-sized block (QR of a Gaussian), with row norms
    redrawn chi(dim) — the unbiased orthogonal random features of
    Choromanski et al. 2021 (the reference's performer-pytorch
    gaussian_orthogonal_random_matrix, README.md:419-449)."""
    n_blocks = -(-nb_features // dim)
    keys = jax.random.split(key, n_blocks + 1)
    blocks = []
    for i in range(n_blocks):
        g = jax.random.normal(keys[i], (dim, dim))
        q, _ = jnp.linalg.qr(g)
        blocks.append(q.T)
    w = jnp.concatenate(blocks, axis=0)[:nb_features]
    norms = jnp.sqrt(jax.random.chisquare(keys[-1], dim, (nb_features, 1)))
    return w * norms


def favor_softmax_features(x, proj, is_query: bool, eps: float = 1e-4,
                           mask=None):
    """Positive softmax-kernel features phi(x) (FAVOR+, Choromanski et al.
    2021 eq. 5): phi(x) = exp(Wx - ||x||^2/2 - c) / sqrt(m), giving the
    unbiased estimator E[phi(q)^T phi(k)] = exp(q . k).

    x: (..., n, d) already scaled by d^-1/4 (so q.k carries the 1/sqrt(d)
    softmax temperature). Stabilizer c: per-token max for queries, per
    ATTENTION INSTANCE (last two axes: tokens x features, i.e. one c per
    batch/head slice) for keys — both cancel in the attention ratio. A
    coarser global key max would let one high-magnitude batch entry crush
    every other entry's features toward the eps floor (performer-pytorch
    likewise uses amax over (-1, -2)). `mask` (..., n) excludes padded
    tokens from the key max; masked rows are pinned near c so exp cannot
    overflow before the caller zeroes them."""
    m = proj.shape[0]
    u = x @ proj.T                                     # (..., n, m)
    sq = (x * x).sum(-1, keepdims=True) / 2.0
    h = u - sq
    if mask is not None:
        h = jnp.where(mask[..., None], h, -jnp.inf)
    finite = jnp.where(jnp.isfinite(h), h, -1e30)
    if is_query:
        c = jax.lax.stop_gradient(finite.max(-1, keepdims=True))
    else:
        c = jax.lax.stop_gradient(
            jnp.max(finite, axis=(-1, -2), keepdims=True))
    h = jnp.where(jnp.isfinite(h), h, c - 100.0)  # masked -> exp ~ 0
    return (jnp.exp(h - c) + eps) / jnp.sqrt(m)


class PerformerAttention(nn.Module):
    """FAVOR+ attention (the reference's cross_attn_linear Performer,
    README.md:419-449): unbiased softmax-kernel approximation via
    orthogonal random features — O(n m d) instead of O(n^2 d), with the
    approximation error shrinking as `nb_features` grows
    (tests/test_attention_menu.py::test_favor_error_shrinks_with_features).

    Redraw hook: the projection is drawn from the 'performer' RNG
    collection when provided — `module.apply(params, x,
    rngs={"performer": key})` redraws per call (the JAX form of
    performer-pytorch's redraw_projections interval); without it a fixed
    fallback key keeps features deterministic across steps.
    """

    dim: int
    heads: int = 8
    dim_head: int = 64
    nb_features: int = 256
    gating: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, context=None, mask=None, context_mask=None):
        dense = _dense_factory(self.dtype)
        ctx = x if context is None else context
        q, k, v = _qkv(dense, x, ctx, self.heads, self.dim_head)
        # FAVOR splits the softmax temperature as d^-1/4 on each of q and
        # k so phi(q)^T phi(k) estimates exp(q.k / sqrt(d)); features run
        # in f32 (exp of differences — bf16 rounding hurts here)
        scale = self.dim_head ** 0.25
        q = (q / scale).astype(jnp.float32)
        k = (k / scale).astype(jnp.float32)

        if self.has_rng("performer"):
            feat_key = self.make_rng("performer")
        else:
            # deterministic fallback, distinct per module path (helps
            # unrolled trunks; a scanned trunk shares one module, so
            # per-layer independence there comes from supplying the
            # 'performer' rng — the train loop and predict.fold both do)
            import zlib
            path = "/".join(self.scope.path) if self.scope else ""
            feat_key = jax.random.PRNGKey(zlib.crc32(path.encode()))
        proj = orthogonal_random_features(feat_key, self.nb_features,
                                          self.dim_head)

        kmask = context_mask if context is not None else mask
        kmask4 = None if kmask is None else kmask[:, None, :]
        phi_q = favor_softmax_features(q, proj, is_query=True)
        phi_k = favor_softmax_features(k, proj, is_query=False,
                                       mask=kmask4)

        if kmask is not None:
            w = kmask[:, None, :, None]
            phi_k = phi_k * w
            v = v * w

        kv = jnp.einsum("bhnm,bhne->bhme", phi_k, v.astype(jnp.float32))
        z = jnp.einsum("bhnm,bhm->bhn", phi_q, phi_k.sum(-2))
        out = jnp.einsum("bhnm,bhme->bhne", phi_q, kv) / \
            jnp.maximum(z[..., None], 1e-6)
        out = out.astype(self.dtype)

        inner = self.heads * self.dim_head
        return attention_output_tail(dense, out, x, inner, self.gating,
                                     self.dim)


class MemoryCompressedAttention(nn.Module):
    """Standard attention with mean-pooled K/V (compression ratio r)."""

    dim: int
    heads: int = 8
    dim_head: int = 64
    compress_ratio: int = 2
    gating: bool = True
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True):
        dense = _dense_factory(self.dtype)
        q, k, v = _qkv(dense, x, x, self.heads, self.dim_head)
        r = self.compress_ratio
        b, h, n, d = k.shape
        pad = (-n) % r
        # always pool with real counts so zero padding never dilutes the
        # last block (mask=None behaves as an all-ones mask)
        m = mask if mask is not None else jnp.ones((b, n), dtype=bool)
        if pad:
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
            m = jnp.pad(m, ((0, 0), (0, pad)))
        w = m[:, None, :, None].astype(k.dtype)
        k = (k * w).reshape(b, h, -1, r, d).sum(3)
        v = (v * w).reshape(b, h, -1, r, d).sum(3)
        counts = w.reshape(b, 1, -1, r, 1).sum(3)
        k = k / jnp.maximum(counts, 1.0)
        v = v / jnp.maximum(counts, 1.0)
        kmask = jnp.broadcast_to((counts[..., 0] > 0)[:, :, None, :],
                                 (b, 1, 1, k.shape[2]))

        dots = jnp.einsum("bhid,bhjd->bhij", q * (d ** -0.5), k)
        dots = jnp.where(kmask, dots, MASK_VALUE)
        if mask is not None:
            dots = jnp.where(mask[:, None, :, None], dots, MASK_VALUE)
        attn = jnn.softmax(dots, axis=-1)
        attn = nn.Dropout(self.dropout)(attn, deterministic=deterministic)
        out = jnp.einsum("bhij,bhjd->bhid", attn, v)

        inner = self.heads * self.dim_head
        return attention_output_tail(dense, out, x, inner, self.gating,
                                     self.dim)


def kronecker_pool_2d(
    context: jnp.ndarray,
    context_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(b, H, W, d) pair map -> (b, H + W, d) axial-mean tokens: masked
    mean over columns (one token per row) concatenated with masked mean
    over rows (one token per column) — the Kronecker-structured O(H+W)
    context compression (reference README.md:451-468).

    context_mask: optional (b, H, W) validity. Returns (tokens, token_mask).
    """
    b, height, width, d = context.shape
    if context_mask is None:
        rows = context.mean(2)
        cols = context.mean(1)
        token_mask = jnp.ones((b, height + width), dtype=bool)
    else:
        w = context_mask[..., None].astype(context.dtype)
        rows = (context * w).sum(2) / jnp.maximum(w.sum(2), 1.0)
        cols = (context * w).sum(1) / jnp.maximum(w.sum(1), 1.0)
        token_mask = jnp.concatenate(
            [context_mask.any(2), context_mask.any(1)], axis=1)
    return jnp.concatenate([rows, cols], axis=1), token_mask


class KroneckerAttention(nn.Module):
    """Cross-attention from a 1-D stream onto the axial-pooled (H + W
    token) pair context."""

    dim: int
    heads: int = 8
    dim_head: int = 64
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, context_2d, mask=None, context_mask=None,
                 deterministic: bool = True):
        from alphafold2_tpu.model.primitives import Attention
        pooled, token_mask = kronecker_pool_2d(context_2d, context_mask)
        return Attention(dim=self.dim, heads=self.heads,
                         dim_head=self.dim_head, dropout=self.dropout,
                         dtype=self.dtype, name="attn")(
            x, mask=mask, context=pooled, context_mask=token_mask,
            deterministic=deterministic)


# README-era defaults (reference README.md:305-307): 1d+2d kernel mix
# for the (n, n) pair map and the (rows, n) MSA. The single source —
# EvoformerBlock/Evoformer/Alphafold2/RevEvoLayer all default to these.
DEFAULT_CONV_SEQ_KERNELS = ((9, 1), (1, 9), (3, 3))
DEFAULT_CONV_MSA_KERNELS = ((1, 9), (3, 3))


class MultiKernelConvBlock(nn.Module):
    """trRosetta2-style residual conv block (reference README.md:271-340
    `use_conv=True` + `conv_seq_kernels`/`conv_msa_kernels`/dilations —
    "combining 1d and 2d kernels in one resnet-like block"): parallel
    NHWC 2-D convolutions with per-branch kernel shapes x dilations over
    the two spatial axes, averaged, gelu, then a zero-init output
    projection (the package's residual-branch convention — the block is
    an identity at init). The caller adds the residual.

    TPU-first deviations from the README-era design: NHWC layout (XLA's
    native conv layout on TPU — no transposes around the MXU) and the
    dilation cycle applied WITHIN the block (one branch per kernel x
    dilation) instead of varying per layer: the trunk runs under
    `nn.scan`, which requires every layer to share one static config,
    and in-block multi-dilation preserves the mixed receptive fields the
    cycle existed to provide.

    Masking: invalid spatial positions are zeroed BEFORE the convs so
    padding never leaks into valid cells through the kernel window.
    """

    dim: int
    kernels: Tuple[Tuple[int, int], ...] = ((3, 3),)
    dilations: Tuple[int, ...] = (1,)
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask=None):
        h = LayerNorm(dtype=self.dtype)(x)
        if mask is not None:
            h = h * mask[..., None].astype(h.dtype)
        branches = []
        for kh, kw in self.kernels:
            for d in self.dilations:
                branches.append(nn.Conv(
                    features=self.dim, kernel_size=(kh, kw),
                    kernel_dilation=(d, d), padding="SAME",
                    dtype=self.dtype, param_dtype=jnp.float32,
                    name=f"conv_{kh}x{kw}_d{d}")(h))
        h = jnn.gelu(sum(branches) / len(branches))
        out = nn.Dense(self.dim, kernel_init=zeros_init(),
                       bias_init=zeros_init(), dtype=self.dtype,
                       param_dtype=jnp.float32, name="proj_out")(h)
        if mask is not None:
            out = out * mask[..., None].astype(out.dtype)
        return out


def block_sparse_block_pattern(n_blocks: int, num_global: int = 1,
                               window: int = 1):
    """(n_blocks, n_blocks) bool numpy block pattern: attend within
    +-`window` blocks of the diagonal plus the first `num_global` blocks
    (global tokens). Delegates to `ops.block_sparse.
    banded_block_pattern` — the ONE local+global source the dense mask
    below and the Pallas kernel plan share, so the two cannot diverge."""
    from alphafold2_tpu.ops.block_sparse import banded_block_pattern
    return banded_block_pattern(n_blocks, window=window,
                                num_global=num_global)


def block_sparse_mask(n: int, block: int = 32, num_global: int = 1,
                      window: int = 1) -> jnp.ndarray:
    """(n, n) bool token mask expanded from `block_sparse_block_pattern`
    (handles a trailing partial block when n % block != 0)."""
    nb = -(-n // block)
    bp = jnp.asarray(block_sparse_block_pattern(nb, num_global, window))
    bi = jnp.arange(n) // block
    return bp[bi[:, None], bi[None, :]]


class BlockSparseAttention(nn.Module):
    """Self-attention restricted to a fixed block-sparse pattern (the
    DeepSpeed sparse-attention analog, reference README.md:388-417).

    Two compute backends behind ONE params tree (the projections and
    gated output tail live in the inner `Attention`, shared by both):

    - the true block-skipping Pallas kernel
      (`ops.block_sparse.block_sparse_attention`, FLOPs ∝ nnz blocks):
      the DEFAULT on a TPU backend whenever n divides into `block`s
      (ISSUE 12 — the documented sparse config must actually skip
      FLOPs, not just mask them); off-TPU it is opt-in via
      `ops.use_pallas_attention(True)` (interpreter mode, exactness
      tests only);
    - dense + additive mask: the CPU fallback (and the dropout-active
      training path) — identical attention support, no FLOP skipping.

    Token masks ride into the kernel as per-key validity (replayed
    across the folded head axis); masked-query rows are unspecified on
    both backends. Exactness between the backends:
    tests/test_ops.py::TestBlockSparseKernel.
    """

    dim: int
    heads: int = 8
    dim_head: int = 64
    block: int = 32
    num_global: int = 1
    window: int = 1
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32

    def _kernel_available(self) -> bool:
        """True when the FLOP-skipping Pallas kernel should serve this
        trace: on a TPU backend it is ALWAYS preferred (ISSUE 12 — the
        old gate made the documented sparse_self_attn config silently
        pay dense N^2 compute unless the unrelated fused-attention
        flag was flipped); off-TPU it stays opt-in via
        `ops.use_pallas_attention(True)` (interpreter mode — exactness
        tests), so CPU tier-1 keeps the cheap masked-dense fallback."""
        from alphafold2_tpu.ops.attention import pallas_attention_enabled
        return on_tpu() or pallas_attention_enabled()

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True):
        from alphafold2_tpu.model.primitives import Attention
        n = x.shape[-2]
        attn = Attention(dim=self.dim, heads=self.heads,
                         dim_head=self.dim_head, dropout=self.dropout,
                         dtype=self.dtype, name="attn")

        use_kernel = self._kernel_available() and n % self.block == 0
        if use_kernel and \
                not (self.dropout == 0.0 or deterministic):
            # refuse-to-be-silent: the Pallas kernel has no dropout, so a
            # dropout-active training trace pays full dense n^2 attention
            import warnings
            warnings.warn(
                "BlockSparseAttention: dropout>0 under training falls "
                "back to DENSE masked attention (the Pallas block-"
                "skipping kernel has no dropout) — the sparse FLOP "
                "savings do not apply to these steps", RuntimeWarning,
                stacklevel=2)
        if use_kernel and (self.dropout == 0.0 or deterministic):
            from alphafold2_tpu.ops.block_sparse import (
                block_sparse_attention)
            block_pattern = block_sparse_block_pattern(
                n // self.block, self.num_global, self.window)
            q, k, v = attn.project_qkv(x)          # (b, h, n, dh), q scaled
            b, h, _, dh = q.shape
            out = block_sparse_attention(
                q.reshape(b * h, n, dh), k.reshape(b * h, n, dh),
                v.reshape(b * h, n, dh), block_pattern,
                k_mask=mask,                       # unrepeated; index map
                heads=h,                           # replays across heads
                scale=1.0,                         # project_qkv pre-scales
                block=self.block,
                interpret=not on_tpu())
            return attn.finish(out.reshape(b, h, n, dh), x)

        pattern = block_sparse_mask(n, self.block, self.num_global,
                                    self.window)
        bias = jnp.where(pattern, 0.0, MASK_VALUE)[None, None]
        return attn(x, mask=mask, attn_bias=bias,
                    deterministic=deterministic)
