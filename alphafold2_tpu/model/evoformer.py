"""Evoformer trunk: MSA/pair blocks and the scanned, rematerialized stack.

Parity with the reference (/root/reference/alphafold2_pytorch/alphafold2.py:
353-467): `PairwiseAttentionBlock` (outer-mean ingest + triangle mult out/in +
triangle attention out/in), `MsaAttentionBlock` (row attn with pair bias, col
attn), `EvoformerBlock` (msa attn -> msa FF -> pair attn -> pair FF, all
residual), `Evoformer` = depth x block.

TPU-first: instead of the reference's `checkpoint_sequential` (alphafold2.py:
466), the stack runs under `nn.scan` over depth with per-layer remat
(`nn.remat`): constant compile time at depth 48. The scan stacks every
block's carry (the pair and MSA tensors) for the backward pass, which makes
each block's interior again, except what `remat_names` chooses to keep from
the forward pass as well (`remat_block`'s policy, by name: the attention
kernels' outputs first): values dear to make again, as many as the shapes,
the depth and the device's memory allow; none where the trace sees no device
memory (the CPU) or a mesh of several devices. Pair/MSA activations carry
sharding constraints so the stack runs identically under a pjit mesh (see
alphafold2_tpu/parallel).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import linen as nn

from alphafold2_tpu import runtime
from alphafold2_tpu.model.attention_variants import (
    DEFAULT_CONV_MSA_KERNELS,
    DEFAULT_CONV_SEQ_KERNELS,
    MultiKernelConvBlock,
)
from alphafold2_tpu.model.primitives import (
    KEPT_ATTENTION,
    KEPT_ATTENTION_OUT,
    AxialAttention,
    FeedForward,
    OuterMean,
    TriangleMultiplicativeModule,
)
from alphafold2_tpu.parallel.mesh import PAIR_I_AXIS, PAIR_J_AXIS
from alphafold2_tpu.parallel.sharding import shard_msa, shard_pair


class PairwiseAttentionBlock(nn.Module):
    """Pair-track block (reference alphafold2.py:353-385).

    `ring_attention=True` runs the two triangle attentions ring-parallel
    over the sharded pair axes when an active mesh shards them
    (AxialAttention.ring_axes; parallel/ring.py) — the long-context mode.
    """

    dim: int
    heads: int
    dim_head: int = 64
    dropout: float = 0.0
    global_column_attn: bool = False
    ring_attention: bool = False
    outer_mean_reference_scale: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask=None, msa_repr=None, msa_mask=None,
                 deterministic: bool = True):
        ring_axes = (PAIR_I_AXIS, PAIR_J_AXIS) if self.ring_attention \
            else None
        if msa_repr is not None:
            x = x + OuterMean(dim=self.dim, dtype=self.dtype,
                              reference_scale=self.outer_mean_reference_scale,
                              name="outer_mean")(msa_repr, mask=msa_mask)
            x = shard_pair(x)

        x = TriangleMultiplicativeModule(
            dim=self.dim, mix="outgoing", dtype=self.dtype,
            name="triangle_multiply_outgoing")(x, mask=mask, residual=x)
        x = TriangleMultiplicativeModule(
            dim=self.dim, mix="ingoing", dtype=self.dtype,
            name="triangle_multiply_ingoing")(x, mask=mask, residual=x)
        x = shard_pair(x)
        x = AxialAttention(
            dim=self.dim, heads=self.heads, dim_head=self.dim_head,
            row_attn=True, col_attn=False, accept_edges=True,
            dropout=self.dropout, ring_axes=ring_axes,
            dtype=self.dtype, name="triangle_attention_outgoing",
        )(x, edges=x, mask=mask, deterministic=deterministic) + x
        x = AxialAttention(
            dim=self.dim, heads=self.heads, dim_head=self.dim_head,
            row_attn=False, col_attn=True, accept_edges=True,
            global_query_attn=self.global_column_attn,
            dropout=self.dropout, ring_axes=ring_axes,
            dtype=self.dtype, name="triangle_attention_ingoing",
        )(x, edges=x, mask=mask, deterministic=deterministic) + x
        return shard_pair(x)


class MsaAttentionBlock(nn.Module):
    """MSA-track block (reference alphafold2.py:387-408).

    `ring_attention=True` runs the row attention (per-alignment attention
    over the residue axis, which `shard_msa` shards over the `i` mesh
    axis) ring-parallel instead of letting GSPMD all-gather the full
    residue axis (round-2 VERDICT next-round #5). Column attention is
    over the alignment axis, which is never mesh-sharded — dense there.

    `row_variant` swaps the residue-axis row attention for one of the
    README-era efficient variants (reference README.md:388-487 — there
    they applied to the pre-Evoformer sequence/MSA self- and cross-
    attention; here the residue axis is where the O(n^2) pressure lives):

    - "full"     — pair-biased axial attention (the default Evoformer row
                   attention; the only variant that consumes pair edges);
    - "sparse"   — `BlockSparseAttention` local+global block pattern (the
                   DeepSpeed sparse-self-attn analog, README.md:388-417;
                   dispatches to the Pallas block-skipping kernel on TPU
                   by default — `ops.use_pallas_attention(True)` opts in
                   the interpreter-mode kernel off-TPU, otherwise CPU
                   keeps the masked-dense fallback);
    - "linear"   — kernelized linear attention (Performer slot,
                   README.md:419-449);
    - "compress" — memory-compressed attention, K/V mean-pooled by
                   `kv_compress_ratio` (README.md:475-487);
    - "kron"     — cross-attention onto the axial-pooled (H+W token) pair
                   map (README.md:451-468's Kronecker operator, re-aimed
                   at the Evoformer's pair context).

    The non-full variants do not take the pair-edge bias — matching the
    README-era modules, which had no pair track to be biased by.
    """

    dim: int
    heads: int
    dim_head: int = 64
    dropout: float = 0.0
    ring_attention: bool = False
    row_variant: str = "full"
    sparse_block: int = 32
    sparse_num_global: int = 1
    sparse_window: int = 1
    kv_compress_ratio: int = 2
    # "linear" row variant backend: "favor" = FAVOR+ Performer (unbiased
    # softmax approximation, the reference's cross_attn_linear), "elu" =
    # the cheap deterministic elu+1 kernel
    linear_attn_kind: str = "favor"
    performer_nb_features: int = 256
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask=None, pairwise_repr=None, pair_mask=None,
                 deterministic: bool = True):
        if self.row_variant == "full":
            x = AxialAttention(
                dim=self.dim, heads=self.heads, dim_head=self.dim_head,
                row_attn=True, col_attn=False, accept_edges=True,
                dropout=self.dropout,
                ring_axes=(None, PAIR_I_AXIS) if self.ring_attention
                else None,
                dtype=self.dtype, name="row_attn",
            )(x, mask=mask, edges=pairwise_repr,
              deterministic=deterministic) + x
        else:
            x = self._row_variant_attn(x, mask, pairwise_repr, pair_mask,
                                       deterministic) + x
        x = AxialAttention(
            dim=self.dim, heads=self.heads, dim_head=self.dim_head,
            row_attn=False, col_attn=True, dropout=self.dropout,
            dtype=self.dtype, name="col_attn",
        )(x, mask=mask, deterministic=deterministic) + x
        return shard_msa(x)

    def _row_variant_attn(self, x, mask, pairwise_repr, pair_mask,
                          deterministic=True):
        """Residue-axis attention via an efficient variant: alignment rows
        fold into batch (as AxialAttention does), pre-LN applied here (the
        variants are bare attention modules; AxialAttention normalizes
        internally). `dropout` reaches the softmax-matrix variants
        (sparse/compress/kron); the linear variants have no attention
        matrix to drop entries from (performer-pytorch likewise)."""
        from alphafold2_tpu.model.attention_variants import (
            BlockSparseAttention,
            LinearAttention,
            MemoryCompressedAttention,
            kronecker_pool_2d,
        )
        from alphafold2_tpu.model.primitives import Attention, LayerNorm

        b, rows, n, d = x.shape
        h = LayerNorm(dtype=self.dtype, name="row_norm")(x)
        hf = h.reshape(b * rows, n, d)
        mf = None if mask is None else mask.reshape(b * rows, n)
        kw = dict(dim=self.dim, heads=self.heads, dim_head=self.dim_head,
                  dtype=self.dtype, name="row_attn")

        if self.row_variant == "sparse":
            out = BlockSparseAttention(
                block=self.sparse_block, num_global=self.sparse_num_global,
                window=self.sparse_window, dropout=self.dropout, **kw)(
                    hf, mask=mf, deterministic=deterministic)
        elif self.row_variant == "linear":
            if self.linear_attn_kind == "favor":
                from alphafold2_tpu.model.attention_variants import (
                    PerformerAttention)
                out = PerformerAttention(
                    nb_features=self.performer_nb_features, **kw)(
                        hf, mask=mf)
            else:
                out = LinearAttention(**kw)(hf, mask=mf)
        elif self.row_variant == "compress":
            out = MemoryCompressedAttention(
                compress_ratio=self.kv_compress_ratio,
                dropout=self.dropout, **kw)(
                    hf, mask=mf, deterministic=deterministic)
        elif self.row_variant == "kron":
            assert pairwise_repr is not None, \
                "row_variant='kron' needs the pair representation"
            pooled, tmask = kronecker_pool_2d(pairwise_repr, pair_mask)
            # one pooled context per batch item, shared by its alignment
            # rows (repeat matches the row-major fold of x above)
            pooled = jnp.repeat(pooled, rows, axis=0)
            tmask = jnp.repeat(tmask, rows, axis=0)
            if mf is None:
                # Attention only honors context_mask alongside a query
                # mask; synthesize all-ones so padded pooled tokens are
                # still excluded when msa_mask is absent
                mf = jnp.ones((b * rows, n), dtype=bool)
            out = Attention(dropout=self.dropout, **kw)(
                hf, mask=mf, context=pooled, context_mask=tmask,
                deterministic=deterministic)
        else:
            raise ValueError(f"unknown row_variant {self.row_variant!r}")
        return out.reshape(b, rows, n, d)


class EvoformerBlock(nn.Module):
    """One Evoformer layer (reference alphafold2.py:412-446).

    `use_conv=True` appends trRosetta2-style residual conv blocks to both
    tracks (the README-era `use_conv` menu item, README.md:271-340):
    `conv_seq_kernels` over the (n, n) pair map, `conv_msa_kernels` over
    the (rows, n) MSA, with the dilation cycle applied in-block
    (attention_variants.MultiKernelConvBlock documents the TPU-first
    deviations)."""

    dim: int
    heads: int
    dim_head: int = 64
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    global_column_attn: bool = False
    ring_attention: bool = False
    outer_mean_reference_scale: bool = False
    use_conv: bool = False
    conv_seq_kernels: tuple = DEFAULT_CONV_SEQ_KERNELS
    conv_msa_kernels: tuple = DEFAULT_CONV_MSA_KERNELS
    conv_dilations: tuple = (1,)
    # README-era efficient-attention menu for the MSA row track
    # (MsaAttentionBlock.row_variant documents the options)
    msa_row_variant: str = "full"
    sparse_block: int = 32
    sparse_num_global: int = 1
    sparse_window: int = 1
    kv_compress_ratio: int = 2
    linear_attn_kind: str = "favor"
    performer_nb_features: int = 256
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, m, mask=None, msa_mask=None,
                 deterministic: bool = True):
        # msa attention and transition
        m = MsaAttentionBlock(
            dim=self.dim, heads=self.heads, dim_head=self.dim_head,
            dropout=self.attn_dropout, ring_attention=self.ring_attention,
            row_variant=self.msa_row_variant,
            sparse_block=self.sparse_block,
            sparse_num_global=self.sparse_num_global,
            sparse_window=self.sparse_window,
            kv_compress_ratio=self.kv_compress_ratio,
            linear_attn_kind=self.linear_attn_kind,
            performer_nb_features=self.performer_nb_features,
            dtype=self.dtype, name="msa_attn",
        )(m, mask=msa_mask, pairwise_repr=x, pair_mask=mask,
          deterministic=deterministic)
        m = FeedForward(dim=self.dim, dropout=self.ff_dropout,
                        dtype=self.dtype, name="msa_ff")(
                            m, deterministic=deterministic) + m
        if self.use_conv:
            m = MultiKernelConvBlock(
                dim=self.dim, kernels=self.conv_msa_kernels,
                dilations=self.conv_dilations, dtype=self.dtype,
                name="msa_conv")(m, mask=msa_mask) + m

        # pairwise attention (ingesting the updated MSA) and transition
        x = PairwiseAttentionBlock(
            dim=self.dim, heads=self.heads, dim_head=self.dim_head,
            dropout=self.attn_dropout,
            global_column_attn=self.global_column_attn,
            ring_attention=self.ring_attention,
            outer_mean_reference_scale=self.outer_mean_reference_scale,
            dtype=self.dtype, name="attn",
        )(x, mask=mask, msa_repr=m, msa_mask=msa_mask,
          deterministic=deterministic)
        x = FeedForward(dim=self.dim, dropout=self.ff_dropout,
                        dtype=self.dtype, name="ff")(
                            x, deterministic=deterministic) + x
        if self.use_conv:
            x = MultiKernelConvBlock(
                dim=self.dim, kernels=self.conv_seq_kernels,
                dilations=self.conv_dilations, dtype=self.dtype,
                name="pair_conv")(x, mask=mask) + x

        return x, m


def remat_block(names=(), block=None, static_argnums=(5,),
                prevent_cse=False):
    """`EvoformerBlock` rematerialised, keeping the marked values `names`
    from the forward pass (none: the backward makes the whole block again).
    The scanned trunk and the pipeline both build their block here; the token
    decoder hands in its own layer (`block`, unrolled: `prevent_cse`)."""
    policy = jax.checkpoint_policies.save_only_these_names(*names) \
        if names else None
    return nn.remat(block or EvoformerBlock, static_argnums=static_argnums,
                    prevent_cse=prevent_cse, policy=policy)


def device_bytes_limit():
    """The memory of the device the trace is made for, as it reports it (a
    v5e: 15.75 GiB), or None where the trace cannot see one: off the TPU, and
    on a backend that reports no `bytes_limit`."""
    if not runtime.on_tpu():
        return None
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


GIB = 2 ** 30
# What `remat_names` leaves of the device's limit: 1.5 GiB free, whatever it
# keeps, and 1 GiB for what a training process holds beside the step's own
# program (the benchmark's: 0.82 GiB over the compiler's peak with every set
# tried, my chip runs, PR 34).
REMAT_HEADROOM = 2.5 * GIB


def remat_name_bytes(pair_shape, msa_shape, dtype, inner):
    """((name, bytes a block keeps of it), ...) in the order `remat_names`
    takes them, dearest to make again per byte first: both triangle and both
    MSA attentions' kernel outputs (`inner` = heads x dim_head wide), then
    their `to_out` outputs. Of the crop-256 step's 475.7 ms the first buys
    20.4 for 192 MiB a block and the second 8.2 more for 96 (my chip runs,
    PR 34; PERF.md section 6 has the names that cost more than they saved)."""
    itemsize = jnp.dtype(dtype).itemsize
    positions = math.prod(pair_shape[:-1]) + math.prod(msa_shape[:-1])
    return ((KEPT_ATTENTION, 2 * positions * inner * itemsize),
            (KEPT_ATTENTION_OUT, 2 * positions * pair_shape[-1] * itemsize))


def remat_names(pair_shape, msa_shape, depth, dtype, bytes_limit, *,
                inner, param_bytes=0):
    """Which marked values of a block the scanned trunk's backward keeps from
    the forward pass instead of making them again: a function of the shapes,
    the depth, the dtype, the device's memory and the active mesh alone.

    The names are taken in `remat_name_bytes`' order, each while its bytes x
    (depth + 1) still fit under `bytes_limit` less `REMAT_HEADROOM` less what
    the step needs with nothing kept (`_need_with_nothing_kept`;
    `param_bytes`: the trunk's parameters). Without a limit
    (`device_bytes_limit`: the CPU, a described topology) or under a mesh of
    more than one device nothing is kept, and the program is the one
    `nn.remat` without a policy gives.
    """
    from alphafold2_tpu.parallel.sharding import active_mesh

    mesh = active_mesh()
    if bytes_limit is None or (mesh is not None and mesh.size > 1):
        return ()
    itemsize = jnp.dtype(dtype).itemsize
    room = bytes_limit - REMAT_HEADROOM - _need_with_nothing_kept(
        math.prod(pair_shape) * itemsize, math.prod(msa_shape) * itemsize,
        depth, param_bytes)
    names = []
    for name, block_bytes in remat_name_bytes(pair_shape, msa_shape, dtype,
                                              inner):
        # every block's in the scan's stack, and the one the backward reads
        room -= block_bytes * (depth + 1)
        if room < 0:
            break
        names.append(name)
    return tuple(names)


def _need_with_nothing_kept(pair_bytes, msa_bytes, depth, param_bytes):
    """The bytes a training step holds at its peak with nothing kept, state
    and all, reckoned from the pair and MSA tensors' bytes, the depth and the
    trunk's parameter bytes. Calibrated on what the v5e's compiler reports
    (`memory_analysis().peak_memory_in_bytes`, what it holds against the
    limit itself; Adam, batch 1, MSA 128, dim 256, bf16; PERF.md section 6,
    PR 34) for crop / depth 256 / 12: 4.589 GiB, 256 / 24: 6.196, 256 / 48:
    9.414, 384 / 12: 8.073, 384 / 48: 14.585, which this reproduces to 0.01:

    - a layer costs its carry (the pair and MSA tensors the scan stacks) and
      4.5 times its parameters (Adam's two moments, the parameters, their
      gradient, a bf16 copy): 0.134 and 0.181 GiB a layer at 256 and 384;
    - one block's backward, whatever the depth: 70.4 pair tensors and 21.8
      MSA tensors (2.54 and 5.46 GiB);
    - 0.44 GiB for the rest of that model (embeddings, heads, the structure
      module, the extra-MSA stack and their state), which the trunk cannot
      see: taken as it is.
    """
    return (70.4 * pair_bytes + 21.8 * msa_bytes + 0.44 * GIB
            + depth * (pair_bytes + msa_bytes) + 4.5 * param_bytes)


class Evoformer(nn.Module):
    """depth x EvoformerBlock under scan + remat (reference alphafold2.py:
    448-467; memory scaling via checkpoint_sequential there, jax.remat with
    `remat_names`' policy here).
    """

    dim: int
    depth: int
    heads: int = 8
    dim_head: int = 64
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    global_column_attn: bool = False
    ring_attention: bool = False
    outer_mean_reference_scale: bool = False
    use_conv: bool = False
    conv_seq_kernels: tuple = DEFAULT_CONV_SEQ_KERNELS
    conv_msa_kernels: tuple = DEFAULT_CONV_MSA_KERNELS
    conv_dilations: tuple = (1,)
    # README-era efficient-attention menu (reference README.md:388-487),
    # applied to the MSA row track (MsaAttentionBlock.row_variant). Each
    # flag is a bool (all layers) or a per-layer tuple of bools — e.g.
    # `sparse_self_attn=(True, False) * 3` interleaves sparse and full
    # layers (README.md:415). `kv_compress_ratio` is 0 (off) or the pool
    # ratio (README.md:485), scalar or per-layer. At most one variant may
    # be on per layer. Per-layer-heterogeneous menus run the unrolled
    # trunk (nn.scan needs layer-uniform params; the README-era reference
    # was an unrolled torch stack too) and are incompatible with
    # `pipeline_stages`/`reversible`, which regroup scan-stacked params.
    sparse_self_attn: "bool | tuple" = False
    linear_attn: "bool | tuple" = False
    kron_attn: "bool | tuple" = False
    kv_compress_ratio: "int | tuple" = 0
    sparse_block: int = 32
    sparse_num_global: int = 1
    sparse_window: int = 1
    linear_attn_kind: str = "favor"
    performer_nb_features: int = 256
    dtype: jnp.dtype = jnp.float32
    use_scan: bool = True
    # O(1)-activation reversible trunk (model/reversible.py; reference
    # README.md:40 `reversible=True`, reversible.py)
    reversible: bool = False
    # GPipe pipeline parallelism over the mesh's `pipe` axis
    # (parallel/pipeline.py): the depth-stacked scan params are regrouped
    # into S stages of depth/S layers and the trunk runs the static skew
    # schedule, microbatching the batch axis. Params are IDENTICAL to the
    # scanned trunk (the pp path re-reads the scan's stacked params), so
    # checkpoints move freely between pp and non-pp runs.
    pipeline_stages: int = 1
    pipeline_microbatches: int = 0   # 0 -> one microbatch per batch row

    def _row_variants(self):
        """Per-layer MSA-row attention variants + compress ratios.

        Returns (variants, ratios): depth-length tuples of variant names
        and kv-pool ratios, validated to at most one variant per layer."""
        def flags(v, label):
            if isinstance(v, (tuple, list)):
                assert len(v) == self.depth, \
                    f"{label} tuple has {len(v)} entries for depth " \
                    f"{self.depth}"
                return tuple(bool(b) for b in v)
            return (bool(v),) * self.depth

        sp = flags(self.sparse_self_attn, "sparse_self_attn")
        li = flags(self.linear_attn, "linear_attn")
        kr = flags(self.kron_attn, "kron_attn")
        cr = self.kv_compress_ratio
        if isinstance(cr, (tuple, list)):
            assert len(cr) == self.depth, \
                f"kv_compress_ratio tuple has {len(cr)} entries for " \
                f"depth {self.depth}"
            cr = tuple(int(c) for c in cr)
        else:
            cr = (int(cr),) * self.depth

        variants = []
        for i in range(self.depth):
            picks = [name for name, on in (
                ("sparse", sp[i]), ("linear", li[i]), ("kron", kr[i]),
                ("compress", cr[i] > 0)) if on]
            assert len(picks) <= 1, \
                f"layer {i}: conflicting attention variants {picks} — " \
                "at most one of sparse_self_attn/linear_attn/kron_attn/" \
                "kv_compress_ratio per layer"
            variants.append(picks[0] if picks else "full")
        return tuple(variants), cr

    def _kept_names(self, x, m):
        """`remat_names` for this trunk at this trace's shapes."""
        params = self.variables.get("params", {})
        return remat_names(
            x.shape, m.shape, self.depth, self.dtype, device_bytes_limit(),
            inner=self.heads * self.dim_head,
            param_bytes=sum(p.size * p.dtype.itemsize
                            for p in jax.tree.leaves(params)))

    def _pipeline_ready(self, deterministic):
        """The active mesh if the pipeline path applies, else None."""
        from alphafold2_tpu.parallel.sharding import active_mesh
        from alphafold2_tpu.parallel.mesh import PIPE_AXIS

        if self.pipeline_stages <= 1:
            return None
        mesh = active_mesh()
        if mesh is None or PIPE_AXIS not in mesh.axis_names:
            return None
        if mesh.shape[PIPE_AXIS] != self.pipeline_stages:
            raise ValueError(
                f"pipeline_stages={self.pipeline_stages} but mesh "
                f"'{PIPE_AXIS}' axis has {mesh.shape[PIPE_AXIS]} devices")
        if self.depth % self.pipeline_stages:
            raise ValueError(
                f"depth {self.depth} not divisible into "
                f"{self.pipeline_stages} pipeline stages")
        return mesh

    def _pipeline_forward(self, mesh, block_kwargs, x, m, mask,
                          msa_mask, deterministic=True):
        """GPipe over the scan-stacked layer params (parallel/pipeline.py).

        Stage s applies layers [s*depth/S, (s+1)*depth/S) — a lax.scan
        over its (depth/S, ...) param slice with per-block remat, the same
        compute as the nn.scan path. Activations (x, m) plus the masks
        ride the pipeline as one microbatched tree; masks pass through
        stages unchanged. The pipeline's shard_map is manual ONLY over
        the `pipe`/`data` axes; the mesh's `i`/`j` axes stay auto, so the
        in-model GSPMD constraints (shard_pair/shard_msa) keep 2-D
        sharding the pair tensor INSIDE each stage — pp composes with
        both dp (microbatch batch dim over `data`) and the pair sharding
        that makes flagship crops fit (VERDICT r4 #4; the constraint
        specs drop the manual axis names via use_mesh's manual_axes).
        """
        import jax

        from alphafold2_tpu.parallel.mesh import DATA_AXIS, PIPE_AXIS
        from alphafold2_tpu.parallel.pipeline import (microbatch,
                                                      pipeline_apply,
                                                      unmicrobatch)
        from alphafold2_tpu.parallel.sharding import use_mesh

        s_count = self.pipeline_stages
        depth_per = self.depth // s_count
        # dropout: one base key; each (microbatch, global layer) derives
        # its mask key by fold_in, so the schedule's recomputations (none
        # in GPipe) and the backward replay see identical masks. Keys ride
        # the pipeline as RAW uint32 key data — a plain array leaf that
        # ppermute/where/zeros_like handle like any activation.
        has_dropout = (self.attn_dropout > 0.0 or self.ff_dropout > 0.0) \
            and not deterministic
        base_key = self.make_rng("dropout") if has_dropout else None
        b, n = x.shape[0], x.shape[1]
        if self.pipeline_microbatches:
            m_count = self.pipeline_microbatches
        else:
            # default: the most microbatches whose per-microbatch batch
            # dim still tiles over the data axis — pp x dp stays real
            # (m_count=b would leave batch-1 microbatches that cannot
            # shard, silently replicating across the data devices)
            data_n = mesh.shape.get(DATA_AXIS, 1)
            m_count = b // data_n if (data_n > 1 and b % data_n == 0) \
                else b
        if b % m_count:
            raise ValueError(f"batch {b} not divisible into {m_count} "
                             "microbatches")

        params = self.get_variable("params", "layers")
        stacked = jax.tree.map(
            lambda p: p.reshape(s_count, depth_per, *p.shape[1:]), params)

        # bf16 under the pipeline is TPU-only: on XLA:CPU the partial-auto
        # lowering emits `psum_invariant` all-reduces whose reduction body
        # has a ROOT copy, and the CPU-only AllReducePromotion pass
        # crashes cloning those in bf16 ("Invalid binary instruction
        # opcode copy", r05). CPU also merely emulates bf16 in f32, so
        # widening to f32 there is strictly better; on TPU the promotion
        # pass does not exist and both the configured block dtype and the
        # activation dtype pass through untouched (no casts, numerics
        # identical to the scan path).
        act_dtype = x.dtype
        on_cpu = jax.default_backend() == "cpu"
        stage_kwargs = dict(block_kwargs)
        if on_cpu and stage_kwargs.get("dtype") == jnp.bfloat16:
            stage_kwargs["dtype"] = jnp.float32
        boundary_dtype = jnp.float32 \
            if (on_cpu and act_dtype == jnp.bfloat16) else act_dtype

        block = remat_block(self._kept_names(x, m))(**stage_kwargs,
                                                    parent=None)

        def stage_fn(stage_params, act):
            xi, mi, pmask, mmask = act[:4]
            bmask, bmsa = pmask > 0.5, mmask > 0.5
            if has_dropout:
                mb_key = jax.random.wrap_key_data(act[4][0])
                s_idx = jax.lax.axis_index(PIPE_AXIS)

            def body(carry, pj):
                p, j = pj
                xi, mi = carry
                # in-stage constraints stay LIVE for the auto (i, j)
                # axes; pipe/data are manual in the enclosing shard_map
                # and get dropped from the specs
                with use_mesh(mesh, manual_axes=frozenset(
                        {PIPE_AXIS, DATA_AXIS})):
                    if has_dropout:
                        lk = jax.random.fold_in(
                            mb_key, s_idx * depth_per + j)
                        xi, mi = block.apply(
                            {"params": p["block"]}, xi, mi, bmask, bmsa,
                            False, rngs={"dropout": lk})
                    else:
                        xi, mi = block.apply({"params": p["block"]}, xi,
                                             mi, bmask, bmsa, True)
                return (xi, mi), None

            (xi, mi), _ = jax.lax.scan(
                body, (xi, mi), (stage_params, jnp.arange(depth_per)))
            return (xi.astype(boundary_dtype), mi.astype(boundary_dtype),
                    pmask, mmask) + act[4:]

        # masks ride as float tensors (one activation tree, one dtype
        # rule per leaf); materialized when absent so the tree is static
        pmask = jnp.ones((b, n, n), jnp.float32) if mask is None else \
            mask.astype(jnp.float32)
        mmask = jnp.ones(m.shape[:3], jnp.float32) if msa_mask is None \
            else msa_mask.astype(jnp.float32)
        xs = jax.tree.map(lambda t: microbatch(t, m_count),
                          (x.astype(boundary_dtype),
                           m.astype(boundary_dtype), pmask, mmask))
        if has_dropout:
            mb_keys = jax.vmap(lambda i: jax.random.key_data(
                jax.random.fold_in(base_key, i)))(jnp.arange(m_count))
            xs = xs + (mb_keys[:, None],)   # (M, 1, key_words)
        out = pipeline_apply(stage_fn, stacked, xs, mesh,
                             data_axis=DATA_AXIS)
        x = unmicrobatch(out[0]).astype(act_dtype)
        m = unmicrobatch(out[1]).astype(act_dtype)
        return x, m

    @nn.compact
    def __call__(self, x, m, mask=None, msa_mask=None,
                 deterministic: bool = True):
        variants, ratios = self._row_variants()
        uniform = len(set(variants)) == 1 and len(set(ratios)) == 1
        if not uniform or variants[0] != "full":
            assert self.pipeline_stages <= 1 and not self.reversible, \
                "the efficient-attention menu is not supported with " \
                "pipeline_stages>1 or reversible=True"
            # refuse-rather-than-silently-drop: the variant row attention
            # does not ring-parallelize; ring_attention would silently
            # all-gather the residue axis it was enabled to keep sharded
            assert not self.ring_attention, \
                "the efficient-attention menu is not supported with " \
                "ring_attention=True (the variant row track is not " \
                "ring-parallel)"
        # refuse-rather-than-silently-drop: pp regroups the scan-stacked
        # params, so it needs the scanned trunk (and depth to stage over)
        if self.pipeline_stages > 1:
            assert not self.reversible, \
                "pipeline_stages>1 is not supported with the reversible " \
                "trunk (pp regroups the scan-stacked params)"
            assert self.use_scan and self.depth > 1, \
                "pipeline_stages>1 requires use_scan=True and depth>1"
        if self.reversible:
            # refuse (rather than silently drop) the OuterMean reference-
            # scaling flag: the reversible blocks construct their own
            # PairwiseAttentionBlock without it
            assert not self.outer_mean_reference_scale, \
                "reversible trunk does not support " \
                "outer_mean_reference_scale yet"
            from alphafold2_tpu.model.reversible import ReversibleEvoformer
            return ReversibleEvoformer(
                dim=self.dim, depth=self.depth, heads=self.heads,
                dim_head=self.dim_head,
                global_column_attn=self.global_column_attn,
                ring_attention=self.ring_attention,
                use_conv=self.use_conv,
                conv_seq_kernels=self.conv_seq_kernels,
                conv_msa_kernels=self.conv_msa_kernels,
                conv_dilations=self.conv_dilations,
                attn_dropout=self.attn_dropout,
                ff_dropout=self.ff_dropout,
                dtype=self.dtype, name="rev")(
                    x, m, mask=mask, msa_mask=msa_mask,
                    deterministic=deterministic)

        block_kwargs = dict(
            dim=self.dim, heads=self.heads, dim_head=self.dim_head,
            attn_dropout=self.attn_dropout, ff_dropout=self.ff_dropout,
            global_column_attn=self.global_column_attn,
            ring_attention=self.ring_attention,
            outer_mean_reference_scale=self.outer_mean_reference_scale,
            use_conv=self.use_conv,
            conv_seq_kernels=self.conv_seq_kernels,
            conv_msa_kernels=self.conv_msa_kernels,
            conv_dilations=self.conv_dilations,
            sparse_block=self.sparse_block,
            sparse_num_global=self.sparse_num_global,
            sparse_window=self.sparse_window,
            linear_attn_kind=self.linear_attn_kind,
            performer_nb_features=self.performer_nb_features,
            dtype=self.dtype,
        )
        if uniform:
            block_kwargs["msa_row_variant"] = variants[0]
            if ratios[0] > 0:
                block_kwargs["kv_compress_ratio"] = ratios[0]

        if self.use_scan and self.depth > 1 and uniform:
            pp = self._pipeline_ready(deterministic)
            if pp is not None and not self.is_initializing():
                # params were created by the scan path at init; regroup
                # the (depth, ...) stack into pp stages and run GPipe
                return self._pipeline_forward(
                    pp, block_kwargs, x, m, mask, msa_mask, deterministic)

            # remat each block, stack parameters along a scanned depth axis:
            # constant compile time; the backward holds one block's interior
            # and, of every block, its carry and what `remat_names` keeps.
            block_cls = remat_block(self._kept_names(x, m))

            class ScanBody(nn.Module):
                dtype: jnp.dtype = self.dtype

                @nn.compact
                def __call__(self, carry, _):
                    x, m = carry
                    x, m = block_cls(**block_kwargs, name="block")(
                        x, m, mask, msa_mask, deterministic)
                    return (x, m), None

            scan = nn.scan(
                ScanBody,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True,
                            "performer": True},
                length=self.depth,
            )
            (x, m), _ = scan(name="layers")((x, m), None)
        else:
            # unrolled trunk: per-layer configs are free here, so each
            # layer takes its own menu entry
            for i in range(self.depth):
                kw = dict(block_kwargs)
                kw["msa_row_variant"] = variants[i]
                if ratios[i] > 0:
                    kw["kv_compress_ratio"] = ratios[i]
                x, m = EvoformerBlock(**kw, name=f"layers_{i}")(
                    x, m, mask=mask, msa_mask=msa_mask,
                    deterministic=deterministic)

        return x, m
