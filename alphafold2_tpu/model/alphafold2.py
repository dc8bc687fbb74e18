"""Top-level Alphafold2 model.

Forward-path parity with the reference
(/root/reference/alphafold2_pytorch/alphafold2.py:469-905): token/relative-
position embeddings, MSA-MLM noising during training, pair-representation
init via outer sum, recycling embedder (norms + bucketized CA-distance
embedding), template pair/angle stacks, extra-MSA Evoformer, the main
Evoformer trunk, distogram + trRosetta-style angle heads, the IPA structure
module, and the lDDT confidence head.

Deviations from the reference (deliberate, documented):
- the extra-MSA path embeds `extra_msa` (the reference embeds `msa` again —
  a bug at alphafold2.py:790);
- angle logits are returned on the `theta`/`phi`/`omega` fields of
  `ReturnValues` (the reference assigns ad-hoc `theta_logits` attributes that
  leave the declared dataclass fields None, alphafold2.py:32-35 vs :816-817);
- randomness (MLM noising, dropout) uses explicit PRNG keys via flax rngs
  {'mlm', 'dropout'} instead of global RNG state;
- the trunk runs in a configurable compute dtype (bf16 on TPU); the
  structure module stays an fp32 island as in the reference
  (alphafold2.py:850-855).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax import struct

from alphafold2_tpu import constants
from alphafold2_tpu.model.attention_variants import (
    DEFAULT_CONV_MSA_KERNELS,
    DEFAULT_CONV_SEQ_KERNELS,
)
from alphafold2_tpu.model.evoformer import Evoformer, PairwiseAttentionBlock
from alphafold2_tpu.model.mlm import MLM
from alphafold2_tpu.model.primitives import Attention, LayerNorm
from alphafold2_tpu.model.refiners import (AtomEGNNRefiner,
                                            Refiner)
from alphafold2_tpu.model.structure import StructureModule
from alphafold2_tpu.parallel.sharding import shard_msa, shard_pair


@struct.dataclass
class Recyclables:
    """Inter-recycle state (reference alphafold2.py:24-28)."""

    coords: jnp.ndarray
    single_msa_repr_row: jnp.ndarray
    pairwise_repr: jnp.ndarray


@struct.dataclass
class ReturnValues:
    """Multi-output container (reference alphafold2.py:30-37)."""

    distance: Optional[jnp.ndarray] = None
    theta: Optional[jnp.ndarray] = None
    phi: Optional[jnp.ndarray] = None
    omega: Optional[jnp.ndarray] = None
    msa_mlm_loss: Optional[jnp.ndarray] = None
    recyclables: Optional[Recyclables] = None
    # raw lddt-confidence head output (b, n, 1); populated on the coords
    # path so the head can be trained (the reference's lddt_linear ships
    # untrained — alphafold2.py:621)
    confidence: Optional[jnp.ndarray] = None
    # full refined atom cloud (b, n, 14, 3); populated only under
    # structure_module_refinement='egnn-atom' (the notebook's atom-level
    # path — coords stay the CA trace for API stability)
    atoms: Optional[jnp.ndarray] = None


class Alphafold2(nn.Module):
    """See reference Alphafold2.__init__ (alphafold2.py:470-501) for the
    hyperparameter contract; names and defaults match."""

    dim: int
    max_seq_len: int = 2048
    depth: int = 6
    heads: int = 8
    dim_head: int = 64
    max_rel_dist: int = 32
    num_tokens: int = constants.NUM_AMINO_ACIDS
    num_embedds: int = constants.NUM_EMBEDDS_TR
    max_num_msas: int = constants.MAX_NUM_MSA
    max_num_templates: int = constants.MAX_NUM_TEMPLATES
    extra_msa_evoformer_layers: int = 4
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    templates_dim: int = 32
    templates_embed_layers: int = 4
    templates_angles_feats_dim: int = 55
    predict_angles: bool = False
    symmetrize_omega: bool = False
    predict_coords: bool = False
    structure_module_depth: int = 4
    structure_module_heads: int = 1
    structure_module_dim_head: int = 4
    # README-era structure-module selection (reference README.md:106-112,
    # :594-600; the current reference code is IPA-only): 'ipa' runs the
    # IPA module; 'egnn' / 'en' / 'se3' run the equivariant refiners from
    # model/refiners.py instead. refinement_iters > 0 additionally refines
    # the produced coordinates (on top of any module type).
    structure_module_type: str = "ipa"
    structure_module_refinement_iters: int = 0
    # what refinement_iters refines: 'residue' = dense EGNN on the CA
    # trace (the README-era refinement loop); 'egnn-atom' = sparse EGNN
    # over the 14-slot covalent-bond atom graph, the reference notebook's
    # atom-level experiment (egnn_esm_end2end.ipynb cells 25-33,
    # utils.py:497-650) — coords stay (b, n, 3) CA; the full refined
    # atom cloud is returned on ReturnValues.atoms
    structure_module_refinement: str = "residue"
    # reversible main trunk (README.md:40-era flag): O(1) activation memory
    reversible: bool = False
    # scan+remat over trunk depth (Evoformer.use_scan); False unrolls the
    # stack with full activation storage — the linear-memory comparison
    # point for tools/memory_probe.py
    use_scan: bool = True
    # ring-parallel triangle attention over the 2-D-sharded pair tensor
    # (parallel/ring.py): exact long-context mode, active only when the
    # mesh actually shards the pair axes; no-op otherwise
    ring_attention: bool = False
    # GPipe pipeline parallelism for the main trunk over the mesh's
    # `pipe` axis (Evoformer.pipeline_stages; parallel/pipeline.py).
    # The small extra-MSA stack stays scanned — only the deep trunk is
    # worth staging.
    pipeline_stages: int = 1
    pipeline_microbatches: int = 0
    # trRosetta2-style conv blocks on both trunk tracks (the reference's
    # README-era `use_conv` menu, README.md:271-340; kernels/dilations
    # mirror its conv_seq_kernels / conv_msa_kernels / dilation cycle)
    use_conv: bool = False
    conv_seq_kernels: tuple = DEFAULT_CONV_SEQ_KERNELS
    conv_msa_kernels: tuple = DEFAULT_CONV_MSA_KERNELS
    conv_dilations: tuple = (1,)
    # README-era efficient-attention menu for the main trunk's MSA row
    # track (reference README.md:388-487; Evoformer documents the
    # semantics). Bools (all layers) or per-layer tuples — e.g.
    # `sparse_self_attn=(True, False) * 3` interleaves sparse and full
    # (README.md:415). kv_compress_ratio: 0 = off (README.md:485).
    # Reference-name mapping (MIGRATING.md): sparse_self_attn ->
    # sparse_self_attn, cross_attn_linear -> linear_attn,
    # cross_attn_kron_primary/_msa -> kron_attn,
    # cross_attn_compress_ratio -> kv_compress_ratio.
    sparse_self_attn: Any = False
    linear_attn: Any = False
    kron_attn: Any = False
    kv_compress_ratio: Any = 0
    sparse_block: int = 32
    sparse_num_global: int = 1
    sparse_window: int = 1
    linear_attn_kind: str = "favor"
    performer_nb_features: int = 256
    # reproduce the reference's masked-OuterMean double division
    # (alphafold2.py:347 + the always-synthesized msa_mask at :703);
    # required for exact parity with reference-trained checkpoints
    # (tools/port_weights.py), off by default in favor of the correct
    # masked mean
    outer_mean_reference_scale: bool = False
    disable_token_embed: bool = False
    mlm_mask_prob: float = 0.15
    mlm_random_replace_token_prob: float = 0.1
    mlm_keep_token_same_prob: float = 0.1
    mlm_exclude_token_ids: tuple = (0,)
    recycling_distance_buckets: int = 32
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self,
        seq,                       # (b, n) int tokens
        msa=None,                  # (b, m, n) int tokens
        mask=None,                 # (b, n) bool
        msa_mask=None,             # (b, m, n) bool
        extra_msa=None,            # (b, e, n) int tokens
        extra_msa_mask=None,       # (b, e, n) bool
        seq_index=None,            # (n,) int residue indices
        seq_embed=None,            # (b, n, dim)
        msa_embed=None,            # (b, m, n, dim)
        templates_feats=None,      # (b, t, n, n, templates_dim)
        templates_mask=None,       # (b, t, n)
        templates_angles=None,     # (b, t, n, templates_angles_feats_dim)
        embedds=None,              # (b, m, n, num_embedds) pretrained embeds
        recyclables: Optional[Recyclables] = None,
        return_trunk: bool = False,
        return_confidence: bool = False,
        return_recyclables: bool = False,
        return_aux_logits: bool = False,
        train: bool = False,
    ):
        assert not (self.disable_token_embed and seq_embed is None), \
            "sequence embedding must be supplied if token embedding disabled"
        assert not (self.disable_token_embed and msa is not None
                    and msa_embed is None), \
            "msa embedding must be supplied if token embedding disabled"

        b, n = seq.shape[:2]
        deterministic = not train

        if mask is None:
            mask = jnp.ones((b, n), dtype=bool)

        # if MSA is not passed in, use the sequence itself
        # (reference alphafold2.py:656-658)
        if msa is None and embedds is None:
            msa = seq[:, None, :]
            msa_mask = mask[:, None, :]

        if msa is not None:
            assert msa.shape[-1] == seq.shape[-1], \
                "sequence length of MSA and primary sequence must match"

        # embedding tables -------------------------------------------------
        token_emb = nn.Embed(self.num_tokens + 1, self.dim,
                             param_dtype=jnp.float32, name="token_emb") \
            if not self.disable_token_embed else None

        def embed_tokens(t):
            if token_emb is None:
                return 0.0
            return token_emb(t).astype(self.dtype)

        # embed main sequence (reference alphafold2.py:676-679). Pretrained
        # LM embeddings at foreign dims (= num_embedds) are projected here —
        # the reference keeps this Linear inside its embed wrappers
        # (embeds.py:14, :41, :84); model-side keeps the wrappers paramless.
        # one projector per input width so a single params tree serves any
        # of the pretrained-LM widths (768/1024/1280 — the reference sizes
        # each wrapper's Linear from its own constant)
        def project_embed(e, prefix):
            e = e.astype(self.dtype)
            if e.shape[-1] != self.dim:
                e = nn.Dense(self.dim, param_dtype=jnp.float32,
                             dtype=self.dtype,
                             name=f"{prefix}_{e.shape[-1]}")(e)
            return e

        x_single = embed_tokens(seq)
        if seq_embed is not None:
            x_single = x_single + project_embed(seq_embed,
                                                "seq_embed_project")

        # MLM noising for MSA during training (reference alphafold2.py:683-688)
        mlm = MLM(
            dim=self.dim,
            num_tokens=self.num_tokens,
            mask_id=self.num_tokens,  # last embedding row is the mask token
            mask_prob=self.mlm_mask_prob,
            random_replace_token_prob=self.mlm_random_replace_token_prob,
            keep_token_same_prob=self.mlm_keep_token_same_prob,
            exclude_token_ids=self.mlm_exclude_token_ids,
            name="mlm",
        )

        original_msa = msa
        replaced_msa_mask = None
        if train and msa is not None:
            if msa_mask is None:
                msa_mask = jnp.ones_like(msa, dtype=bool)
            noised_msa, replaced_msa_mask = mlm.noise(
                self.make_rng("mlm"), msa, msa_mask)
            msa = noised_msa

        # embed MSA (reference alphafold2.py:692-709)
        if msa is not None:
            m = embed_tokens(msa)
            if msa_embed is not None:
                m = m + project_embed(msa_embed, "msa_embed_project")
            m = m + x_single[:, None, :, :]
            if msa_mask is None:
                msa_mask = jnp.ones_like(msa, dtype=bool)
        elif embedds is not None:
            m = nn.Dense(self.dim, param_dtype=jnp.float32, dtype=self.dtype,
                         name="embedd_project")(embedds.astype(self.dtype))
            if msa_mask is None:
                msa_mask = jnp.ones(embedds.shape[:-1], dtype=bool)
        else:
            raise ValueError("either MSA or embedds must be given")
        m = shard_msa(m)

        # pairwise representation by outer sum (reference alphafold2.py:715-717)
        x_pair_proj = nn.Dense(self.dim * 2, param_dtype=jnp.float32,
                               dtype=self.dtype, name="to_pairwise_repr")(
                                   x_single)
        x_left, x_right = jnp.split(x_pair_proj, 2, axis=-1)
        x = x_left[:, :, None, :] + x_right[:, None, :, :]  # (b, i, j, d)
        x_mask = mask[:, :, None] & mask[:, None, :]

        # relative positional embedding, clamped (reference alphafold2.py:721-726)
        if seq_index is None:
            seq_index = jnp.arange(n)
        rel = seq_index[:, None] - seq_index[None, :]
        rel = jnp.clip(rel, -self.max_rel_dist, self.max_rel_dist) + \
            self.max_rel_dist
        pos_emb = nn.Embed(self.max_rel_dist * 2 + 1, self.dim,
                           param_dtype=jnp.float32, name="pos_emb")(rel)
        x = x + pos_emb[None].astype(self.dtype)
        x = shard_pair(x)

        # recycling (reference alphafold2.py:730-739)
        if recyclables is not None:
            first_row = m[:, 0] + LayerNorm(
                dtype=jnp.float32, name="recycling_msa_norm")(
                    recyclables.single_msa_repr_row).astype(self.dtype)
            m = m.at[:, 0].set(first_row)
            x = x + LayerNorm(
                dtype=jnp.float32, name="recycling_pairwise_norm")(
                    recyclables.pairwise_repr).astype(self.dtype)

            coords = recyclables.coords
            dists = jnp.sqrt(jnp.maximum(jnp.sum(
                (coords[:, :, None] - coords[:, None, :]) ** 2, -1), 1e-12))
            boundaries = jnp.linspace(2.0, 20.0,
                                      self.recycling_distance_buckets)[:-1]
            buckets = jnp.searchsorted(boundaries, dists, side="left")
            dist_embed = nn.Embed(
                self.recycling_distance_buckets, self.dim,
                param_dtype=jnp.float32, name="recycling_distance_embed")(
                    buckets)
            x = x + dist_embed.astype(self.dtype)

        # templates (reference alphafold2.py:743-785)
        if templates_feats is not None:
            num_templates = templates_feats.shape[1]
            t = nn.Dense(self.dim, param_dtype=jnp.float32, dtype=self.dtype,
                         name="to_template_embed")(
                             templates_feats.astype(self.dtype))
            t_mask_crossed = templates_mask[:, :, :, None] & \
                templates_mask[:, :, None, :]

            t = t.reshape(b * num_templates, *t.shape[2:])
            t_mask_flat = t_mask_crossed.reshape(
                b * num_templates, *t_mask_crossed.shape[2:])

            # weight-shared pair embedder applied templates_embed_layers
            # times (reference alphafold2.py:751-755)
            template_embedder = PairwiseAttentionBlock(
                dim=self.dim, heads=self.heads, dim_head=self.dim_head,
                dtype=self.dtype, name="template_pairwise_embedder")
            for _ in range(self.templates_embed_layers):
                t = template_embedder(t, mask=t_mask_flat,
                                      deterministic=deterministic)

            t = t.reshape(b, num_templates, *t.shape[1:])

            # pointwise attention across templates per pair cell
            # (reference alphafold2.py:762-778)
            x_point = x.reshape(b * n * n, 1, self.dim)
            t_point = t.transpose(0, 2, 3, 1, 4).reshape(
                b * n * n, num_templates, self.dim)
            x_mask_point = x_mask.reshape(b * n * n, 1)
            t_mask_point = t_mask_crossed.transpose(0, 2, 3, 1).reshape(
                b * n * n, num_templates)

            template_pooled = Attention(
                dim=self.dim, heads=self.heads, dim_head=self.dim_head,
                dropout=self.attn_dropout, dtype=self.dtype,
                name="template_pointwise_attn",
            )(x_point, mask=x_mask_point, context=t_point,
              context_mask=t_mask_point, deterministic=deterministic)

            has_template = (t_mask_point.sum(-1) > 0)[:, None, None]
            template_pooled = template_pooled * has_template
            x = x + template_pooled.reshape(b, n, n, self.dim)

        # template angle features -> extra MSA rows (reference
        # alphafold2.py:782-785)
        if templates_angles is not None:
            t_angs = templates_angles.astype(self.dtype)
            t_angle_feats = nn.Dense(
                self.dim, param_dtype=jnp.float32, dtype=self.dtype,
                name="template_angle_mlp_in")(t_angs)
            t_angle_feats = nn.Dense(
                self.dim, param_dtype=jnp.float32, dtype=self.dtype,
                name="template_angle_mlp_out")(jax.nn.gelu(t_angle_feats))
            m = jnp.concatenate([m, t_angle_feats], axis=1)
            msa_mask = jnp.concatenate([msa_mask, templates_mask], axis=1)

        # extra MSA stack (reference alphafold2.py:789-798; the reference
        # embeds `msa` here by mistake — we embed `extra_msa`)
        if extra_msa is not None:
            extra_m = embed_tokens(extra_msa)
            if extra_msa_mask is None:
                extra_msa_mask = jnp.ones(extra_msa.shape, dtype=bool)
            x, extra_m = Evoformer(
                dim=self.dim, depth=self.extra_msa_evoformer_layers,
                heads=self.heads, dim_head=self.dim_head,
                attn_dropout=self.attn_dropout, ff_dropout=self.ff_dropout,
                global_column_attn=True,
                ring_attention=self.ring_attention,
                outer_mean_reference_scale=self.outer_mean_reference_scale,
                dtype=self.dtype,
                name="extra_msa_evoformer",
            )(x, extra_m, mask=x_mask, msa_mask=extra_msa_mask,
              deterministic=deterministic)

        # main trunk (reference alphafold2.py:802-807)
        x, m = Evoformer(
            dim=self.dim, depth=self.depth, heads=self.heads,
            dim_head=self.dim_head, attn_dropout=self.attn_dropout,
            ff_dropout=self.ff_dropout,
            ring_attention=self.ring_attention,
            outer_mean_reference_scale=self.outer_mean_reference_scale,
            use_conv=self.use_conv,
            conv_seq_kernels=self.conv_seq_kernels,
            conv_msa_kernels=self.conv_msa_kernels,
            conv_dilations=self.conv_dilations,
            sparse_self_attn=self.sparse_self_attn,
            linear_attn=self.linear_attn,
            kron_attn=self.kron_attn,
            kv_compress_ratio=self.kv_compress_ratio,
            sparse_block=self.sparse_block,
            sparse_num_global=self.sparse_num_global,
            sparse_window=self.sparse_window,
            linear_attn_kind=self.linear_attn_kind,
            performer_nb_features=self.performer_nb_features,
            dtype=self.dtype,
            reversible=self.reversible, use_scan=self.use_scan,
            pipeline_stages=self.pipeline_stages,
            pipeline_microbatches=self.pipeline_microbatches, name="net",
        )(x, m, mask=x_mask, msa_mask=msa_mask, deterministic=deterministic)

        # --- init-time coverage of conditional branches -------------------
        # flax creates params lazily on first call; to keep one params tree
        # valid for every forward configuration (recycling on/off, templates
        # on/off, train on/off — the torch reference gets this for free by
        # building all modules in __init__, alphafold2.py:507-628), touch
        # every branch this trace skipped with tiny dummies during init.
        if self.is_initializing():
            zf = lambda *s: jnp.zeros(s, dtype=self.dtype)
            if msa is not None or embedds is None:
                # embedd_project ran only on the (msa-absent, embedds-given)
                # path; create it otherwise
                nn.Dense(self.dim, param_dtype=jnp.float32, dtype=self.dtype,
                         name="embedd_project")(zf(1, 1, 1, self.num_embedds))
            # projector coverage for every known pretrained-LM width plus
            # the configured num_embedds (skip widths this trace created)
            widths = {constants.MSA_EMBED_DIM, constants.PROTTRAN_EMBED_DIM,
                      constants.ESM_EMBED_DIM, self.num_embedds} - {self.dim}
            seq_w = None if seq_embed is None else seq_embed.shape[-1]
            msa_w = None if msa_embed is None else msa_embed.shape[-1]
            for w in sorted(widths):
                if w != seq_w:
                    nn.Dense(self.dim, param_dtype=jnp.float32,
                             dtype=self.dtype,
                             name=f"seq_embed_project_{w}")(zf(1, 1, w))
                if w != msa_w:
                    nn.Dense(self.dim, param_dtype=jnp.float32,
                             dtype=self.dtype,
                             name=f"msa_embed_project_{w}")(zf(1, 1, 1, w))
            if not (train and original_msa is not None):
                mlm(zf(1, 1, 1, self.dim), jnp.zeros((1, 1, 1), jnp.int32),
                    jnp.ones((1, 1, 1), bool))
            if recyclables is None:
                LayerNorm(dtype=jnp.float32, name="recycling_msa_norm")(
                    jnp.zeros((1, 1, self.dim), jnp.float32))
                LayerNorm(dtype=jnp.float32, name="recycling_pairwise_norm")(
                    jnp.zeros((1, 1, 1, self.dim), jnp.float32))
                nn.Embed(self.recycling_distance_buckets, self.dim,
                         param_dtype=jnp.float32,
                         name="recycling_distance_embed")(
                             jnp.zeros((1, 1, 1), jnp.int32))
            if templates_feats is None:
                t_d = nn.Dense(self.dim, param_dtype=jnp.float32,
                               dtype=self.dtype, name="to_template_embed")(
                                   zf(1, 1, 1, self.templates_dim))
                t_d = PairwiseAttentionBlock(
                    dim=self.dim, heads=self.heads, dim_head=self.dim_head,
                    dtype=self.dtype, name="template_pairwise_embedder")(t_d)
                Attention(dim=self.dim, heads=self.heads,
                          dim_head=self.dim_head, dtype=self.dtype,
                          name="template_pointwise_attn")(
                              zf(1, 1, self.dim), context=zf(1, 1, self.dim))
            if templates_angles is None:
                a = nn.Dense(self.dim, param_dtype=jnp.float32,
                             dtype=self.dtype, name="template_angle_mlp_in")(
                                 zf(1, 1, 1, self.templates_angles_feats_dim))
                nn.Dense(self.dim, param_dtype=jnp.float32, dtype=self.dtype,
                         name="template_angle_mlp_out")(jax.nn.gelu(a))
            if extra_msa is None:
                Evoformer(dim=self.dim, depth=self.extra_msa_evoformer_layers,
                          heads=self.heads, dim_head=self.dim_head,
                          attn_dropout=self.attn_dropout,
                          ff_dropout=self.ff_dropout,
                          global_column_attn=True, dtype=self.dtype,
                          name="extra_msa_evoformer")(
                    zf(1, 1, 1, self.dim), zf(1, 1, 1, self.dim))

        ret_kwargs = {}

        # theta / phi heads before symmetrization (reference alphafold2.py:815-817)
        x_f32 = x.astype(jnp.float32)
        if self.predict_angles:
            ret_kwargs["theta"] = nn.Dense(
                constants.THETA_BUCKETS, param_dtype=jnp.float32,
                name="to_prob_theta")(x_f32)
            ret_kwargs["phi"] = nn.Dense(
                constants.PHI_BUCKETS, param_dtype=jnp.float32,
                name="to_prob_phi")(x_f32)

        # symmetrize pair; distogram head (reference alphafold2.py:821-823)
        trunk_embeds = (x_f32 + x_f32.swapaxes(1, 2)) * 0.5
        distance_pred = LayerNorm(
            dtype=jnp.float32, name="distogram_norm")(trunk_embeds)
        distance_pred = nn.Dense(
            constants.DISTOGRAM_BUCKETS, param_dtype=jnp.float32,
            name="to_distogram_logits")(distance_pred)
        ret_kwargs["distance"] = distance_pred

        # MLM loss (reference alphafold2.py:827-830)
        if train and original_msa is not None and replaced_msa_mask is not None:
            num_msa = original_msa.shape[1]
            ret_kwargs["msa_mlm_loss"] = mlm(
                m[:, :num_msa], original_msa, replaced_msa_mask)

        # omega head (reference alphafold2.py:834-836)
        if self.predict_angles:
            omega_input = trunk_embeds if self.symmetrize_omega else x_f32
            ret_kwargs["omega"] = nn.Dense(
                constants.OMEGA_BUCKETS, param_dtype=jnp.float32,
                name="to_prob_omega")(omega_input)

        # during init, fall through even for return_trunk so the structure
        # module's params always exist in the tree
        if (not self.predict_coords) or \
                (return_trunk and not self.is_initializing()):
            return ReturnValues(**ret_kwargs)

        # single / pairwise projections for the structure module
        # (reference alphafold2.py:843-851); fp32 island from here on
        single_msa_repr_row = m[:, 0]
        single_repr = nn.Dense(self.dim, param_dtype=jnp.float32,
                               name="msa_to_single_repr_dim")(
                                   single_msa_repr_row.astype(jnp.float32))
        pairwise_repr = nn.Dense(self.dim, param_dtype=jnp.float32,
                                 name="trunk_to_pairwise_repr_dim")(
                                     x.astype(jnp.float32))

        if self.structure_module_type == "ipa":
            coords, single_out = StructureModule(
                dim=self.dim,
                depth=self.structure_module_depth,
                heads=self.structure_module_heads,
                name="structure_module",
            )(single_repr, pairwise_repr, mask=mask)
        else:
            # equivariant-refiner structure module: deterministic chain
            # init (3.8 A CA spacing) breaks translational symmetry, then
            # iterative E(n)/SE(3) updates driven by single + pair reprs
            chain = jnp.arange(n, dtype=jnp.float32)[None, :, None] * \
                jnp.asarray([3.8, 0.0, 0.0])
            init_coords = jnp.broadcast_to(chain, (b, n, 3))
            single_out, coords = Refiner(
                dim=self.dim, kind=self.structure_module_type,
                iters=self.structure_module_depth,
                edge_dim=self.dim, name="structure_module_refiner",
            )(single_repr, init_coords, edges=pairwise_repr, mask=mask)

        if self.structure_module_refinement_iters > 0:
            if self.structure_module_refinement == "egnn-atom":
                # notebook atom-level path: CA trace -> 14-atom scaffold
                # -> sparse EGNN over the covalent graph; coords contract
                # stays the refined CA slot
                _, atoms = AtomEGNNRefiner(
                    dim=self.dim,
                    iters=self.structure_module_refinement_iters,
                    name="atom_refiner",
                )(single_out, coords, seq, mask=mask)
                ret_kwargs["atoms"] = atoms
                # CA slot — except for residues with no atom cloud at all
                # (unknown/'_' tokens: scn cloud mask all-zero), whose
                # refined slot is zeroed; they keep the structure-module
                # coords instead of collapsing to the origin (r05 review)
                from alphafold2_tpu.data.scn import scn_cloud_mask
                has_ca = scn_cloud_mask(seq)[:, :, 1:2] > 0
                coords = jnp.where(has_ca, atoms[:, :, 1], coords)
            elif self.structure_module_refinement == "residue":
                single_out, coords = Refiner(
                    dim=self.dim, kind="egnn",
                    iters=self.structure_module_refinement_iters,
                    edge_dim=self.dim, name="coords_refiner",
                )(single_out, coords, edges=pairwise_repr, mask=mask)
            else:
                raise ValueError(
                    "structure_module_refinement must be 'residue' or "
                    f"'egnn-atom', got "
                    f"{self.structure_module_refinement!r}")

        # confidence head always built (cheap nn.Dense(1)) so one params tree
        # serves every return configuration
        confidence = nn.Dense(1, param_dtype=jnp.float32,
                              name="lddt_linear")(single_out)
        ret_kwargs["confidence"] = confidence

        if return_recyclables:
            ret_kwargs["recyclables"] = Recyclables(
                jax.lax.stop_gradient(coords),
                jax.lax.stop_gradient(single_msa_repr_row.astype(jnp.float32)),
                jax.lax.stop_gradient(pairwise_repr),
            )

        ret = ReturnValues(**ret_kwargs)

        if return_aux_logits:
            return coords, ret

        if return_confidence:
            return coords, confidence

        if return_recyclables:
            return coords, ret

        return coords
