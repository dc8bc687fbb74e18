"""Equivariant coordinate refiners: EGNN, En-Transformer-style, SE3-style.

Capability parity with the reference's secondary structure modules — the
README-era API `structure_module_type = 'se3' | 'egnn' | 'en'` with
`refinement_iters` (/root/reference/README.md:106-112, :594-600,
train_end2end.py:83-87) and the EGNN end-to-end notebook
(notebooks/egnn_esm_end2end.ipynb cells 25-33). The reference outsources
these to external CUDA-backed packages (egnn-pytorch, En-transformer,
se3-transformer-pytorch — setup.py:19-34); here they are small pure-JAX
message-passing layers:

- E(n)-equivariant updates operate on distances and relative vectors only,
  so rotating/translating inputs rotates/translates outputs exactly;
- all-pairs messages are dense (b, n, n) tensors — at protein scale the
  dense form is one MXU matmul, beating sparse gather/scatter on TPU
  (SURVEY.md §2.4's torch-sparse note);
- coordinate updates are tanh-clamped for stability (the notebook's NaN
  debugging, cell 37, is the failure mode this guards).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from alphafold2_tpu.model.primitives import LayerNorm, zeros_init


def _safe_norm2(v, eps=1e-8):
    return jnp.sum(v * v, axis=-1, keepdims=True) + eps


class EGNNLayer(nn.Module):
    """One E(n)-GNN layer (Satorras et al.): invariant messages from
    (h_i, h_j, ||x_i - x_j||^2, e_ij), equivariant coordinate update along
    relative vectors."""

    dim: int
    edge_dim: int = 0
    hidden: Optional[int] = None
    coor_clamp: float = 3.0

    @nn.compact
    def __call__(self, h, x, edges=None, mask=None):
        """h: (b, n, d) node feats; x: (b, n, 3) coords;
        edges: (b, n, n, e) optional; mask: (b, n) optional."""
        hidden = self.hidden or self.dim * 2
        b, n, _ = h.shape

        rel = x[:, :, None, :] - x[:, None, :, :]           # (b, n, n, 3)
        dist2 = _safe_norm2(rel)                            # (b, n, n, 1)

        feats = [jnp.broadcast_to(h[:, :, None, :], (b, n, n, h.shape[-1])),
                 jnp.broadcast_to(h[:, None, :, :], (b, n, n, h.shape[-1])),
                 dist2]
        if edges is not None:
            feats.append(edges)
        msg_in = jnp.concatenate(feats, axis=-1)

        msg = nn.Dense(hidden, param_dtype=jnp.float32, name="edge_mlp_in")(
            msg_in)
        msg = jax.nn.silu(msg)
        msg = nn.Dense(hidden, param_dtype=jnp.float32, name="edge_mlp_out")(
            msg)
        msg = jax.nn.silu(msg)

        if mask is not None:
            pair_mask = (mask[:, :, None] & mask[:, None, :])[..., None]
            msg = msg * pair_mask
        # no self-messages
        eye = jnp.eye(n, dtype=msg.dtype)[None, :, :, None]
        msg = msg * (1.0 - eye)

        # equivariant coordinate update, zero-init scale so the layer starts
        # as identity on coordinates
        coor_w = nn.Dense(1, param_dtype=jnp.float32, use_bias=False,
                          kernel_init=zeros_init(), name="coor_mlp")(msg)
        coor_w = jnp.tanh(coor_w) * self.coor_clamp
        denom = jnp.maximum(
            (mask.astype(x.dtype).sum(-1) - 1.0)[:, None, None]
            if mask is not None else jnp.asarray(float(n - 1)), 1.0)
        x = x + (rel / jnp.sqrt(dist2) * coor_w).sum(axis=2) / denom

        # invariant feature update
        agg = msg.sum(axis=2) / denom
        h_in = jnp.concatenate([h, agg], axis=-1)
        dh = nn.Dense(hidden, param_dtype=jnp.float32, name="node_mlp_in")(
            h_in)
        dh = jax.nn.silu(dh)
        dh = nn.Dense(self.dim, param_dtype=jnp.float32, name="node_mlp_out")(
            dh)
        return h + dh, x


class EnAttentionLayer(nn.Module):
    """En-Transformer-style layer: attention-weighted invariant messages +
    equivariant coordinate update (attention replaces EGNN's sum pooling;
    reference capability via the `En-transformer` dependency,
    setup.py:19-34)."""

    dim: int
    heads: int = 4
    dim_head: int = 32
    edge_dim: int = 0
    coor_clamp: float = 3.0

    @nn.compact
    def __call__(self, h, x, edges=None, mask=None):
        b, n, d = h.shape
        hd, nh = self.dim_head, self.heads
        inner = hd * nh

        hn = LayerNorm(name="norm")(h)
        q = nn.Dense(inner, use_bias=False, param_dtype=jnp.float32,
                     name="to_q")(hn).reshape(b, n, nh, hd)
        k = nn.Dense(inner, use_bias=False, param_dtype=jnp.float32,
                     name="to_k")(hn).reshape(b, n, nh, hd)
        v = nn.Dense(inner, use_bias=False, param_dtype=jnp.float32,
                     name="to_v")(hn).reshape(b, n, nh, hd)

        rel = x[:, :, None, :] - x[:, None, :, :]
        dist2 = _safe_norm2(rel)

        logits = jnp.einsum("bihd,bjhd->bhij", q, k) * (hd ** -0.5)
        # distance-aware bias (+ optional pair-rep edge bias)
        dist_bias = nn.Dense(nh, param_dtype=jnp.float32,
                             name="dist_to_bias")(jnp.log(dist2))
        logits = logits + dist_bias.transpose(0, 3, 1, 2)
        if edges is not None:
            logits = logits + nn.Dense(
                nh, use_bias=False, param_dtype=jnp.float32,
                name="edge_to_bias")(edges).transpose(0, 3, 1, 2)

        if mask is not None:
            pair_mask = mask[:, None, :, None] & mask[:, None, None, :]
            logits = jnp.where(pair_mask, logits, -1e9)

        attn = jax.nn.softmax(logits, axis=-1)              # (b, h, i, j)

        out = jnp.einsum("bhij,bjhd->bihd", attn, v).reshape(b, n, inner)
        h = h + nn.Dense(self.dim, param_dtype=jnp.float32,
                         kernel_init=zeros_init(), bias_init=zeros_init(),
                         name="to_out")(out)

        # equivariant coordinate update weighted by mean attention
        coor_w = nn.Dense(1, use_bias=False, param_dtype=jnp.float32,
                          kernel_init=zeros_init(), name="coor_mlp")(
                              attn.mean(1)[..., None])
        coor_w = jnp.tanh(coor_w) * self.coor_clamp
        x = x + (rel / jnp.sqrt(dist2) * coor_w).sum(axis=2) / max(n - 1, 1)
        return h, x


class Refiner(nn.Module):
    """Iterative equivariant refinement head (README-era
    `structure_module_type` + `refinement_iters`). Weight-shared layer
    applied `iters` times, mirroring the reference's refinement loop."""

    dim: int
    kind: str = "egnn"        # 'egnn' | 'en' | 'se3'
    iters: int = 4
    edge_dim: int = 0
    heads: int = 4

    @nn.compact
    def __call__(self, h, x, edges=None, mask=None):
        if self.kind == "egnn":
            layer = EGNNLayer(dim=self.dim, edge_dim=self.edge_dim,
                              name="layer")
        elif self.kind in ("en", "se3"):
            # 'se3' maps onto the vector-equivariant attention layer: on
            # point clouds with scalar features, SE(3) equivariance is
            # exactly E(3) equivariance of this update
            layer = EnAttentionLayer(dim=self.dim, heads=self.heads,
                                     edge_dim=self.edge_dim, name="layer")
        else:
            raise ValueError(f"unknown refiner kind {self.kind!r}")

        for _ in range(self.iters):
            h, x = layer(h, x, edges=edges, mask=mask)
        return h, x


# ---------------------------------------------------------------------------
# Atom-level refinement over the covalent-bond graph (round-4 VERDICT #8)
# ---------------------------------------------------------------------------


class SparseEGNNLayer(nn.Module):
    """EGNN over a fixed-degree neighbor list instead of all pairs.

    The reference notebook refines at the ATOM level with a *sparse* EGNN
    over the 14-slot covalent graph (egnn_esm_end2end.ipynb cells 25-33,
    utils.py:497-650). The atom cloud is L*14 nodes; all-pairs messages
    would be O((L*14)^2) — 12.8M pairs at 256 res — for a graph whose true
    degree is <= 4. The TPU-native sparse form is a static-shape GATHER:
    each node sees exactly `max_degree` neighbor slots (take_along_axis
    over precomputed indices), so messages are O(N * max_degree), no
    dynamic shapes, no scatter.
    """

    dim: int
    max_degree: int = 4
    hidden: Optional[int] = None
    coor_clamp: float = 3.0

    @nn.compact
    def __call__(self, h, x, neigh_idx, neigh_mask, mask=None):
        """h: (b, N, d); x: (b, N, 3); neigh_idx/(b, N, K) int indices;
        neigh_mask: (b, N, K) 1.0 where the slot holds a real bond;
        mask: (b, N) node validity."""
        hidden = self.hidden or self.dim * 2
        b, n_nodes, d = h.shape
        k = neigh_idx.shape[-1]

        def gather(t, idx):
            # t (b, N, c), idx (b, N, K) -> (b, N, K, c)
            c = t.shape[-1]
            flat = jnp.broadcast_to(idx.reshape(b, n_nodes * k, 1),
                                    (b, n_nodes * k, c))
            return jnp.take_along_axis(t, flat, axis=1).reshape(
                b, n_nodes, k, c)

        h_j = gather(h, neigh_idx)                       # (b, N, K, d)
        x_j = gather(x, neigh_idx)                       # (b, N, K, 3)
        rel = x[:, :, None, :] - x_j                     # (b, N, K, 3)
        dist2 = _safe_norm2(rel)                         # (b, N, K, 1)

        live = neigh_mask[..., None]
        if mask is not None:
            live = live * mask[:, :, None, None]
        msg_in = jnp.concatenate(
            [jnp.broadcast_to(h[:, :, None, :], (b, n_nodes, k, d)),
             h_j, dist2], axis=-1)
        msg = jax.nn.silu(nn.Dense(hidden, param_dtype=jnp.float32,
                                   name="edge_mlp_in")(msg_in))
        msg = jax.nn.silu(nn.Dense(hidden, param_dtype=jnp.float32,
                                   name="edge_mlp_out")(msg))
        msg = msg * live

        coor_w = nn.Dense(1, param_dtype=jnp.float32, use_bias=False,
                          kernel_init=zeros_init(), name="coor_mlp")(msg)
        coor_w = jnp.tanh(coor_w) * self.coor_clamp * live
        denom = jnp.maximum(live.sum(axis=2), 1.0)       # (b, N, 1)
        x = x + (rel / jnp.sqrt(dist2) * coor_w).sum(axis=2) / denom

        agg = msg.sum(axis=2) / denom
        dh = jax.nn.silu(nn.Dense(hidden, param_dtype=jnp.float32,
                                  name="node_mlp_in")(
            jnp.concatenate([h, agg], axis=-1)))
        dh = nn.Dense(self.dim, param_dtype=jnp.float32,
                      name="node_mlp_out")(dh)
        if mask is not None:
            dh = dh * mask[:, :, None]
        return h + dh, x


class AtomEGNNRefiner(nn.Module):
    """Atom-level coordinate refinement: residue repr + CA trace ->
    14-atom scaffold (core/nerf.sidechain_container) -> sparse EGNN over
    the covalent-bond adjacency (data/graph.prot_covalent_bond) ->
    refined atom cloud.

    The `structure_module_refinement='egnn-atom'` mode (reference
    notebook cells 25-33; utils.py:497-650 `mat_input_to_masked` +
    `prot_covalent_bond`). Returns (h_atoms, atoms) with atoms
    (b, L, 14, 3); the CA slot [:, :, 1] is the model's coords contract.
    """

    dim: int
    iters: int = 2
    max_degree: int = 4

    @nn.compact
    def __call__(self, h_res, ca_coords, seq, mask=None):
        """h_res: (b, L, d) single repr; ca_coords: (b, L, 3);
        seq: (b, L) tokens; mask: (b, L) residue validity."""
        from alphafold2_tpu import constants
        from alphafold2_tpu.core.nerf import sidechain_container
        from alphafold2_tpu.data.graph import covalent_neighbor_table
        from alphafold2_tpu.data.scn import scn_atom_embedd, scn_cloud_mask

        b, l, d = h_res.shape
        kk = constants.NUM_COORDS_PER_RES
        n_atoms = l * kk

        atoms = sidechain_container(
            ca_coords.astype(jnp.float32)[:, :, None, :], seq)
        cloud = scn_cloud_mask(seq)                     # (b, L, 14)
        if mask is not None:
            cloud = cloud * mask[..., None].astype(cloud.dtype)

        atom_tok = scn_atom_embedd(seq)                 # (b, L, 14)
        h_atom = nn.Dense(self.dim, param_dtype=jnp.float32,
                          name="res_to_atom")(h_res)[:, :, None, :] + \
            nn.Embed(constants.NUM_ATOM_TOKENS, self.dim,
                     param_dtype=jnp.float32,
                     name="atom_id_embed")(atom_tok)

        # static-degree neighbor list straight from the bond tables —
        # O(N*K); never materializes the (N, N) adjacency
        neigh_idx, neigh_mask = covalent_neighbor_table(seq)

        h = h_atom.reshape(b, n_atoms, self.dim)
        x = atoms.reshape(b, n_atoms, 3)
        node_mask = cloud.reshape(b, n_atoms)
        # a bond to a masked atom slot is not a message path
        neigh_mask = neigh_mask * jnp.take_along_axis(
            node_mask, neigh_idx.reshape(b, -1), axis=1).reshape(
            neigh_idx.shape)

        layer = SparseEGNNLayer(dim=self.dim, max_degree=self.max_degree,
                                name="layer")
        for _ in range(self.iters):
            h, x = layer(h, x, neigh_idx, neigh_mask, mask=node_mask)

        atoms = x.reshape(b, l, kk, 3) * cloud[..., None]
        return h.reshape(b, l, kk, self.dim), atoms
