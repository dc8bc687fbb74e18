"""Invariant Point Attention structure module, from scratch in JAX.

The reference outsources IPA to the external `invariant-point-attention`
package (/root/reference/alphafold2_pytorch/alphafold2.py:19, :608-611,
:873-879) and runs the frame-refinement loop inline in `Alphafold2.forward`
(alphafold2.py:855-891). Here both are first-class:

- `InvariantPointAttention`: the AF2 (Jumper et al. 2021, Alg. 22) attention
  with scalar, point, and pairwise terms. Point terms are computed in global
  coordinates via the per-residue frames, giving SE(3)-invariant logits and
  equivariant point outputs.
- `IPABlock`: IPA -> post-LN -> transition FF -> post-LN (residual), the
  external package's block layout the reference composes with.
- `StructureModule`: the iterative frame refinement with weight sharing
  across iterations, stop-gradient on rotations except the last iteration
  (the DeepMind folding.py trick the reference cites at alphafold2.py:867),
  and the final local-points -> global-coords map.

Whole module is an fp32 island (reference alphafold2.py:850-855): callers
cast trunk outputs to float32 before entry; all params here are fp32.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from alphafold2_tpu.core.quaternion import quaternion_multiply as quat_multiply
from alphafold2_tpu.core.rigid import Rigid
from alphafold2_tpu.model.primitives import (MASK_VALUE, LayerNorm,
                                              zeros_init)


class InvariantPointAttention(nn.Module):
    """AF2 Algorithm 22. All computation fp32."""

    dim: int
    heads: int = 8
    scalar_key_dim: int = 16
    scalar_value_dim: int = 16
    point_key_dim: int = 4
    point_value_dim: int = 8
    pairwise_repr_dim: Optional[int] = None
    eps: float = 1e-8

    @nn.compact
    def __call__(self, single_repr, pairwise_repr, frames: Rigid, mask=None):
        """single_repr: (b, n, d); pairwise_repr: (b, n, n, d_pair);
        frames: Rigid with (b, n, 4)/(b, n, 3); mask: (b, n) bool."""
        b, n, _ = single_repr.shape
        h = self.heads
        x = single_repr

        dense = lambda features, name, use_bias=True: nn.Dense(
            features, use_bias=use_bias, param_dtype=jnp.float32, name=name)

        # --- scalar qkv ---------------------------------------------------
        q_s = dense(h * self.scalar_key_dim, "to_scalar_q", False)(x)
        k_s = dense(h * self.scalar_key_dim, "to_scalar_k", False)(x)
        v_s = dense(h * self.scalar_value_dim, "to_scalar_v", False)(x)
        split = lambda t, dh: t.reshape(b, n, h, dh).transpose(0, 2, 1, 3)
        q_s = split(q_s, self.scalar_key_dim)
        k_s = split(k_s, self.scalar_key_dim)
        v_s = split(v_s, self.scalar_value_dim)

        # --- point qkv (local frame), mapped to globals -------------------
        n_qk, n_v = self.point_key_dim, self.point_value_dim
        q_p = dense(h * n_qk * 3, "to_point_q", False)(x)
        k_p = dense(h * n_qk * 3, "to_point_k", False)(x)
        v_p = dense(h * n_v * 3, "to_point_v", False)(x)
        as_points = lambda t, p: t.reshape(b, n, h, p, 3)
        q_p, k_p = as_points(q_p, n_qk), as_points(k_p, n_qk)
        v_p = as_points(v_p, n_v)

        # frames broadcast over (h, p): local (b, n, h*p, 3) -> global
        to_global = lambda t: frames.apply(
            t.reshape(b, n, -1, 3)).reshape(t.shape)
        q_pg, k_pg, v_pg = map(to_global, (q_p, k_p, v_p))

        # --- attention logits (Alg. 22 line 7) ----------------------------
        w_c = (2.0 / (9.0 * n_qk)) ** 0.5
        w_l = (1.0 / 3.0) ** 0.5

        scalar_logits = jnp.einsum("bhid,bhjd->bhij", q_s, k_s) * \
            (self.scalar_key_dim ** -0.5)

        # per-head learned point weight gamma, softplus-parameterized
        gamma_raw = self.param(
            "point_weights", nn.initializers.constant(0.541324854612918), (h,))
        gamma = jax.nn.softplus(gamma_raw)

        d2 = jnp.sum(
            (q_pg[:, :, None, :, :, :] - k_pg[:, None, :, :, :, :]) ** 2,
            axis=-1)                                   # (b, i, j, h, p)
        point_logits = -0.5 * w_c * gamma[None, None, None, :] * d2.sum(-1)
        point_logits = point_logits.transpose(0, 3, 1, 2)  # (b, h, i, j)

        logits = scalar_logits + point_logits
        if pairwise_repr is not None:
            pair_bias = nn.Dense(h, use_bias=False, param_dtype=jnp.float32,
                                 name="pairwise_to_bias")(pairwise_repr)
            logits = logits + pair_bias.transpose(0, 3, 1, 2)
        logits = logits * w_l

        if mask is not None:
            pair_mask = mask[:, None, :, None] & mask[:, None, None, :]
            logits = jnp.where(pair_mask, logits, MASK_VALUE)

        attn = jax.nn.softmax(logits, axis=-1)  # (b, h, i, j)

        # --- aggregate ----------------------------------------------------
        out_scalar = jnp.einsum("bhij,bhjd->bhid", attn, v_s)
        out_scalar = out_scalar.transpose(0, 2, 1, 3).reshape(b, n, -1)

        out_point_g = jnp.einsum("bhij,bjhpc->bihpc", attn, v_pg)
        # back to the local frame of residue i (equivariance)
        out_point = frames.invert_apply(
            out_point_g.reshape(b, n, -1, 3)).reshape(out_point_g.shape)
        out_point_flat = out_point.reshape(b, n, -1)
        out_point_norm = jnp.sqrt(
            jnp.sum(out_point ** 2, axis=-1) + self.eps).reshape(b, n, -1)

        outputs = [out_scalar, out_point_flat, out_point_norm]
        if pairwise_repr is not None:
            out_pair = jnp.einsum("bhij,bijd->bihd", attn, pairwise_repr)
            outputs.append(out_pair.reshape(b, n, -1))

        out = jnp.concatenate(outputs, axis=-1)
        # zero-init final projection (reference zero-inits ipa attn to_out,
        # alphafold2.py:615)
        return nn.Dense(self.dim, param_dtype=jnp.float32,
                        kernel_init=zeros_init(), bias_init=zeros_init(),
                        name="to_out")(out)


class IPABlock(nn.Module):
    """IPA + transition, post-norm layout (matches the external package the
    reference composes with at alphafold2.py:608-611, :873-879)."""

    dim: int
    heads: int = 8
    ff_mult: int = 1
    ff_num_layers: int = 3

    @nn.compact
    def __call__(self, x, pairwise_repr, frames: Rigid, mask=None):
        x = InvariantPointAttention(
            dim=self.dim, heads=self.heads,
            pairwise_repr_dim=pairwise_repr.shape[-1]
            if pairwise_repr is not None else None,
            name="attn",
        )(x, pairwise_repr, frames, mask=mask) + x
        x = LayerNorm(name="attn_norm")(x)

        hidden = self.dim * self.ff_mult
        ff = x
        for i in range(self.ff_num_layers - 1):
            ff = nn.Dense(hidden, param_dtype=jnp.float32,
                          name=f"ff_{i}")(ff)
            ff = jax.nn.relu(ff)
        ff = nn.Dense(self.dim, param_dtype=jnp.float32,
                      name=f"ff_{self.ff_num_layers - 1}")(ff)
        x = x + ff
        return LayerNorm(name="ff_norm")(x)


class StructureModule(nn.Module):
    """Iterative frame refinement (reference alphafold2.py:855-891).

    One weight-shared IPABlock applied `depth` times; quaternion/translation
    updates from a Linear(dim -> 6); rotation stop-gradient except on the
    last iteration; final coords = to_points(single) mapped through frames.
    """

    dim: int
    depth: int = 4
    heads: int = 1

    @nn.compact
    def __call__(self, single_repr, pairwise_repr, mask=None,
                 return_frames: bool = False):
        single_repr = single_repr.astype(jnp.float32)
        pairwise_repr = pairwise_repr.astype(jnp.float32)
        b, n, _ = single_repr.shape

        block = IPABlock(dim=self.dim, heads=self.heads, name="ipa_block")
        to_update = nn.Dense(6, param_dtype=jnp.float32,
                             name="to_quaternion_update")
        init = Rigid.identity((b, n), dtype=jnp.float32)
        quaternions, translations = init.quaternions, init.translations

        x = single_repr
        for i in range(self.depth):
            is_last = i == self.depth - 1

            # stop-gradient on the rotation *matrices* except on the last
            # iteration (reference alphafold2.py:867-871, citing DeepMind
            # folding.py:L383) — the quaternion chain itself stays
            # differentiable across iterations, exactly as in the reference.
            rot_q = quaternions if is_last else \
                jax.lax.stop_gradient(quaternions)
            frames = Rigid(rot_q, translations)

            x = block(x, pairwise_repr, frames, mask=mask)

            update = to_update(x)
            dq, dt = update[..., :3], update[..., 3:]
            dq = jnp.concatenate(
                [jnp.ones((*dq.shape[:-1], 1), dq.dtype), dq], axis=-1)
            # not Rigid.compose_update: the translation update must rotate by
            # the (possibly stop-gradient) rot_q frames while the quaternion
            # chain stays fully differentiable — compose_update would tie both
            # to the same quaternions
            quaternions = quat_multiply(quaternions, dq)
            translations = translations + jnp.einsum(
                "...c,...cd->...d", dt, frames.rotations)

        points_local = nn.Dense(3, param_dtype=jnp.float32,
                                name="to_points")(x)
        frames = Rigid(quaternions, translations)
        coords = frames.apply_single(points_local)

        if return_frames:
            return coords, x, frames
        return coords, x
