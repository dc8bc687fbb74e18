"""Training losses.

Parity with the reference's two training objectives:
- distogram cross-entropy with ignore_index=-100 over bucketized CA
  distances (/root/reference/train_pre.py:76-89, utils.py:45-50);
- end-to-end coordinate loss: Kabsch-align prediction onto ground truth,
  then RMSD, plus a distogram-dispersion weighting term
  (/root/reference/train_end2end.py:157-159);
- trRosetta-style angle cross-entropies for the theta/phi/omega heads
  (/root/reference/training_scripts/datasets/trrosetta.py targets);
- MSA-MLM loss comes out of the model itself (mlm.py:86-92 there).

All losses are masked means with static shapes; `ignore_index` semantics are
implemented with `where` masks rather than boolean indexing.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from alphafold2_tpu import constants
from alphafold2_tpu.core import geometry as geo


def softmax_cross_entropy(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    ignore_index: int = constants.IGNORE_INDEX,
) -> jnp.ndarray:
    """Mean CE over positions whose label != ignore_index.

    logits: (..., C) float; labels: (...,) int.
    """
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    m = valid.astype(jnp.float32)
    return (ce * m).sum() / jnp.maximum(m.sum(), 1.0)


def distogram_loss(
    distogram_logits: jnp.ndarray,
    coords_ca: jnp.ndarray,
    mask: jnp.ndarray,
) -> jnp.ndarray:
    """Distogram pretraining loss (reference train_pre.py:76-89):
    bucketize true CA distances, CE against predicted logits."""
    targets = geo.bucketed_distance_matrix(coords_ca, mask)
    return softmax_cross_entropy(distogram_logits, targets)


def angle_loss(
    theta_logits, phi_logits, omega_logits,
    theta_target, phi_target, omega_target,
) -> jnp.ndarray:
    """Sum of trRosetta anglegram CEs (targets carry ignore_index fill)."""
    loss = softmax_cross_entropy(theta_logits, theta_target)
    loss += softmax_cross_entropy(phi_logits, phi_target)
    loss += softmax_cross_entropy(omega_logits, omega_target)
    return loss


def coords_loss(
    pred_coords: jnp.ndarray,
    true_coords: jnp.ndarray,
    mask: jnp.ndarray,
    distogram_logits: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """End-to-end coordinate loss (reference train_end2end.py:150-159):
    Kabsch-align then RMSD; if distogram logits are given, add the
    dispersion-weighted distance-matrix term the reference combines in."""
    aligned, target = geo.kabsch(pred_coords, true_coords, mask=mask)
    loss = geo.rmsd(aligned, target, mask=mask).mean()

    if distogram_logits is not None:
        probs = jax.nn.softmax(distogram_logits.astype(jnp.float32), axis=-1)
        _, weights = geo.center_distogram(probs)
        pair_mask = (mask[..., :, None] & mask[..., None, :])
        loss = loss + geo.distmat_loss(
            pred_coords, true_coords, mask=weights * pair_mask)
    return loss


def lddt_confidence_loss(
    pred_confidence: jnp.ndarray,   # (b, n, 1) raw head output
    pred_coords: jnp.ndarray,
    true_coords: jnp.ndarray,
    mask: jnp.ndarray,
) -> jnp.ndarray:
    """Regress the confidence head onto the actual per-residue CA lDDT of
    the prediction (net-new vs the reference, whose lddt_linear head ships
    untrained — alphafold2.py:621, :903)."""
    target = geo.lddt_ca(true_coords, pred_coords, mask=mask)
    target = jax.lax.stop_gradient(target)
    pred = jax.nn.sigmoid(pred_confidence[..., 0].astype(jnp.float32))
    m = mask.astype(jnp.float32)
    return (((pred - target) ** 2) * m).sum() / jnp.maximum(m.sum(), 1.0)


def next_token_loss(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token cross-entropy of a causal decoder: logits (b, n, v)
    at positions 0..n-1 against `targets` (b, n), the tokens at 1..n; float32,
    as log-sum-exp less the target's logit (no (b, n, v) log-softmax is kept)."""
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
