"""MSA masked-language-model self-supervision.

Parity with the reference (/root/reference/alphafold2_pytorch/mlm.py:11-92):
proportional subset masking per MSA row, mask/keep/random-replace split with
excluded token ids, CE loss over replaced positions only.

JAX differences: noising takes an explicit PRNG key (the reference uses
global torch RNG), and the loss uses a masked mean instead of boolean
indexing (`logits[mask]`, mlm.py:88) so shapes stay static for XLA.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import linen as nn

from alphafold2_tpu import constants


def get_mask_subset_with_prob(rng, mask: jnp.ndarray, prob: float) -> jnp.ndarray:
    """Sample ~prob fraction of True positions per row (reference
    mlm.py:11-25). mask: (b, n) bool -> (b, n) bool subset.

    Picks top-`ceil(prob*n)` random valid positions, then trims rows with
    fewer valid tokens so each row gets ~prob * (its valid count).
    """
    batch, seq_len = mask.shape
    max_masked = math.ceil(prob * seq_len)

    num_tokens = mask.sum(axis=-1, keepdims=True)
    mask_excess = jnp.cumsum(mask, axis=-1) > jnp.ceil(num_tokens * prob)
    mask_excess = mask_excess[:, :max_masked]

    rand = jnp.where(mask, jax.random.uniform(rng, mask.shape), -1e9)
    _, sampled = jax.lax.top_k(rand, max_masked)
    sampled = jnp.where(mask_excess, 0, sampled + 1)

    new_mask = jnp.zeros((batch, seq_len + 1), dtype=bool)
    new_mask = new_mask.at[jnp.arange(batch)[:, None], sampled].set(True)
    return new_mask[:, 1:]


class MLM(nn.Module):
    """MSA-MLM head + noising (reference mlm.py:27-92)."""

    dim: int
    num_tokens: int
    mask_id: int
    mask_prob: float = 0.15
    random_replace_token_prob: float = 0.1
    keep_token_same_prob: float = 0.1
    exclude_token_ids: tuple = (0,)

    def noise(self, rng, seq: jnp.ndarray, mask: jnp.ndarray):
        """BERT-style noising. seq: (b, m, n) int tokens; mask: (b, m, n).
        Returns (noised_seq, replaced_mask) both (b, m, n)."""
        b, num_msa, n = seq.shape
        seq_f = seq.reshape(b * num_msa, n)
        mask_f = mask.reshape(b * num_msa, n)

        excluded = mask_f
        for token_id in self.exclude_token_ids:
            excluded = excluded & (seq_f != token_id)

        k_subset, k_rand_subset, k_tokens = jax.random.split(rng, 3)
        mlm_mask = get_mask_subset_with_prob(k_subset, excluded, self.mask_prob)

        noised = jnp.where(mlm_mask, self.mask_id, seq_f)

        random_replace_mask = get_mask_subset_with_prob(
            k_rand_subset, mlm_mask,
            (1.0 - self.keep_token_same_prob) * self.random_replace_token_prob)
        random_tokens = jax.random.randint(
            k_tokens, seq_f.shape, 1, constants.NUM_AMINO_ACIDS)
        for token_id in self.exclude_token_ids:
            random_replace_mask = random_replace_mask & \
                (random_tokens != token_id)

        noised = jnp.where(random_replace_mask, random_tokens, noised)
        return noised.reshape(b, num_msa, n), mlm_mask.reshape(b, num_msa, n)

    @nn.compact
    def __call__(self, seq_embed, original_seq, replaced_mask):
        """CE loss over replaced positions (reference mlm.py:86-92).
        seq_embed: (b, m, n, d); original_seq/replaced_mask: (b, m, n)."""
        logits = nn.Dense(self.num_tokens, param_dtype=jnp.float32,
                          name="to_logits")(seq_embed.astype(jnp.float32))
        logp = jax.nn.log_softmax(logits, axis=-1)
        labels = jax.nn.one_hot(original_seq, self.num_tokens,
                                dtype=logp.dtype)
        ce = -(labels * logp).sum(-1)
        m = replaced_mask.astype(logp.dtype)
        return (ce * m).sum() / jnp.maximum(m.sum(), 1.0)
