"""Analytic FLOP accounting for benchmark/MFU claims (round-4 VERDICT #2).

Why not XLA ``cost_analysis``: it cannot see through custom calls — the
AMX FFI GEMMs on the CPU path and ``pallas_call`` kernels on TPU simply
vanish from its flop count (observed: reported tflops *fell* 10x when the
AMX kernels made the step 2x faster). Any MFU computed from it is wrong
exactly when the fast path is engaged.

The model here is analytic and backend-independent: trace the FORWARD
loss function once with every custom kernel disabled (pure
``dot_general``/``conv`` jaxpr — the trace is only counted, never run),
walk the jaxpr counting matmul/conv FLOPs, and charge the training step

    F_step = 3 x F_forward

— the standard accounting where each matmul's backward is two matmuls of
equal cost (input-grad + weight-grad). Elementwise/softmax/LN work is
excluded (negligible next to the contractions, and excluded by the MFU
convention), and rematerialized recompute is excluded BY CONSTRUCTION
(the forward trace contains each op once), so the resulting figure is
model FLOPs — the "MFU" numerator — not hardware FLOPs ("HFU"). The same
count applies to AMX-on/AMX-off/Pallas runs of one config by definition,
which is the agreement property the round-4 verdict demanded.

`lax.scan` bodies are counted once and multiplied by trip count;
`lax.cond` charges the most expensive branch; `shard_map` bodies count
per-device work times the number of devices doing DISTINCT work (mesh
axes appearing in the in/out specs — axes the operands are replicated
over are hardware redundancy, not model FLOPs); `while_loop` bodies are
charged for ONE trip (no static trip count exists — none of the benched
models put contractions in a while body; documented limitation).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.extend import core as jax_core


def _prod(xs) -> float:
    out = 1.0
    for x in xs:
        out *= float(x)
    return out


def _dot_general_flops(eqn) -> float:
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = _prod(lhs[i] for i in lb)
    k = _prod(lhs[i] for i in lc)
    m = _prod(d for i, d in enumerate(lhs) if i not in set(lc) | set(lb))
    n = _prod(d for i, d in enumerate(rhs) if i not in set(rc) | set(_rb))
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval.shape
    kernel = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    # kernel's in-channel dim already holds C_in/groups
    rhs_spec = dn.rhs_spec  # (out_c, in_c, *spatial) positions
    in_c = kernel[rhs_spec[1]]
    spatial = _prod(kernel[i] for i in rhs_spec[2:])
    return 2.0 * _prod(out) * in_c * spatial


def _iter_sub_jaxprs(params):
    for v in params.values():
        if isinstance(v, jax_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jax_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jax_core.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jax_core.Jaxpr):
                    yield x


def _shard_map_multiplier(params) -> float:
    """Number of devices doing DISTINCT work in a shard_map: the product
    of the sizes of mesh axes that actually appear in an in/out spec.
    Axes the operands are not sharded over hold replicas — replicated
    compute is hardware work, not model FLOPs, so it must not inflate
    the MFU numerator (e.g. a batch too small to tile the data axis
    makes the ring kernel drop that axis from its specs)."""
    used = set()
    for spec in tuple(params.get("in_specs", ())) + \
            tuple(params.get("out_specs", ())):
        for entry in tuple(spec):
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                used.update(entry)
            else:
                used.add(entry)
    try:
        shape = dict(params["mesh"].shape)
    except Exception:
        return 1.0
    return _prod(shape.get(a, 1) for a in used)


def count_jaxpr_flops(jaxpr) -> float:
    """Contraction FLOPs (dot_general + conv) of one jaxpr, recursive."""
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "scan":
            total += eqn.params["length"] * count_jaxpr_flops(
                eqn.params["jaxpr"].jaxpr)
        elif name == "while":
            # no static trip count: charge one iteration (documented)
            total += count_jaxpr_flops(eqn.params["body_jaxpr"].jaxpr)
        elif name == "cond":
            total += max(count_jaxpr_flops(b.jaxpr)
                         for b in eqn.params["branches"])
        elif name == "shard_map":
            inner = sum(count_jaxpr_flops(s)
                        for s in _iter_sub_jaxprs(eqn.params))
            total += _shard_map_multiplier(eqn.params) * inner
        else:
            # pjit / remat(checkpoint) / custom_vjp / custom_jvp / core
            # calls: count their sub-jaxpr once
            for sub in _iter_sub_jaxprs(eqn.params):
                total += count_jaxpr_flops(sub)
    return total


def forward_flops(fn, *args, **kwargs) -> float:
    """Contraction FLOPs of fn's forward pass (traced, never executed)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return count_jaxpr_flops(closed.jaxpr)


def train_step_flops(model, params, batch, rng=None) -> float:
    """Analytic FLOPs of one training step of `model` on `batch`:
    3 x forward contraction FLOPs of the composite loss (fwd 1x, bwd 2x).
    Optimizer update FLOPs (~10 x n_params elementwise) are excluded as
    negligible and non-contraction."""
    from alphafold2_tpu.ops.attention import pallas_attention
    from alphafold2_tpu.train.loop import compute_loss

    rng = jax.random.PRNGKey(0) if rng is None else rng
    # counting must see plain dot_general: a Pallas call hides its
    # contractions behind an opaque primitive
    with pallas_attention(False):
        fwd = forward_flops(
            lambda p, b: compute_loss(model, p, b, rng, train=True)[0],
            params, batch)
    return 3.0 * fwd


def evoformer_step_flops_formula(
    dim: int, depth: int, seq_len: int, msa_depth: int,
    heads: int = 8, dim_head: int = 64, batch: int = 1,
    num_tokens: int = 21, distogram_buckets: int = 37,
) -> float:
    """Closed-form cross-check of the dominant terms of the benched
    distogram train step (documented FLOP model, fwd x3). Per Evoformer
    layer, with L = seq_len, M = msa_depth, d = dim, h*dh = inner:

      MSA row/col attention:   QKV/out projections 4*(M*L)*d*inner each
                               axis + logits/AV 2*(L + M) contractions
      Pair tri-attn row/col:   projections over L^2 cells + L^3 logits/AV
      Triangle mult out/in:    2 mixes, each ~ L^3 * d einsum + 4 L^2 d^2
                               projections
      OuterMean:               L^2 * M * d_hidden outer + projections
      FeedForwards:            MSA (M*L) and pair (L^2) * 2*(2*4d*d + 4d*d)

    This intentionally re-derives the big-O structure only to sanity-check
    `train_step_flops` (the jaxpr count is the number of record); tests
    assert agreement of the leading L^3/L^2 terms within ~15%.
    """
    L, M, d = float(seq_len), float(msa_depth), float(dim)
    inner = float(heads * dim_head)
    b = float(batch)

    def attn(tokens, ctx):
        # q,k,v,out projections + gating: 5 GEMMs of tokens*d*inner
        proj = 5 * 2.0 * tokens * d * inner
        # logits + AV: 2 * tokens * ctx * inner
        core = 2 * 2.0 * tokens * ctx * inner
        return proj + core

    msa_tokens = M * L
    pair_tokens = L * L
    layer = 0.0
    layer += attn(msa_tokens, L)          # MSA row attention
    layer += attn(msa_tokens, M)          # MSA col attention
    layer += attn(pair_tokens, L) * 2     # triangle attn out + in
    # triangle multiplicative x2: left/right/out projections (+3 gates)
    # ~6 GEMMs of L^2*d*d, plus the L^3 mix einsum (2 * L^3 * d)
    layer += 2 * (6 * 2.0 * pair_tokens * d * d + 2.0 * L ** 3 * d)
    # outer mean: hidden d_h=d//4 typical? use d (upper bound, small term)
    layer += 2.0 * L * L * M * d + 2 * 2.0 * msa_tokens * d * d
    # feedforwards (GEGLU: in proj 2*4d, out proj 4d)
    ff = lambda tokens: 2.0 * tokens * d * (2 * 4 * d) + \
        2.0 * tokens * (4 * d) * d
    layer += ff(msa_tokens) + ff(pair_tokens)

    trunk = depth * layer
    # embeds + distogram head (small)
    heads_flops = 2.0 * pair_tokens * d * distogram_buckets + \
        2.0 * (L + msa_tokens) * num_tokens * d
    return 3.0 * b * (trunk + heads_flops)
