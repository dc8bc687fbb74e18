"""A causal token decoder with routed experts, for the trainer; its
attention latent in every layer (the `deepseek_v3` layer: kanana-2,
DeepSeek-V3), or grouped-query with a gate a head, full and windowed layers
mixed (the `laguna` layer: Laguna-S-2.1), layer by layer as the model's
`layer_attention` says.

    layer:  h = x + A(RMS(x));  y = h + F(RMS(h))
    F:      SwiGLU at the dense width in the first `first_dense` layers, the
            expert layer after them
    model:  embedding -> layers -> RMS -> untied head over the vocabulary held

RMS is RMSNorm with a learned scale; SwiGLU_w(u) = W_down(silu(W_gate u) *
W_up u); no biases anywhere. Activations are `dtype` (bf16 on the chip) over
float32 weights, as the Evoformer's; the router computes in float32.

Latent attention (`MLAttention`), in the expanded form a trainer runs:
q = W_q u -> heads x (nope | rope); [c | k_r] = W_kva u, c = RMS(c),
[k_nope | v] = W_kvb c per head; k = [k_nope | RoPE(k_r), one for all heads],
q = [q_nope | RoPE(q_rope)]; softmax(q k^T / sqrt(nope + rope) + causal) v.
RoPE pairs dimension i with i + rope / 2 (the half-split pairing; the
interleaved one differs by a fixed permutation of W_q's and W_kva's columns).
On a TPU the attention is `ops.attention.causal_attention` (blocked, logits
in VMEM only); elsewhere masked dense attention.

Grouped-query attention (`GroupedAttention`, named `full_attention` or
`window_attention` by its layer's type): H query heads over G key and value
heads, query head h reading key head h // (H / G), a sigmoid gate a query
head on the output before W_o; RoPE by the layer type's `rope_parameters`
(YaRN on part of each head, or plain); the window a causal band of keys. The
same kernel, told the band, visits only the blocks it touches. The layer
holds a share of the heads: one chip's of a tensor-parallel attention.

The expert layer (`ExpertLayer`) is TOLD which experts it holds
(`expert_start`, `experts_held`): one chip's share of an expert-parallel
layer. It routes over all `router_experts` (sigmoid scores in float32, the
top `experts_per_token` of score + bias, or of the score alone where the
router has no `correction_bias`, weights score / sum x `routed_scale`), and
adds the terms of its own experts only, plus the shared expert. What the
absent experts would add is left out; nothing stands in for the other chips
or their exchange.

The slots routed to held experts are laid, sorted by expert, into ONE
buffer of STATIC rows, each expert's group padded to whole row tiles, the
groups one after another from row 0 (a grouped matmul whose tile -> expert
map is data: `ops/grouped_matmul.py`). Where a slot lands is data (row
indices), never a shape, a grid extent or a trip count; so is how many
leading tiles the groups fill (`expert_tiles`), and the grouped matmuls
compute those alone. On a TPU the rows move between the tokens and the
buffer by two kernels, each the other's transpose (`ops/expert_rows.py`):
the dispatch writes the filled rows of the live tiles, one row DMA each,
and the rest of each live tile as zeros (the tiles past them unspecified);
the combine reads the held slots' rows alone, a token tile's range of each
held expert's group, and sums each token's weighted rows in float32. So the
expert layer's device time follows the routing by the live tiles and the
held slots, the rest of the step's does not. Off the TPU the rows move by
XLA's gathers in both directions (`_gather_rows`: the backward of a gather
is a gather through the inverse map, no scatter-add). Routing is dropless:
a slot beyond the buffer is COUNTED (`expert_overflow`) and the benchmark's
step turns any into a NaN loss; none is dropped silently. A buffer of
`capacity_factor` = router_experts / experts_held takes every slot a step
has, whatever the routing (router_experts x min(experts_per_token,
experts_held) / (experts_per_token x experts_held) is the least that does).

Every module's name is a kernel of `obs/device.py`'s table: `mla_attention`,
`full_attention`, `window_attention`, `expert_router` (scores, top-k, the
rows' indices, both row moves), `expert_mlp`, `shared_expert`, `dense_mlp`,
`lm_head` (embedding, head; the loss takes the same scope in the trainer).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from alphafold2_tpu import runtime
from alphafold2_tpu.model.evoformer import remat_block
from alphafold2_tpu.ops import attention as attention_ops
from alphafold2_tpu.ops import expert_rows
from alphafold2_tpu.ops.grouped_matmul import (grouped_matmul,
                                               grouped_matmul_reference)

ATTENTION_SCOPE = "mla_attention"
FULL_SCOPE = "full_attention"
WINDOW_SCOPE = "window_attention"
ROUTER_SCOPE = "expert_router"
DENSE_SCOPE = "dense_mlp"
HEAD_SCOPE = "lm_head"


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, keep_float32: bool = False):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps) * scale
        return y if keep_float32 else y.astype(self.dtype)


def _dense(features: int, dtype, name: str):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


class SwiGLU(nn.Module):
    """down(silu(gate u) * up u); `norm`: RMSNorm the input first."""
    width: int
    norm: bool = False
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        if self.norm:
            x = RMSNorm(self.eps, self.dtype, name="norm")(x)
        gate = _dense(self.width, self.dtype, "gate_proj")(x)
        up = _dense(self.width, self.dtype, "up_proj")(x)
        return _dense(x.shape[-1], self.dtype, "down_proj")(
            jax.nn.silu(gate) * up)


def rope(x, theta: float):
    """Rotary embedding along axis -2 (positions 0..n-1) of (..., n, d),
    dimension i paired with i + d / 2; float32 inside."""
    d = x.shape[-1]
    return rotary(x, theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def rotary(x, freq, scale=None):
    """x (..., n, d) turned at positions 0..n-1 by the angles position x
    `freq` (d / 2 of them), dimension i paired with i + d / 2; cos and sin
    times `scale` where one is given (YaRN's attention factor)."""
    n = x.shape[-2]
    angle = jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max_position_embeddings: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """The dim / 2 angular frequencies of YaRN (`rope_type` "yarn", as
    transformers' `_compute_yarn_parameters` makes them, `truncate` on):
    each the extrapolated theta^(-2i/dim) or that over `factor`, blended by
    a ramp between the dimensions that turn `beta_fast` and `beta_slow` times
    over the original context."""
    def dim_of(turns):
        return dim * math.log(original_max_position_embeddings
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), dim - 1)
    high = high + 0.001 if low == high else high
    extrapolated = theta ** (-np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    return (extrapolated / factor * ramp
            + extrapolated * (1.0 - ramp)).astype(np.float32)


def rope_by_type(x, params: dict):
    """A layer type's `rope_parameters` entry on (..., n, head_dim): the
    first `partial_rotary_factor` of each head's dimensions turned ("default"
    at `rope_theta`, or "yarn" with its cos and sin times
    `attention_factor`), the rest passed through."""
    d = x.shape[-1]
    dim = int(d * params.get("partial_rotary_factor", 1))
    theta = float(params["rope_theta"])
    if params["rope_type"] == "yarn":
        freq = yarn_frequencies(
            dim, theta, params["factor"],
            params["original_max_position_embeddings"], params["beta_fast"],
            params["beta_slow"])
        turned = rotary(x[..., :dim], jnp.asarray(freq),
                        params["attention_factor"])
    elif params["rope_type"] == "default":
        turned = rope(x[..., :dim], theta)
    else:
        raise ValueError(f"rope_type {params['rope_type']!r}")
    return turned if dim == d else jnp.concatenate(
        [turned, x[..., dim:]], axis=-1)


class MLAttention(nn.Module):
    heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_theta: float = 1e6
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, n, dim = x.shape
        h, nope, rot, dv = (self.heads, self.qk_nope_dim, self.qk_rope_dim,
                            self.v_head_dim)
        u = RMSNorm(self.eps, self.dtype, name="norm")(x)
        q = _dense(h * (nope + rot), self.dtype, "q_proj")(u)
        q = q.reshape(b, n, h, nope + rot).transpose(0, 2, 1, 3)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], self.rope_theta)], axis=-1)
        q = q * (nope + rot) ** -0.5

        ckv = _dense(self.kv_lora_rank + rot, self.dtype, "kv_a_proj")(u)
        c = RMSNorm(self.eps, self.dtype, name="kv_a_norm")(
            ckv[..., :self.kv_lora_rank])
        k_rope = rope(ckv[..., self.kv_lora_rank:], self.rope_theta)
        kv = _dense(h * (nope + dv), self.dtype, "kv_b_proj")(c)
        kv = kv.reshape(b, n, h, nope + dv).transpose(0, 2, 1, 3)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, None], (b, h, n, rot))], axis=-1)
        v = kv[..., nope:]

        # On a TPU the blocked kernel, by what the trace can see (as
        # `primitives.Attention` chooses the axial one); off it masked dense
        # attention, or the kernel interpreted behind the CPU tests' door.
        kernel = runtime.on_tpu() or attention_ops.pallas_attention_enabled()
        if kernel and attention_ops.causal_admits(n):
            out = attention_ops.causal_attention(
                q, k, v, interpret=not runtime.on_tpu())
        else:
            out = checkpoint_name(
                attention_ops.causal_attention_reference(q, k, v),
                attention_ops.KEPT_CAUSAL)
        out = out.transpose(0, 2, 1, 3).reshape(b, n, h * dv)
        return _dense(dim, self.dtype, "o_proj")(out)


class GroupedAttention(nn.Module):
    """Grouped-query attention with a sigmoid gate a head (the `laguna`
    layer): `heads` query heads over `kv_heads` key and value heads, query
    head h reading key head h // (heads / kv_heads); `window`: each query
    sees the `window` latest keys, itself included (None: all before it).
    The heads HELD: one chip's share of a tensor-parallel layer, whole
    groups of query heads with their key head. Which heads they are does not
    enter the arithmetic, only how many; what the absent heads would add
    through `o_proj` is left out, as the absent experts' part is.

        u = RMS(x); q = W_q u, k = W_k u, v = W_v u (heads of `head_dim`);
        q, k = RoPE by the layer type's `rope` entry;
        a_h = softmax(q_h k_g(h)^T / sqrt(head_dim) + mask) v_g(h);
        out = W_o [a_h sigmoid(W_g u)_h]_h
    """
    heads: int
    kv_heads: int
    head_dim: int
    rope: dict
    window: Optional[int] = None
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, n, dim = x.shape
        h, g, d = self.heads, self.kv_heads, self.head_dim
        u = RMSNorm(self.eps, self.dtype, name="norm")(x)
        by_head = lambda t, count: t.reshape(b, n, count, d).transpose(
            0, 2, 1, 3)
        q = by_head(_dense(h * d, self.dtype, "q_proj")(u), h)
        k = by_head(_dense(g * d, self.dtype, "k_proj")(u), g)
        v = by_head(_dense(g * d, self.dtype, "v_proj")(u), g)
        q = rope_by_type(q, self.rope) * d ** -0.5
        k = rope_by_type(k, self.rope)

        # the door as `MLAttention`'s
        kernel = runtime.on_tpu() or attention_ops.pallas_attention_enabled()
        if kernel and attention_ops.causal_admits(n):
            out = attention_ops.causal_attention(
                q, k, v, window=self.window, interpret=not runtime.on_tpu())
        else:
            out = checkpoint_name(
                attention_ops.causal_attention_reference(q, k, v,
                                                         self.window),
                attention_ops.KEPT_CAUSAL)
        gate = jax.nn.sigmoid(_dense(h, self.dtype, "head_gate")(u))
        out = out.transpose(0, 2, 1, 3) * gate[..., None]
        return _dense(dim, self.dtype, "o_proj")(out.reshape(b, n, h * d))


# a layer's attention by its kind, which is also its module's name and its
# kernel's in obs/device.py
ATTENTIONS = {ATTENTION_SCOPE: MLAttention, FULL_SCOPE: GroupedAttention,
              WINDOW_SCOPE: GroupedAttention}


@jax.custom_vjp
def _gather_rows(table, index, inverse):
    """table[index] for a (rows + 1, d) table whose last row is zeros (the
    row a padding index reads). `inverse` (rows + 1, m) lists, for each row
    of the table, the positions of `index.ravel()` that read it, padded with
    `index.size`: the backward pass is the gather through it, no scatter.
    The row moves' contract off the TPU (and the tests' reference for the
    kernels of `ops/expert_rows.py`): every row of the buffer and every
    (token, k) slot moves."""
    return jnp.take(table, index, axis=0)


# a `custom_vjp`'s rules are traced with no scope around them: name them
@jax.named_scope(ROUTER_SCOPE)
def _gather_rows_fwd(table, index, inverse):
    return jnp.take(table, index, axis=0), (index, inverse)


@jax.named_scope(ROUTER_SCOPE)
def _gather_rows_bwd(res, g):
    index, inverse = res
    flat = jnp.concatenate([g.reshape(index.size, g.shape[-1]),
                            jnp.zeros((1, g.shape[-1]), g.dtype)])
    return jnp.take(flat, inverse, axis=0).sum(axis=1), None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def expert_buffer(tokens: int, experts_per_token: int, router_experts: int,
                  experts_held: int, capacity_factor: float) -> tuple:
    """(rows, row tile) of the held experts' one buffer: `capacity_factor`
    times the slots a step of `tokens` tokens sends the held experts under
    even routing, rounded up to the grouped matmul's row tile, and a tile
    more for each held expert, since every group is padded to whole tiles.
    At `capacity_factor` = router_experts / experts_held the buffer takes
    every slot of the step: no routing can overflow it.

    The tile is 128 rows where a held expert expects 128 slots or more (8
    for a toy): the kernels compute and move the filled tiles alone (on a
    TPU the padding rows of a live tile are written as zeros, the tiles
    past them never), so what a tile costs is the padding of each group's
    last one and a step of each kernel's grid for each tile of the buffer.
    On a v5e, at 320 and at 768 slots an expert, 128 rows gave the expert
    layer its shortest time (against 256 and 512), when XLA's gathers still
    filled the whole buffer."""
    slots = math.ceil(capacity_factor * tokens * experts_per_token
                      * experts_held / router_experts)
    expected = tokens * experts_per_token / router_experts
    tile = 128 if expected >= 128 else 8
    return (-(-slots // tile) + experts_held) * tile, tile


class ExpertRouter(nn.Module):
    """RMS(h), and from it the scores, the choice and its weights, all
    float32: (u in `dtype`, choice (t, k), weights (t, k)).
    `correction_bias`: a bias that steers the choice (`deepseek_v3`'s
    `e_score_correction_bias`); without it the choice is the top k scores."""
    router_experts: int
    experts_per_token: int
    routed_scale: float
    correction_bias: bool = True
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        u32 = RMSNorm(self.eps, self.dtype, name="norm")(
            h, keep_float32=True)
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (h.shape[-1], self.router_experts))
        scores = jax.nn.sigmoid(jnp.dot(
            u32, kernel, precision=jax.lax.Precision.HIGHEST))
        steered = scores
        if self.correction_bias:
            bias = self.param("bias", nn.initializers.zeros_init(),
                              (self.router_experts,))
            # the bias steers the choice and takes no gradient
            steered = scores + jax.lax.stop_gradient(bias)
        _, choice = jax.lax.top_k(steered, self.experts_per_token)
        # the chosen scores by a one-hot product: its backward is dense,
        # where `take_along_axis`'s is a scatter-add
        picked = jnp.einsum("te,tke->tk", scores, jax.nn.one_hot(
            choice, self.router_experts, dtype=scores.dtype))
        weights = picked / picked.sum(-1, keepdims=True) * self.routed_scale
        return u32.astype(self.dtype), choice, weights


class Kernel(nn.Module):
    """A bare `kernel` of a shape of its own (a stack of the held experts'
    matrices, the head): LeCun over the last two axes."""
    shape: tuple

    @nn.compact
    def __call__(self):
        batch = tuple(range(len(self.shape) - 2))
        return self.param("kernel", nn.initializers.lecun_normal(
            in_axis=-2, out_axis=-1, batch_axis=batch), self.shape)


class ExpertMLP(nn.Module):
    """The held experts' SwiGLUs on their one buffer, (rows, d) in and out:
    three grouped matmuls (`ops/grouped_matmul.py`); `tile_group` says whose
    expert's rows a tile holds, `live_tiles` how many leading tiles hold
    rows (the rows out past them are unspecified on the kernels' path)."""
    experts_held: int
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, buf, tile_group, live_tiles):
        dim = buf.shape[-1]
        stack = lambda name, *shape: Kernel(
            (self.experts_held, *shape), name=name)().astype(self.dtype)
        # On a TPU the kernels, by what the trace can see; off it XLA's
        # gather of each tile's weights, or the kernels interpreted behind
        # the CPU tests' door (as the attention above)
        if runtime.on_tpu() or attention_ops.pallas_attention_enabled():
            mm = functools.partial(grouped_matmul, live_tiles=live_tiles,
                                   interpret=not runtime.on_tpu())
        else:
            mm = grouped_matmul_reference
        gate = mm(buf, stack("gate_proj", dim, self.width), tile_group)
        up = mm(buf, stack("up_proj", dim, self.width), tile_group)
        return mm(jax.nn.silu(gate) * up,
                  stack("down_proj", self.width, dim), tile_group)


class ExpertLayer(nn.Module):
    router_experts: int
    experts_held: int
    expert_start: int
    experts_per_token: int
    expert_width: int
    shared_experts: int
    routed_scale: float
    capacity_factor: float
    correction_bias: bool = True
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, n, dim = x.shape
        tokens, k, held = b * n, self.experts_per_token, self.experts_held
        rows, tile = expert_buffer(tokens, k, self.router_experts, held,
                                   self.capacity_factor)
        slots = tokens * k

        u, choice, weights = ExpertRouter(
            self.router_experts, k, self.routed_scale, self.correction_bias,
            self.eps, self.dtype, name=ROUTER_SCOPE)(x.reshape(tokens, dim))

        with jax.named_scope(ROUTER_SCOPE):
            local = (choice - self.expert_start).reshape(slots)
            is_held = (local >= 0) & (local < held)
            local = jnp.where(is_held, local, held)
            load = (local[:, None] == jnp.arange(held)[None, :]).sum(0)
            first = jnp.cumsum(load) - load      # an expert's first slot...
            # ...in the order of (expert, token), the slots of no held expert
            # last: two sorts, no scatter
            slot = jnp.arange(slots, dtype=jnp.int32)
            order = jnp.argsort(local * slots + slot)
            rank = jnp.argsort(order)
            place = rank - jnp.take(jnp.append(first, 0), local)
            # an expert's group: its slots, padded to whole tiles, at least
            # one; the groups lie one after another from row 0
            group = jnp.maximum(-(-load // tile), 1) * tile
            start = jnp.cumsum(group) - group
            # slot -> its row of the buffer (the zero row `rows` if none)
            at = jnp.take(jnp.append(start, rows), local) + place
            fits = is_held & (at < rows)
            row_of_slot = jnp.where(fits, at, rows)
            # tile -> its expert (the tiles past the last group are the last
            # expert's: rows of zeros), row -> its slot (`slots` if empty)
            tile_group = jnp.minimum(
                (jnp.arange(rows // tile)[:, None] * tile
                 >= (start + group)[None, :]).sum(1), held - 1
            ).astype(jnp.int32)
            # the tiles the groups fill, a prefix: the grouped matmuls
            # compute those alone, and the row moves touch no row past it
            live = jnp.minimum((start[-1] + group[-1]) // tile, rows // tile)
            row_group = jnp.repeat(tile_group, tile)
            within = jnp.arange(rows) - jnp.take(start, row_group)
            filled = within < jnp.take(load, row_group)
            slot_of_row = jnp.where(filled, jnp.take(order, jnp.minimum(
                jnp.take(first, row_group) + within, slots - 1)), slots)
            token_of_row = jnp.where(filled, slot_of_row // k, tokens)
            # On a TPU the row moves are the kernels, by what the trace can
            # see; off it XLA's gathers, or the kernels interpreted behind
            # the CPU tests' door (as the grouped matmuls)
            kernels = ((runtime.on_tpu()
                        or attention_ops.pallas_attention_enabled())
                       and expert_rows.admits(dim, self.dtype))
            if kernels:
                plan = expert_rows.plan_rows(
                    token_of_row, slot_of_row, row_of_slot, local, start,
                    live, k=k, tile=tile)
                moves = dict(scope=ROUTER_SCOPE,
                             interpret=not runtime.on_tpu())
                buf = expert_rows.dispatch_rows(u, plan, **moves)
            else:
                pad = lambda idx, fill: jnp.concatenate(
                    [idx, jnp.full((1,) + idx.shape[1:], fill, idx.dtype)])
                zero_row = jnp.zeros((1, dim), self.dtype)
                buf = _gather_rows(
                    jnp.concatenate([u, zero_row]), token_of_row,
                    pad(row_of_slot.reshape(tokens, k), rows))

        out = ExpertMLP(held, self.expert_width, self.dtype,
                        name="expert_mlp")(buf, tile_group, live)

        with jax.named_scope(ROUTER_SCOPE):
            if kernels:
                routed = expert_rows.combine_rows(out, weights, plan, **moves)
            else:
                back = _gather_rows(
                    jnp.concatenate([out, zero_row]),
                    row_of_slot.reshape(tokens, k),
                    pad(slot_of_row[:, None], slots))       # (tokens, k, d)
                w = jnp.where(fits.reshape(tokens, k), weights, 0.0)
                routed = jnp.einsum("tk,tkd->td", w,
                                    back.astype(jnp.float32))

        shared = SwiGLU(self.shared_experts * self.expert_width,
                        dtype=self.dtype, name="shared_expert")(u)
        with jax.named_scope(ROUTER_SCOPE):
            y = (routed.astype(self.dtype) + shared).reshape(b, n, dim)
            counters = {
                "expert_slots": load.sum(),
                "expert_overflow": (is_held & ~fits).sum(),
                "expert_max_load": load.max(),
                "expert_tiles": live,
            }
        return y, counters


class DecoderLayer(nn.Module):
    """One layer; `expert` chooses F, and the `kind` of `attention` (an entry
    of `ATTENTIONS`; latent attention where it names none) the attention,
    which takes the rest of `attention` as its fields and the kind as its
    name. Returns (y, the expert layer's counters; None for a dense
    layer)."""
    expert: bool
    attention: dict
    dense_width: int
    moe: dict
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        fields = dict(self.attention)
        kind = fields.pop("kind", ATTENTION_SCOPE)
        attended = ATTENTIONS[kind](**fields, eps=self.eps, dtype=self.dtype,
                                    name=kind)(x)
        # the residual sums take their branch's name too: the profile's
        # reader goes by names (obs/device.py)
        with jax.named_scope(kind):
            h = x + attended
        if self.expert:
            f, counters = ExpertLayer(**self.moe, eps=self.eps,
                                      dtype=self.dtype, name="moe")(h)
        else:
            f = SwiGLU(self.dense_width, norm=True, eps=self.eps,
                       dtype=self.dtype, name=DENSE_SCOPE)(h)
            counters = None
        with jax.named_scope(ROUTER_SCOPE if self.expert else DENSE_SCOPE):
            return h + f, counters


class LMHead(nn.Module):
    """The embedding and the untied head, under one name. The embedding's
    backward is a one-hot contraction, not a scatter-add."""
    vocab_size: int
    dim: int
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.embedding = self.param(
            "embedding", nn.initializers.normal(1.0),
            (self.vocab_size, self.dim))
        self.norm = RMSNorm(self.eps, self.dtype)
        self.head = Kernel((self.dim, self.vocab_size))

    def embed(self, tokens):
        with jax.named_scope(HEAD_SCOPE):
            return _embed(self.embedding.astype(self.dtype), tokens)

    def __call__(self, x):
        """float32 logits over the vocabulary held."""
        return jnp.einsum("bnd,dv->bnv", self.norm(x),
                          self.head().astype(self.dtype),
                          preferred_element_type=jnp.float32)


@jax.custom_vjp
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


@jax.named_scope(HEAD_SCOPE)
def _embed_fwd(table, tokens):
    return jnp.take(table, tokens, axis=0), (tokens, table.shape[0])


@jax.named_scope(HEAD_SCOPE)
def _embed_bwd(res, g):
    tokens, vocab = res
    one_hot = jax.nn.one_hot(tokens.reshape(-1), vocab, dtype=g.dtype)
    grad = jnp.einsum("tv,td->vd", one_hot, g.reshape(-1, g.shape[-1]),
                      preferred_element_type=jnp.float32)
    return grad.astype(g.dtype), None


_embed.defvjp(_embed_fwd, _embed_bwd)


class CausalDecoder(nn.Module):
    """tokens (b, n) -> (float32 logits (b, n, vocab held), counters).

    `counters`: `expert_slots` (slots routed to held experts, mean over the
    expert layers), `expert_overflow` (slots beyond their expert's rows, all
    layers), `expert_max_load` (the fullest expert's slots, any layer),
    `expert_tiles` (row tiles the grouped matmuls compute, mean over the
    expert layers). `expert_rows` gives the rows of one layer's buffer.
    """
    vocab_size: int
    hidden_size: int
    num_layers: int
    first_dense: int
    dense_width: int
    expert_width: int
    router_experts: int
    experts_held: int
    experts_per_token: int
    shared_experts: int
    routed_scale: float
    # latent attention in every layer, of these sizes...
    heads: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    kv_lora_rank: int = 0
    rope_theta: float = 1e6
    # ...or one `DecoderLayer.attention` a layer, its kind and its fields
    # (`FrozenDict`s: a module's fields are hashed)
    layer_attention: tuple = ()
    expert_start: int = 0
    capacity_factor: float = 2.0
    correction_bias: bool = True
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    def expert_rows(self, tokens: int) -> int:
        """Rows of one expert layer's buffer for a step of `tokens`."""
        return expert_buffer(tokens, self.experts_per_token,
                             self.router_experts, self.experts_held,
                             self.capacity_factor)[0]

    @nn.compact
    def __call__(self, tokens):
        head = LMHead(self.vocab_size, self.hidden_size, self.eps, self.dtype,
                      name=HEAD_SCOPE)
        x = head.embed(tokens)
        latent = dict(
            heads=self.heads, qk_nope_dim=self.qk_nope_dim,
            qk_rope_dim=self.qk_rope_dim, v_head_dim=self.v_head_dim,
            kv_lora_rank=self.kv_lora_rank, rope_theta=self.rope_theta)
        attention = self.layer_attention or (latent,) * self.num_layers
        moe = dict(
            router_experts=self.router_experts,
            experts_held=self.experts_held, expert_start=self.expert_start,
            experts_per_token=self.experts_per_token,
            expert_width=self.expert_width,
            shared_experts=self.shared_experts,
            routed_scale=self.routed_scale,
            capacity_factor=self.capacity_factor,
            correction_bias=self.correction_bias)
        # each layer made again for its backward, but for the attention
        # kernel's output and log-sum-exp (1/15 of a layer's activations)
        layer_cls = remat_block((attention_ops.KEPT_CAUSAL,),
                                block=DecoderLayer, static_argnums=(),
                                prevent_cse=True)
        counted = []
        for i in range(self.num_layers):
            x, counters = layer_cls(
                expert=i >= self.first_dense, attention=attention[i],
                dense_width=self.dense_width, moe=moe, eps=self.eps,
                dtype=self.dtype, name=f"layers_{i}")(x)
            if counters is not None:
                counted.append(counters)
        total = lambda key, fn: fn(jnp.stack([c[key] for c in counted])) \
            if counted else jnp.zeros((), jnp.int32)
        with jax.named_scope(ROUTER_SCOPE):
            counters = {
                "expert_slots": total("expert_slots", jnp.mean),
                "expert_overflow": total("expert_overflow", jnp.sum),
                "expert_max_load": total("expert_max_load", jnp.max),
                "expert_tiles": total("expert_tiles", jnp.mean),
            }
        return head(x), counters
