"""The plain reference of the `laguna` family: one example's next-token loss
through a causal decoder with grouped-query attention, window and full layers
mixed, a gate a head, and routed experts beside one shared expert (the layer
of poolside/Laguna-S-2.1), in straightforward `jax.numpy`, float32, every
contraction at the highest precision.

It imports nothing of the program (it borrows the `kanana2` reference's
RMSNorm, SwiGLU and blocking). It reads the weights the benchmark drew
(`weights.py`) by the names the parameter tree gives them. No kernel, no
buffer, no capacity: the attention is masked dense attention, one head and
one block of 512 queries at a time, the window a mask; the expert layer is,
per token, a dense sum over the HELD experts of (the token's weight for that
expert, nought where the expert is not among its choice) x that expert's
SwiGLU. Heads, query blocks, experts, the loss's rows and each layer are made
again in a backward pass (`jax.checkpoint`), so that 8,192 tokens fit beside
the check's state.

    layer l: u = RMS(x); H_l query heads (48 full, 72 sliding, published),
             8 key and value heads, query head h reading g(h) = h // (H_l / 8)
             q = W_q u, k = W_k u, v = W_v u (heads of 128)
             full layers:    YaRN on the first 64 dims of q and k (theta 5e5,
                             factor 128 over 8,192 positions, beta 32 / 1),
                             cos and sin x attention_factor; 64 dims pass
             sliding layers: RoPE at theta 1e4 on all 128 dims
             a_h = softmax(q_h k_g(h)^T / sqrt(128) + M) v_g(h), M causal,
                   and on sliding layers also hiding j <= i - 512
             h = x + W_o [a_h sigmoid(W_g u)_h]_h
             y = h + SwiGLU_12288(RMS(h)) in layer 0; after it
             y = h + sum_i w_i SwiGLU_1024^(e_i)(u') + SwiGLU_1024^shared(u')
             with u' = RMS(h), s = sigmoid(W_r u') over 256 experts, the top
             10 chosen, w = s_chosen / sum(s_chosen) x 2.5
    model:   embedding -> layers -> RMS -> untied head

`kind` ("f32", "bf16", "fp8") goes to `reference.Numerics`: a lower precision
rounds the inputs of every contraction, the router's and the gate's too,
forward and backward.

Departures from the published description (config.json, `laguna`), the
configuration file's `reduced` and `assumed`:
- The layer holds `num_experts` of `router_experts` experts, those from
  `expert_start`: one chip's share of an expert-parallel layer. What the
  absent experts would add is left out and that partial sum goes on.
- It holds `num_attention_heads_per_layer[l]` query heads and
  `num_key_value_heads` key and value heads: one chip's share of a
  tensor-parallel attention, whole groups. What the absent heads would add
  through W_o is left out as well.
- The vocabulary is the slice held: ids, logits and the loss are over it.
- Assumed, the config naming none of it: sigmoid scores with no correction
  bias; the gate a sigmoid of a bias-free linear map of u, one logit a query
  head, applied before W_o; the shared expert ungated; no norm on q or k; the
  window HF's (a query sees i - j < 512); RoPE pairs dimension i with i + half
  the rotated width, YaRN's ramp truncated to whole dimensions.
- The embedding is a lookup, not a contraction: no `kind` rounds it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.kanana2_reference import (LOSS_BLOCK, MLP_BLOCK, QUERY_BLOCK,
                                         _blocks, _rms, _swiglu)
from benchmark.reference import Numerics


def yarn_inverse_frequencies(dim: int, rope: dict) -> np.ndarray:
    """YaRN's dim / 2 frequencies, written out: the dimension that turns r
    times over the original context is d(r) = dim ln(L / (2 pi r)) /
    (2 ln theta); below floor(d(beta_fast)) a dimension keeps theta^(-2i /
    dim), above ceil(d(beta_slow)) it is divided by `factor`, linearly
    between."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    length = rope["original_max_position_embeddings"]
    turns_at = lambda r: dim * math.log(length / (2 * math.pi * r)) \
        / (2 * math.log(theta))
    low = max(math.floor(turns_at(rope["beta_fast"])), 0)
    high = min(math.ceil(turns_at(rope["beta_slow"])), dim - 1)
    inverse = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    interpolated = np.clip((np.arange(dim // 2) - low) / max(high - low,
                                                             1e-3), 0, 1)
    return inverse * (1 - interpolated) + inverse / factor * interpolated


def _rotate(x, rope: dict):
    """x (n, head_dim) of one head at positions 0..n-1, by the layer type's
    `rope_parameters` entry."""
    n, d = x.shape
    dim = int(d * rope.get("partial_rotary_factor", 1))
    if rope["rope_type"] == "yarn":
        inverse, scale = yarn_inverse_frequencies(dim, rope), \
            rope["attention_factor"]
    else:
        inverse = 1.0 / float(rope["rope_theta"]) ** (
            np.arange(0, dim, 2) / dim)
        scale = 1.0
    angle = jnp.arange(n, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inverse, jnp.float32)[None, :]
    cos, sin = scale * jnp.cos(angle), scale * jnp.sin(angle)
    a, b = x[:, :dim // 2], x[:, dim // 2:dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[:, dim:]], -1)


def _attention(nx, cfg, layer: int, p, x):
    """One query head at a time, from the normed input to that head's part
    of the output projection: no tensor of all heads is ever held."""
    n, d = x.shape
    kind = cfg["layer_types"][layer]
    rope = cfg["rope_parameters"][kind]
    heads = cfg["num_attention_heads_per_layer"][layer]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    u = _rms(p["norm"], x, cfg["rms_norm_eps"])
    by_head = lambda w, count: jnp.moveaxis(
        w.reshape(w.shape[0], count, hd), 1, 0)
    k = jnp.stack([_rotate(nx.ein("nd,de->ne", u, w), rope)
                   for w in by_head(p["k_proj"]["kernel"], kv)])
    v = jnp.stack([nx.ein("nd,de->ne", u, w)
                   for w in by_head(p["v_proj"]["kernel"], kv)])
    gate = jax.nn.sigmoid(nx.ein("nd,dh->nh", u, p["head_gate"]["kernel"]))
    w_q = by_head(p["q_proj"]["kernel"], heads)
    w_o = p["o_proj"]["kernel"].reshape(heads, hd, d)
    group = jnp.arange(heads) // (heads // kv)

    def one_head(w_qh, w_oh, g, gate_h):
        q = _rotate(nx.ein("nd,de->ne", u, w_qh), rope) * hd ** -0.5
        k_h, v_h = k[g], v[g]

        def queries(block):
            qb, rows = block
            logits = nx.ein("id,jd->ij", qb, k_h)
            ago = rows[:, None] - jnp.arange(n)[None, :]
            seen = ago >= 0 if window is None else (ago >= 0) & (ago < window)
            attn = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
            return nx.ein("ij,jd->id", attn, v_h)
        out = _blocks(queries, (q, jnp.arange(n)), QUERY_BLOCK)
        return nx.ein("ne,ed->nd", out * gate_h[:, None], w_oh)

    term = jax.checkpoint(one_head)
    total, _ = jax.lax.scan(
        lambda total, w: (total + term(*w), None), jnp.zeros_like(x),
        (w_q, w_o, group, gate.T))
    return total


def expert_weights(nx, cfg, p, u):
    """(n, router_experts) float32: a token's weight for each expert, nought
    outside its choice; the choice the top k scores."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(nx.ein("nd,de->ne", u, p["kernel"]))
    kth = jnp.sort(jax.lax.stop_gradient(scores), axis=-1)[:, -k][:, None]
    picked = jnp.where(jax.lax.stop_gradient(scores) >= kth, scores, 0.0)
    return picked / picked.sum(-1, keepdims=True) \
        * cfg["moe_routed_scaling_factor"]


def expert_layer(nx, cfg, p, h, start=None, held=None):
    """F(RMS(h)) for the experts `start` .. `start + held` (the
    configuration's own share where left out), the shared expert with it."""
    start = cfg.get("expert_start", 0) if start is None else start
    held = cfg["num_experts"] if held is None else held
    router = p["expert_router"]
    u = _rms(router["norm"], h, cfg["rms_norm_eps"])
    mine = jax.lax.dynamic_slice_in_dim(expert_weights(nx, cfg, router, u),
                                        start, held, axis=1)
    # one held expert's term for every token (the running sum stays outside
    # what is made again: a backward pass keeps no copy of it an expert)
    term = jax.checkpoint(
        lambda kernels, w: w[:, None] * _swiglu(nx, kernels, u))
    routed, _ = jax.lax.scan(
        lambda total, expert: (total + term(*expert), None),
        jnp.zeros_like(u), (p["expert_mlp"], mine.T))
    return routed + _swiglu(nx, p["shared_expert"], u)


def _layer(nx, cfg, i: int, p, x):
    kind = {"full_attention": "full_attention",
            "sliding_attention": "window_attention"}[cfg["layer_types"][i]]
    h = x + _attention(nx, cfg, i, p[kind], x)
    if cfg["mlp_layer_types"][i] == "sparse":
        return h + expert_layer(nx, cfg, p["moe"], h)
    mlp = p["dense_mlp"]
    return h + _blocks(lambda rows: _swiglu(nx, mlp, _rms(
        mlp["norm"], rows, cfg["rms_norm_eps"])), h, MLP_BLOCK)


def _hidden(nx, p, cfg: dict, ids):
    """The normed output of the last layer for one row of ids (n,), each
    layer made again in a backward pass: what the head reads."""
    x = p["lm_head"]["embedding"][ids]
    for i in range(cfg["num_hidden_layers"]):
        layer = jax.checkpoint(lambda lp, x, i=i: _layer(nx, cfg, i, lp, x))
        x = layer(p[f"layers_{i}"], x)
    return _rms(p["lm_head"]["norm"], x, cfg["rms_norm_eps"])


def logits(params, cfg: dict, ids, kind: str = "f32"):
    """(n, vocab held) logits of one row of ids (n,), whole (tests)."""
    nx, p = Numerics(kind), params["params"]
    with jax.default_matmul_precision("highest"):
        return nx.ein("nd,dv->nv", _hidden(nx, p, cfg, ids),
                      p["lm_head"]["head"]["kernel"])


def train_loss(params, cfg: dict, example: dict, kind: str = "f32"):
    """Mean next-token cross-entropy over the vocabulary held of the rows of
    tokens an example holds, (n + 1,) or (rows, n + 1): one row at a time,
    each made again in a backward pass."""
    nx = Numerics(kind)
    p = params["params"]

    def row_loss(tokens):
        x = _hidden(nx, p, cfg, tokens[:-1])

        def rows(block):
            xb, target = block
            logits = nx.ein("nd,dv->nv", xb, p["lm_head"]["head"]["kernel"])
            top = logits.max(-1, keepdims=True)
            log_z = jnp.log(jnp.exp(logits - top).sum(-1)) + top[:, 0]
            return log_z - jnp.take_along_axis(
                logits, target[:, None], axis=-1)[:, 0]
        return jnp.mean(_blocks(rows, (x, tokens[1:]), LOSS_BLOCK))

    with jax.default_matmul_precision("highest"):
        tokens = jnp.atleast_2d(example["tokens"])
        if len(tokens) == 1:
            return row_loss(tokens[0])
        return jnp.mean(jax.lax.map(jax.checkpoint(row_loss), tokens))
