"""The plain reference of the `kanana2` family: one example's next-token loss
through a causal decoder with latent attention (MLA) and routed experts (the
`deepseek_v3` layer of kakaocorp/kanana-2-30b-a3b), in straightforward
`jax.numpy`, float32, every contraction at the highest precision.

It imports nothing of the program. It reads the weights the benchmark drew
(`weights.py`) by the names the parameter tree gives them. No kernel, no
buffer, no capacity: the attention is masked dense attention, one head and
one block of queries at a time; the expert layer is, per token, a dense sum
over the HELD experts of (the token's weight for that expert, nought where
the expert is not among its choice) x that expert's SwiGLU. Heads, query
blocks, experts, the loss's rows and each layer are made again in a backward
pass (`jax.checkpoint`), so that 8,192 tokens fit beside the check's state.

    layer:  h = x + MLA(RMS(x));  y = h + F(RMS(h))
    MLA:    q = W_q u -> heads x (nope | rope); [c | k_r] = W_kva u;
            [k_nope | v] = W_kvb RMS(c); k = [k_nope | RoPE(k_r)];
            softmax(q k^T / sqrt(nope + rope) + causal) v; W_o
    F:      SwiGLU(intermediate_size) in the first `first_k_dense_replace`
            layers; after them sum_i w_i SwiGLU^(e_i)(u) over the chosen
            experts THAT ARE HELD + SwiGLU(n_shared x moe width)(u), with
            s = sigmoid(W_r u), the choice the top k of s + bias, w = s[choice]
            / sum(s[choice]) x routed_scaling_factor

`kind` ("f32", "bf16", "fp8") goes to `reference.Numerics`: a lower precision
rounds the inputs of every contraction, the router's too, forward and
backward.

Departures from the published description (config.json and the
`deepseek_v3` modelling code it names):
- RoPE pairs dimension i with i + rope / 2; the published `rope_interleave`
  pairs 2i with 2i + 1. On drawn weights the two differ by a fixed
  permutation of W_q's and W_kva's rope columns.
- `e_score_correction_bias` steers the choice as published, but nothing
  nudges it between steps: the balancing update is a training recipe the
  config does not give. It takes no gradient.
- The layer holds `n_routed_experts` of `router_experts` experts, those from
  `expert_start`: one chip's share of an expert-parallel layer. What the
  absent experts would add is left out and that partial sum goes on.
- The vocabulary is the slice held: ids, logits and the loss are over it.
- The embedding is a lookup, not a contraction: no `kind` rounds it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import Numerics

QUERY_BLOCK = 512       # query rows of one head computed at once
LOSS_BLOCK = 1024       # rows of logits computed at once
MLP_BLOCK = 1024        # rows of the dense layer's SwiGLU computed at once


def _rms(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * p["scale"]


def _blocks(fn, x, block):
    """fn over blocks of x's leading axis (rows independent), each block made
    again in a backward pass."""
    fn = jax.checkpoint(fn)
    rows = jax.tree.leaves(x)[0].shape[0]
    if rows <= block or rows % block:
        return fn(x)
    split = jax.tree.map(lambda a: a.reshape(-1, block, *a.shape[1:]), x)
    out = jax.lax.map(fn, split)
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), out)


def _rope(x, theta):
    """x (n, ..., d) at positions 0..n-1: dimension i turns with i + d/2."""
    n, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None, :]
    angle = angle.reshape(n, *(1,) * (x.ndim - 2), half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def _swiglu(nx, p, u):
    gate = nx.ein("nd,dw->nw", u, p["gate_proj"]["kernel"])
    up = nx.ein("nd,dw->nw", u, p["up_proj"]["kernel"])
    return nx.ein("nw,wd->nd", jax.nn.silu(gate) * up,
                  p["down_proj"]["kernel"])


def _attention(nx, cfg, p, x):
    """One head at a time, from the normed input to that head's part of the
    output projection: no tensor of all heads is ever held."""
    n, d = x.shape
    heads, nope, rot, dv, rank = (
        cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    u = _rms(p["norm"], x, eps)
    ckv = nx.ein("nd,de->ne", u, p["kv_a_proj"]["kernel"])
    c = _rms(p["kv_a_norm"], ckv[:, :rank], eps)
    k_rope = _rope(ckv[:, rank:], theta)                      # (n, rot)
    scale = (nope + rot) ** -0.5
    by_head = lambda w, width: jnp.moveaxis(
        w.reshape(w.shape[0], heads, width), 1, 0)
    w_q = by_head(p["q_proj"]["kernel"], nope + rot)
    w_kv = by_head(p["kv_b_proj"]["kernel"], nope + dv)
    w_o = p["o_proj"]["kernel"].reshape(heads, dv, d)

    def one_head(w_qh, w_kvh, w_oh):
        q = nx.ein("nd,de->ne", u, w_qh)
        q = jnp.concatenate([q[:, :nope], _rope(q[:, nope:], theta)], -1)
        kv = nx.ein("nr,re->ne", c, w_kvh)
        k = jnp.concatenate([kv[:, :nope], k_rope], -1)
        v = kv[:, nope:]

        def queries(block):
            qb, rows = block
            logits = nx.ein("id,jd->ij", qb * scale, k)
            seen = rows[:, None] >= jnp.arange(n)[None, :]
            attn = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
            return nx.ein("ij,jd->id", attn, v)
        out = _blocks(queries, (q, jnp.arange(n)), QUERY_BLOCK)
        return nx.ein("ne,ed->nd", out, w_oh)

    term = jax.checkpoint(one_head)
    total, _ = jax.lax.scan(
        lambda total, w: (total + term(*w), None), jnp.zeros_like(x),
        (w_q, w_kv, w_o))
    return total


def expert_weights(nx, cfg, p, u):
    """(n, router_experts) float32: a token's weight for each expert, nought
    outside its choice."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(nx.ein("nd,de->ne", u, p["kernel"]))
    steered = jax.lax.stop_gradient(scores + p["bias"])
    kth = jnp.sort(steered, axis=-1)[:, -k][:, None]
    picked = jnp.where(steered >= kth, scores, 0.0)
    return picked / picked.sum(-1, keepdims=True) \
        * cfg["routed_scaling_factor"]


def expert_layer(nx, cfg, p, h, start=None, held=None):
    """F(RMS(h)) for the experts `start` .. `start + held` (the
    configuration's own share where left out), the shared expert with it."""
    start = cfg.get("expert_start", 0) if start is None else start
    held = cfg["n_routed_experts"] if held is None else held
    router = p["expert_router"]
    u = _rms(router["norm"], h, cfg["rms_norm_eps"])
    weights = expert_weights(nx, cfg, router, u)
    mine = jax.lax.dynamic_slice_in_dim(weights, start, held, axis=1)

    # one held expert's term for every token (the running sum stays outside
    # what is made again: a backward pass keeps no copy of it an expert)
    term = jax.checkpoint(
        lambda kernels, w: w[:, None] * _swiglu(nx, kernels, u))
    routed, _ = jax.lax.scan(
        lambda total, expert: (total + term(*expert), None),
        jnp.zeros_like(u), (p["expert_mlp"], mine.T))
    return routed + _swiglu(nx, p["shared_expert"], u)


def _layer(nx, cfg, expert, p, x):
    h = x + _attention(nx, cfg, p["mla_attention"], x)
    if expert:
        return h + expert_layer(nx, cfg, p["moe"], h)
    mlp = p["dense_mlp"]
    return h + _blocks(lambda rows: _swiglu(nx, mlp, _rms(
        mlp["norm"], rows, cfg["rms_norm_eps"])), h, MLP_BLOCK)


def train_loss(params, cfg: dict, example: dict, kind: str = "f32"):
    """Mean next-token cross-entropy over the vocabulary held of the rows of
    tokens an example holds, (n + 1,) or (rows, n + 1): one row at a time,
    each made again in a backward pass, so that the gradient is summed in
    place and two rows need no more memory than one."""
    nx = Numerics(kind)
    p = params["params"]

    def row_loss(tokens):
        x = p["lm_head"]["embedding"][tokens[:-1]]
        for i in range(cfg["num_hidden_layers"]):
            layer = jax.checkpoint(
                lambda lp, x, i=i: _layer(
                    nx, cfg, i >= cfg["first_k_dense_replace"], lp, x))
            x = layer(p[f"layers_{i}"], x)
        x = _rms(p["lm_head"]["norm"], x, cfg["rms_norm_eps"])

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
