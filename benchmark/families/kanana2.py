"""The kanana2 family: what the benchmark knows of the program's causal token
decoder with latent attention and routed experts
(`alphafold2_tpu.model.decoder.CausalDecoder`; the interface is set out in
`benchmark/families/__init__.py`). A configuration's file keeps the keys of
the published `config.json` (`deepseek_v3`); `n_routed_experts` counts the
experts HELD here, `router_experts` the router's width.

The draw follows the first family's rule: matrices one LeCun init wide, the
projections that close a residual branch (`o_proj`, `down_proj`) a fifth of
one, vectors 0.05 around what their initializer gives (1 for a norm's scale,
0 for the router's bias); the embedding at unit width, so that the residual
stream is of the size its norms expect. Nothing is left at zero.

Beside the interface, for the readers this family's cells bring:
`kernel_costs` (a named kernel's FLOPs and bytes a step, for
`kernel_roofline`) and `expert_counters` (one forward pass's routing
counters, for `expert_fill`).
"""

from __future__ import annotations

import numpy as np

TINY = dict(family="kanana2", vocab_size=64, hidden_size=32,
            num_hidden_layers=3, num_attention_heads=2, qk_nope_head_dim=16,
            qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
            kv_lora_rank=16, intermediate_size=64, moe_intermediate_size=16,
            router_experts=8, n_routed_experts=2, num_experts_per_tok=2,
            capacity_factor=4.0, dtype="float32")
TINY_TRAFFIC = {"train_steps": dict(batch=2, tokens=16)}
CONTROL = "fp8"       # the configurations state bfloat16

_BRANCH_CLOSERS = ("o_proj", "down_proj")
_CLOSER_WIDTH = 0.2


def build_model(config: dict):
    import jax.numpy as jnp
    from alphafold2_tpu.model.decoder import CausalDecoder
    return CausalDecoder(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        first_dense=config["first_k_dense_replace"],
        heads=config["num_attention_heads"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], kv_lora_rank=config["kv_lora_rank"],
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        router_experts=config["router_experts"],
        experts_held=config["n_routed_experts"],
        expert_start=config["expert_start"],
        experts_per_token=config["num_experts_per_tok"],
        shared_experts=config["n_shared_experts"],
        routed_scale=config["routed_scaling_factor"],
        capacity_factor=config["capacity_factor"],
        rope_theta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["dtype"]))


def param_shapes(model, tokens: int = 8):
    """The shapes of `model.init`'s tree, traced and never run (they do not
    depend on the input's length)."""
    import jax
    import jax.numpy as jnp
    return jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, tokens), jnp.int32)),
        jax.random.PRNGKey(0))


def centre_and_width(names: tuple, shape) -> tuple:
    leaf, owner = names[-1], names[-2] if len(names) > 1 else ""
    if leaf == "embedding":
        return 0.0, 1.0
    if leaf == "kernel":
        width = _CLOSER_WIDTH if owner in _BRANCH_CLOSERS else 1.0
        return 0.0, width * shape[-2] ** -0.5
    return (1.0 if leaf == "scale" else 0.0), 0.05


# -- training ---------------------------------------------------------------

def train_step(model):
    """`train.make_decoder_train_step`, with the dropless rule on top: a
    step that left a routed slot out of its buffer reports a NaN loss, which
    the driver counts as a failed step."""
    import jax.numpy as jnp
    from alphafold2_tpu import train
    step = train.make_decoder_train_step(model)

    def dropless(state, batch):
        state, metrics = step(state, batch)
        metrics["loss"] = jnp.where(metrics["expert_overflow"] > 0, jnp.nan,
                                    metrics["loss"])
        return state, metrics
    return dropless


def train_batch(seed: int, step: int, config: dict, traffic: dict) -> dict:
    """Token ids uniform over the vocabulary held, from the seed and the
    step: (batch, tokens + 1), the model reads the first `tokens` of a row
    and is held to the last `tokens`."""
    rng = np.random.default_rng([int(seed), 7000 + step])
    return {"tokens": rng.integers(
        0, config["vocab_size"], (traffic["batch"], traffic["tokens"] + 1),
        dtype=np.int32)}


def train_batch_shapes(config: dict, traffic: dict) -> dict:
    return {"tokens": ((traffic["batch"], traffic["tokens"] + 1), np.int32)}


def reference_examples(batch: dict) -> list:
    """ONE example that holds all the batch's rows: the reference's loss
    takes them one at a time and sums their gradient in place (a gradient
    tree a row would not fit beside the check's state at 8,192 tokens)."""
    return [{"tokens": batch["tokens"]}]


def reference_loss(params, config: dict, example: dict, kind: str = "f32"):
    from benchmark import kanana2_reference
    return kanana2_reference.train_loss(params, config, example, kind)


# -- FLOPs and bytes, from the shapes ---------------------------------------

def _matrices(config: dict) -> dict:
    """Multiply-adds a token of each named kernel's projections, one layer
    (= the parameters of its matrices; the routed experts': one expert)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    kvb = config["qk_nope_head_dim"] + config["v_head_dim"]
    rank, rot = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return {
        "mla_attention": d * heads * qk + d * (rank + rot)
        + rank * heads * kvb + heads * config["v_head_dim"] * d,
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert_router": d * config["router_experts"],
        "expert_mlp": 3 * d * config["moe_intermediate_size"],
        "shared_expert": 3 * d * config["n_shared_experts"]
        * config["moe_intermediate_size"],
        "lm_head": d * config["vocab_size"]}


def _layers(config: dict) -> dict:
    dense = config["first_k_dense_replace"]
    expert = config["num_hidden_layers"] - dense
    return {"mla_attention": config["num_hidden_layers"], "dense_mlp": dense,
            "expert_router": expert, "expert_mlp": expert,
            "shared_expert": expert, "lm_head": 1}


def _routed_share(config: dict) -> float:
    """Held experts a token is routed to, under even routing: the EXPECTED
    rows of the experts' buffers a token (0.75 of 6)."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] \
        / config["router_experts"]


def forward_flops(config: dict, traffic: dict) -> dict:
    """{kernel: contraction FLOPs of one forward pass over the batch}: the
    causal half of the scores and values (a query at position i sees i + 1
    keys), the expected routed rows; nothing made again, no padding."""
    n = traffic["tokens"]
    tokens = traffic.get("batch", 1) * n
    per_token = {k: 2.0 * v for k, v in _matrices(config).items()}
    per_token["expert_mlp"] *= _routed_share(config)
    heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    per_token["mla_attention"] += \
        2.0 * heads * (qk + config["v_head_dim"]) * (n + 1) / 2
    return {k: tokens * per_token[k] * layers
            for k, layers in _layers(config).items()}


def step_flops(config: dict, traffic: dict) -> float:
    """The contraction FLOPs one optimizer step needs, the whole batch:
    forward once, backward twice; recomputation not counted."""
    return 3.0 * sum(forward_flops(config, traffic).values())


def kernel_costs(config: dict, traffic: dict) -> dict:
    """{kernel: (FLOPs, bytes) one step needs of it} for `kernel_roofline`.
    FLOPs: 3 x the forward's. Bytes: the float32 weights read forward and
    backward and their gradient written; the kernel's input and output
    activations read or written once forward, and their cotangents once
    backward, in the activations' type; the attention's q, k, v and output
    the same. Never more than ran: nothing made again, no padded row."""
    n = traffic["tokens"]
    tokens = traffic.get("batch", 1) * n
    act = 4 if config["dtype"] == "float32" else 2
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    forward, layers = forward_flops(config, traffic), _layers(config)
    weights = _matrices(config)
    weights["expert_mlp"] *= config["n_routed_experts"]
    rows = {k: tokens for k in weights}
    rows["expert_mlp"] = tokens * _routed_share(config)
    extra = {"mla_attention": tokens * heads * (2 * qk + 2
                                                * config["v_head_dim"])}
    costs = {}
    for k in weights:
        moved = 2 * rows[k] * d + extra.get(k, 0)      # in and out, + q k v o
        costs[k] = (3.0 * forward[k], layers[k] * (
            3.0 * 4 * weights[k] + 2.0 * act * moved))
    return costs


# -- routing counters -------------------------------------------------------

def expert_counters(run) -> dict:
    """One forward pass on the seed's first batch, on the benchmark's own
    weights: the expert layers' counters as numbers, and `expert_rows`, the
    rows of one layer's buffer (C)."""
    import jax
    batch = train_batch(run.seed, 0, run.config, run.traffic)
    tokens = batch["tokens"][:, :-1]
    counters = jax.device_get(jax.jit(
        lambda p, t: run.model.apply(p, t)[1])(run.params, tokens))
    out = {k: float(v) for k, v in counters.items()}
    out["expert_rows"] = run.model.expert_rows(tokens.size)
    return out
