"""The laguna family: what the benchmark knows of the program's causal token
decoder with grouped-query attention, window and full layers mixed, and
routed experts (`alphafold2_tpu.model.decoder.CausalDecoder` with one
`GroupedAttention` a layer; the interface is set out in
`benchmark/families/__init__.py`). A configuration's file keeps the keys of
the published `config.json` (`laguna`); `num_experts` counts the experts
HELD here, `router_experts` the router's width, and
`num_attention_heads_per_layer` and `num_key_value_heads` the heads held.

The draw, the step with its dropless rule, the batches and the routing
counters are the `kanana2` family's: the same decoder, the same trainer, the
same rule (matrices one LeCun init wide, the branch closers `o_proj` and
`down_proj` a fifth of one, the embedding at unit width, vectors 0.05 round
what their initializer gives). What is this family's own: the model built
from the file, the plain reference, and the FLOPs and bytes by kernel, where
a window layer's scores are the band's.
"""

from __future__ import annotations

from benchmark.families import config_key
from benchmark.families.kanana2 import (  # noqa: F401  (the interface)
    centre_and_width, expert_counters, param_shapes, reference_examples,
    train_batch, train_batch_shapes, train_step)

TINY = dict(family="laguna", vocab_size=64, hidden_size=32,
            num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
            num_attention_heads_per_layer=[4, 6, 6, 6, 4],
            intermediate_size=64, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, router_experts=8,
            num_experts=2, num_experts_per_tok=2, capacity_factor=4.0,
            sliding_window=6, dtype="float32")
TINY_TRAFFIC = {"train_steps": dict(tokens=16)}
CONTROL = "fp8"       # the configurations state bfloat16

# a layer type of the file -> the program's attention (its kernel's name)
KINDS = {"full_attention": "full_attention",
         "sliding_attention": "window_attention"}


def _layer_types(config: dict) -> list:
    return config["layer_types"][:config["num_hidden_layers"]]


def _first_dense(config: dict) -> int:
    """The leading dense layers (`mlp_layer_types`); every later one has
    experts."""
    kinds = config["mlp_layer_types"][:config["num_hidden_layers"]]
    dense = next((i for i, k in enumerate(kinds) if k != "dense"),
                 len(kinds))
    if "dense" in kinds[dense:]:
        raise ValueError("a dense layer after an expert layer")
    return dense


def _attention(config: dict, i: int):
    """Layer i's `DecoderLayer.attention`, frozen: a flax module's fields
    are hashed (`readings.py` caches the step by its model)."""
    from flax.core import FrozenDict
    kind = _layer_types(config)[i]
    return FrozenDict(kind=KINDS[kind],
                      heads=config["num_attention_heads_per_layer"][i],
                      kv_heads=config["num_key_value_heads"],
                      head_dim=config["head_dim"],
                      rope=FrozenDict(config["rope_parameters"][kind]),
                      window=config["sliding_window"]
                      if kind == "sliding_attention" else None)


# every configuration a model was built from, by its scalars: the training
# check hands the reference a configuration's scalars alone (it keys its one
# trace on them), and the layer lists and the RoPE settings are no scalars
_BUILT = {}


def build_model(config: dict):
    import jax.numpy as jnp
    _BUILT[config_key(config)] = config
    from alphafold2_tpu.model.decoder import CausalDecoder
    layers = config["num_hidden_layers"]
    return CausalDecoder(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=layers, first_dense=_first_dense(config),
        layer_attention=tuple(_attention(config, i) for i in range(layers)),
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        router_experts=config["router_experts"],
        experts_held=config["num_experts"],
        expert_start=config["expert_start"],
        experts_per_token=config["num_experts_per_tok"],
        shared_experts=config["shared_expert_intermediate_size"]
        // config["moe_intermediate_size"],
        routed_scale=config["moe_routed_scaling_factor"],
        capacity_factor=config["capacity_factor"], correction_bias=False,
        eps=config["rms_norm_eps"], dtype=jnp.dtype(config["dtype"]))


def reference_loss(params, config: dict, example: dict, kind: str = "f32"):
    from benchmark import laguna_reference
    whole = {**_BUILT.get(config_key(config), {}), **config}
    return laguna_reference.train_loss(params, whole, example, kind)


# -- FLOPs and bytes, from the shapes ---------------------------------------

def _matrices(config: dict, heads: int) -> dict:
    """Multiply-adds a token of each named kernel's projections, one layer
    of `heads` query heads (= the parameters of its matrices; the routed
    experts': one expert)."""
    d, hd = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    return {
        "attention": d * hd * (2 * heads + 2 * kv) + d * heads,
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert_router": d * config["router_experts"],
        "expert_mlp": 3 * d * config["moe_intermediate_size"],
        "shared_expert": 3 * d * config["shared_expert_intermediate_size"],
        "lm_head": d * config["vocab_size"]}


def _keys_seen(config: dict, kind: str, n: int) -> float:
    """Keys a query sees, the mean over the n positions: (n + 1) / 2 under
    the causal mask, the band's sum_i min(i + 1, window) / n in a window
    layer (what the block grid visits beyond it is not work)."""
    if kind == "window_attention":
        w = min(config["sliding_window"], n)
        return (w * (w + 1) / 2 + (n - w) * w) / n
    return (n + 1) / 2


def _routed_share(config: dict) -> float:
    """Held experts a token is routed to, under even routing: the EXPECTED
    rows of the experts' buffers a token (10 x 8 / 256)."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["router_experts"]


def _per_layer(config: dict, traffic: dict):
    """(kernel, FLOPs a token of one forward pass, weights' multiply-adds,
    extra activations a token) for every named part of every layer, and the
    head: the expected routed rows, each layer's own heads and mask."""
    n = traffic["tokens"]
    hd, kv = config["head_dim"], config["num_key_value_heads"]
    first = _first_dense(config)
    parts = []
    for i, kind in enumerate(_layer_types(config)):
        heads = config["num_attention_heads_per_layer"][i]
        m = _matrices(config, heads)
        scores = 2.0 * heads * 2 * hd * _keys_seen(config, KINDS[kind], n)
        parts.append((KINDS[kind], 2.0 * m["attention"] + scores,
                      m["attention"], hd * (2 * heads + 2 * kv)))
        mlps = ("dense_mlp",) if i < first else (
            "expert_router", "expert_mlp", "shared_expert")
        for k in mlps:
            weights = m[k] * (config["num_experts"] if k == "expert_mlp"
                              else 1)
            flops = 2.0 * m[k] * (_routed_share(config)
                                  if k == "expert_mlp" else 1)
            parts.append((k, flops, weights, 0))
    m = _matrices(config, 0)
    parts.append(("lm_head", 2.0 * m["lm_head"], m["lm_head"], 0))
    return parts


def forward_flops(config: dict, traffic: dict) -> dict:
    """{kernel: contraction FLOPs of one forward pass over the batch}: each
    layer's own heads, the causal half of a full layer's scores and values
    and the band of a window layer's, the expected routed rows; nothing made
    again, no padding."""
    tokens = traffic.get("batch", 1) * traffic["tokens"]
    out = {}
    for kernel, flops, _, _ in _per_layer(config, traffic):
        out[kernel] = out.get(kernel, 0.0) + tokens * flops
    return out


def step_flops(config: dict, traffic: dict) -> float:
    """The contraction FLOPs one optimizer step needs, the whole batch:
    forward once, backward twice; recomputation not counted."""
    return 3.0 * sum(forward_flops(config, traffic).values())


def kernel_costs(config: dict, traffic: dict) -> dict:
    """{kernel: (FLOPs, bytes) one step needs of it} for `kernel_roofline`,
    by the `kanana2` family's rule. FLOPs: 3 x the forward's. Bytes: the
    float32 weights read forward and backward and their gradient written;
    the kernel's input and output activations read or written once forward,
    and their cotangents once backward, in the activations' type (the
    routed experts' rows: the expected ones); the attention's q, k, v and
    output the same. Never more than ran: nothing made again, no padded
    row."""
    tokens = traffic.get("batch", 1) * traffic["tokens"]
    act = 4 if config["dtype"] == "float32" else 2
    d = config["hidden_size"]
    costs = {}
    for kernel, flops, weights, extra in _per_layer(config, traffic):
        rows = tokens * (_routed_share(config) if kernel == "expert_mlp"
                         else 1)
        moved = 2 * rows * d + tokens * extra       # in and out, + q k v o
        f, b = costs.get(kernel, (0.0, 0.0))
        costs[kernel] = (f + 3.0 * tokens * flops,
                         b + 3.0 * 4 * weights + 2.0 * act * moved)
    return costs
