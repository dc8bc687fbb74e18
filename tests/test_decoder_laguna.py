"""The token decoder's grouped-query attention, window and full layers mixed
(`model/decoder.GroupedAttention`, the `laguna` layer), against the
benchmark's plain reference (`benchmark/laguna_reference.py`, which imports
nothing of the program), at a small size on the CPU: the whole model's loss
and gradients with both layer types present; the banded kernel (interpreted)
at the band's edge, with groups of 6 and 9 query heads; YaRN with partial
rotary against its formula; the head share and the expert share tied to the
uncut layer; and the other decoder's parameter tree as it was."""

import functools
import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu import train
from alphafold2_tpu.model import decoder
from alphafold2_tpu.ops import attention as ops_attn
from benchmark import laguna_reference as plain
from benchmark import reference, weights
from benchmark.families import kanana2, laguna

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NX = reference.Numerics("f32")
SHARES = 4


def _config(**changes) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna_s21_ep32.json")) as f:
        return {**json.load(f), **laguna.TINY, **changes}


CFG = _config()
DIM = CFG["hidden_size"]


def _close(got, want, tol, what):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert got.shape == want.shape, what
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= tol * scale, what


def _draw(module, *inputs, seed=3):
    """The module's tree under the family's draw (nothing at zero)."""
    class Shapes:
        centre_and_width = staticmethod(laguna.centre_and_width)

        @staticmethod
        def param_shapes(m):
            return jax.eval_shape(lambda k: m.init(k, *inputs),
                                  jax.random.PRNGKey(0))
    return weights.make_params(Shapes, module, seed)


def test_the_whole_model_against_the_reference():
    """Logits, loss and every gradient of a tiny decoder whose five layers
    hold both attentions (full, three windows of 6 keys, full over 16
    positions), the dense layer and the expert layers."""
    assert set(laguna._layer_types(CFG)) == {"full_attention",
                                            "sliding_attention"}
    model = laguna.build_model(CFG)
    params = weights.make_params(laguna, model, 5)
    batch = laguna.train_batch(5, 0, CFG, dict(batch=1, tokens=16))
    tokens = jnp.asarray(batch["tokens"])

    def program(p):
        logits, counters = model.apply(p, tokens[:, :-1])
        return train.losses.next_token_loss(logits, tokens[:, 1:]), (
            logits, counters)

    (got, (got_logits, counters)), got_grad = jax.jit(jax.value_and_grad(
        program, has_aux=True))(params)
    want, want_grad = jax.jit(jax.value_and_grad(
        lambda p: laguna.reference_loss(
            p, CFG, laguna.reference_examples(batch)[0])))(params)
    _close(got_logits[0], jax.jit(lambda p, ids: plain.logits(p, CFG, ids))(
        params, tokens[0, :-1]), 1e-5, "logits")
    _close(got, want, 1e-5, "loss")
    flat = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    for (path, g), (_, w) in zip(flat(got_grad), flat(want_grad)):
        _close(g, w, 1e-4, jax.tree_util.keystr(path))
    assert int(counters["expert_overflow"]) == 0
    layer = params["params"]["layers_1"]
    assert "window_attention" in layer and "bias" not in \
        layer["moe"]["expert_router"]


def _edge_case(heads, kv, batch, seed):
    n, d = 768, 128
    key = jax.random.PRNGKey(seed)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (batch, h, n, d))
               for i, h in enumerate((heads, kv, kv)))
    return q * d ** -0.5, k, v


@pytest.mark.parametrize("heads,kv,batch", [(6, 1, 2), (18, 2, 1)],
                         ids=["group_of_6", "group_of_9"])
def test_the_window_kernel_at_the_bands_edge(heads, kv, batch):
    """Interpreted, against masked dense attention: a query at i sees key
    i - 511 and not key i - 512 (the kernel reads as the window of 512, and
    unlike 511 or 513); the grouped key head is the one the reference
    reads, and the batch folds into the heads."""
    q, k, v = _edge_case(heads, kv, batch, heads)
    got = ops_attn.causal_attention(q, k, v, window=512, interpret=True)
    rows = slice(512, None)           # the rows the band cuts
    gap = lambda w: float(jnp.abs(
        got - ops_attn.causal_attention_reference(q, k, v, w))[
        :, :, rows].max())
    assert gap(512) < 1e-5
    assert gap(511) > 1e-3 and gap(513) > 1e-3


def test_the_window_kernels_gradient():
    """The dq and dk/dv kernels (a key head summed over its group of 9) give
    the masked dense attention's gradient."""
    q, k, v = _edge_case(18, 2, 1, 7)
    cot = jax.random.normal(jax.random.PRNGKey(9), (1, 18, 768, 128))
    loss = lambda fn: lambda q, k, v: (fn(q, k, v) * cot).sum()
    got = jax.grad(loss(functools.partial(
        ops_attn.causal_attention, window=512, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(functools.partial(
        ops_attn.causal_attention_reference, window=512)),
        argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        _close(g, w, 1e-4, "d" + name)


def test_the_reference_window_is_the_latest_512_keys():
    """With q = 0 a query averages the keys it sees: with v the key's
    position, row i reads (max(0, i - 511) + i) / 2."""
    n = 1024
    v = jnp.broadcast_to(jnp.arange(n, dtype=jnp.float32)[:, None],
                         (1, 1, n, 128))
    out = ops_attn.causal_attention_reference(
        jnp.zeros((1, 3, n, 128)), jnp.zeros((1, 1, n, 128)), v, 512)
    i = np.arange(n)
    np.testing.assert_allclose(out[0, :, :, 0], np.broadcast_to(
        (np.maximum(0, i - 511) + i) / 2, (3, n)), rtol=1e-5)


def test_yarn_with_partial_rotary_is_the_formula():
    """The published full layer's RoPE, written out by hand: 64 of 128
    dimensions turned, frequency i blended between theta^(-2i/64) and that
    over 128 by a ramp from dimension 9 to 18 (floor and ceil of where 32
    and 1 turns fit 8,192 positions), cos and sin times 1.4852; the other 64
    passed through. The program and the reference agree with it."""
    rope = CFG["rope_parameters"]["full_attention"]
    turns = lambda r: 64 * math.log(8192 / (2 * math.pi * r)) \
        / (2 * math.log(5e5))
    assert (math.floor(turns(32)), math.ceil(turns(1))) == (9, 18)
    base = 5e5 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 9) / 9, 0, 1)
    freq = base * (1 - ramp) + base / 128 * ramp
    np.testing.assert_allclose(decoder.yarn_frequencies(
        64, 5e5, 128, 8192, 32, 1), freq, rtol=1e-6)
    np.testing.assert_allclose(plain.yarn_inverse_frequencies(64, rope),
                               freq, rtol=1e-6)

    n = 300
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 128))
    angle = np.arange(n)[:, None] * freq[None, :]
    cos, sin = (1.4852030263919618 * f(angle) for f in (np.cos, np.sin))
    a, b = np.asarray(x[:, :32]), np.asarray(x[:, 32:64])
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin,
                           np.asarray(x[:, 64:])], -1)
    np.testing.assert_allclose(decoder.rope_by_type(x, rope), want,
                               atol=2e-4)
    np.testing.assert_allclose(plain._rotate(x, rope), want, atol=2e-4)


def _grouped(heads, kv, kind="sliding_attention"):
    return decoder.GroupedAttention(
        heads=heads, kv_heads=kv, head_dim=CFG["head_dim"],
        rope=CFG["rope_parameters"][kind],
        window=CFG["sliding_window"] if kind == "sliding_attention"
        else None)


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_the_four_head_shares_add_up_to_the_uncut_layer(kind):
    """An uncut layer of 8 key heads and 24 (or 32) query heads against four
    shares of 2 and 6 (or 8), each on its own columns of W_q, W_k, W_v and
    the gate and rows of W_o (the norm alike in all): the shares' outputs
    sum to the uncut layer's, the program's and the reference's."""
    group = 3 if kind == "full_attention" else 4
    heads, kv, hd = group * 8, 8, CFG["head_dim"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, DIM))
    whole = _grouped(heads, kv, kind)
    params = _draw(whole, x)["params"]
    want = jax.jit(whole.apply)({"params": params}, x)
    cfg = dict(CFG, layer_types=[kind], num_attention_heads_per_layer=[heads],
               num_key_value_heads=kv)
    _close(want, jax.jit(jax.vmap(functools.partial(
        plain._attention, NX, cfg, 0, params)))(x), 1e-5,
        "the uncut layer against the reference")
    per = heads // SHARES
    cols = lambda w, width, s: w[:, s * width:(s + 1) * width]
    total = 0.0
    for s in range(SHARES):
        mine = dict(params,
                    q_proj={"kernel": cols(params["q_proj"]["kernel"],
                                           per * hd, s)},
                    k_proj={"kernel": cols(params["k_proj"]["kernel"],
                                           kv // SHARES * hd, s)},
                    v_proj={"kernel": cols(params["v_proj"]["kernel"],
                                           kv // SHARES * hd, s)},
                    head_gate={"kernel": cols(params["head_gate"]["kernel"],
                                              per, s)},
                    o_proj={"kernel": params["o_proj"]["kernel"][
                        s * per * hd:(s + 1) * per * hd]})
        total = total + jax.jit(_grouped(per, kv // SHARES, kind).apply)(
            {"params": mine}, x)
    _close(total, want, 1e-5, "the shares' sum")


def _expert_layer(start, held):
    return decoder.ExpertLayer(
        router_experts=CFG["router_experts"], experts_held=held,
        expert_start=start, experts_per_token=CFG["num_experts_per_tok"],
        expert_width=CFG["moe_intermediate_size"], shared_experts=1,
        routed_scale=CFG["moe_routed_scaling_factor"],
        capacity_factor=CFG["router_experts"] / held,
        correction_bias=False)


def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """Routing with no correction bias over 8 experts, 2 a token: each share
    holds 2 of them; its routed part, with the one shared expert counted
    once, sum to the uncut reference's layer, and every slot is routed
    once."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, DIM))
    held = CFG["router_experts"] // SHARES
    whole = _expert_layer(0, CFG["router_experts"])
    params = _draw(whole, x)["params"]
    assert "bias" not in params["expert_router"]
    want = jax.jit(jax.vmap(lambda row: plain.expert_layer(
        NX, CFG, params, row, start=0, held=CFG["router_experts"])))(x)
    shared = jax.jit(jax.vmap(lambda row: plain._swiglu(
        NX, params["shared_expert"], plain._rms(
            params["expert_router"]["norm"], row, CFG["rms_norm_eps"]))))(x)
    total, slots = -(SHARES - 1) * shared, 0
    for s in range(SHARES):
        mine = dict(params, expert_mlp=jax.tree.map(
            lambda stack: stack[s * held:(s + 1) * held],
            params["expert_mlp"]))
        out, counters = jax.jit(_expert_layer(s * held, held).apply)(
            {"params": mine}, x)
        assert int(counters["expert_overflow"]) == 0
        total, slots = total + out, slots + int(counters["expert_slots"])
    _close(total, want, 1e-5, "the shares' sum")
    assert slots == x.shape[0] * x.shape[1] * CFG["num_experts_per_tok"]


# sha256 over every leaf's path, shape and dtype of the `kanana2` family's
# parameter tree, (the configuration file, its TINY cut): taken on the parent
# commit (PR 36), before the decoder took a second attention
KANANA_TREES = {
    "published": ("9d888057f1a57fd1a718a40dd1f383ef0ca2c03fc4aa784ff86ddbe9e"
                  "84e1aa3", 575_955_968),
    "tiny": ("e8cc7e91a3879abd702e26b8b756b09e659d988d4d04052068865d10ae5861"
             "72", 36_384)}


@pytest.mark.parametrize("size", sorted(KANANA_TREES))
def test_the_other_decoders_parameter_tree_is_as_it_was(size):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kanana2_30b_a3b_ep8.json")) as f:
        cfg = json.load(f)
    if size == "tiny":
        cfg.update(kanana2.TINY)
    flat = jax.tree_util.tree_flatten_with_path(
        kanana2.param_shapes(kanana2.build_model(cfg)))[0]
    text = ";".join(jax.tree_util.keystr(p) + str(tuple(x.shape))
                    + str(x.dtype) for p, x in flat)
    assert (hashlib.sha256(text.encode()).hexdigest(),
            sum(math.prod(x.shape) for _, x in flat)) == KANANA_TREES[size]


def test_the_configuration_holds_what_it_says():
    """602,680,320 parameters at the published widths, and the step's FLOPs
    count a window layer's band, not its triangle."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna_s21_ep32.json")) as f:
        cfg = json.load(f)
    model = laguna.build_model(cfg)
    hash(model)             # `readings.py` caches the step by its model
    shapes = laguna.param_shapes(model)
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) \
        == cfg["parameters"] == 602_680_320
    costs = laguna.kernel_costs(cfg, dict(batch=1, tokens=8192))
    window = 3 * 18 * 2 * 2 * 128 * (512 * 513 / 2 + (8192 - 512) * 512)
    full = 2 * 12 * 2 * 2 * 128 * 8192 * 8193 / 2
    proj = lambda heads: 2 * 8192 * 3072 * (128 * (2 * heads + 4) + heads)
    assert costs["window_attention"][0] == pytest.approx(
        3 * (window + 3 * proj(18)))
    assert costs["full_attention"][0] == pytest.approx(
        3 * (full + 2 * proj(12)))
