"""The causal token decoder (`model/decoder.py`: latent attention, routed
experts) against the benchmark's plain reference
(`benchmark/kanana2_reference.py`, which imports nothing of the program), at
a small size on the CPU, in the manner of `test_parity.py`: each new block on
the module's own parameter tree, forward and `jax.grad`, in float32 and with
bfloat16 activations; the expert layer's share tied to the uncut layer; the
static buffer against the dense masked sum; and the dropless rule."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu import train
from alphafold2_tpu.model import decoder
from alphafold2_tpu.ops import attention as ops_attn
from benchmark import kanana2_reference as plain
from benchmark import reference, weights
from benchmark.families import kanana2 as family

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NX = reference.Numerics("f32")
N, BATCH = 128, 2          # positions: the least the blocked kernel admits
SHARES = 8
DTYPES = [(jnp.float32, 1e-5), (jnp.bfloat16, 4e-2)]
IDS = ["float32", "bfloat16"]


def _config(**changes) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kanana2_30b_a3b_ep8.json")) as f:
        return {**json.load(f), **family.TINY, **changes}


CFG = _config()
DIM = CFG["hidden_size"]


def _draw(module, *inputs, seed=3):
    """The module's tree under the family's draw (nothing at zero)."""
    class Shapes:                     # what `weights.make_params` asks
        centre_and_width = staticmethod(family.centre_and_width)

        @staticmethod
        def param_shapes(m):
            return jax.eval_shape(
                lambda k: m.init(k, *inputs), jax.random.PRNGKey(0))
    return weights.make_params(Shapes, module, seed)


def _close(got, want, tol, what):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert got.shape == want.shape, what
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= tol * scale, what


def _forward_and_gradient(program, reference_fn, params, x, tol):
    """`program(params, x)` and `reference_fn(params["params"], x)` (one row
    of the batch at a time) agree, and so do their gradients under one
    cotangent, by every leaf."""
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    rows = lambda p, x: jnp.stack([reference_fn(p["params"], r) for r in x])
    got, got_vjp = jax.vjp(lambda p, x: program(p, x).astype(jnp.float32),
                           params, x)
    want, want_vjp = jax.vjp(rows, params, x)
    _close(got, want, tol, "forward")
    flat = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    for (path, g), (_, w) in zip(flat(got_vjp(cot)), flat(want_vjp(cot))):
        _close(g, w, tol, jax.tree_util.keystr(path))


def _x(seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (BATCH, N, DIM))


def _attention(dtype):
    return decoder.MLAttention(
        heads=CFG["num_attention_heads"], qk_nope_dim=CFG["qk_nope_head_dim"],
        qk_rope_dim=CFG["qk_rope_head_dim"], v_head_dim=CFG["v_head_dim"],
        kv_lora_rank=CFG["kv_lora_rank"], rope_theta=CFG["rope_theta"],
        dtype=dtype)


@pytest.mark.parametrize("dtype,tol", DTYPES, ids=IDS)
def test_latent_attention(dtype, tol):
    module = _attention(dtype)
    params = _draw(module, _x())
    _forward_and_gradient(
        module.apply, functools.partial(plain._attention, NX, CFG),
        params, _x(), tol)


def test_latent_attention_through_the_blocked_kernel():
    """The door a TPU takes, interpreted."""
    module = _attention(jnp.float32)
    params = _draw(module, _x())
    with ops_attn.pallas_attention():
        kernel = jax.jit(module.apply)(params, _x())
    _close(kernel, module.apply(params, _x()), 1e-5, "kernel against XLA")
    with ops_attn.pallas_attention():
        _forward_and_gradient(
            module.apply, functools.partial(plain._attention, NX, CFG),
            params, _x(), 1e-4)


@pytest.mark.parametrize("dtype,tol", DTYPES, ids=IDS)
def test_dense_layer(dtype, tol):
    module = decoder.SwiGLU(CFG["intermediate_size"], norm=True, dtype=dtype)
    params = _draw(module, _x())
    _forward_and_gradient(
        module.apply, lambda p, h: plain._swiglu(NX, p, plain._rms(
            p["norm"], h, CFG["rms_norm_eps"])), params, _x(), tol)


def _expert_layer(cfg, dtype, start=None, held=None, capacity_factor=None):
    return decoder.ExpertLayer(
        router_experts=cfg["router_experts"],
        experts_held=cfg["n_routed_experts"] if held is None else held,
        expert_start=cfg["expert_start"] if start is None else start,
        experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"],
        capacity_factor=capacity_factor or cfg["capacity_factor"],
        dtype=dtype)


@pytest.mark.parametrize("dtype,tol", DTYPES, ids=IDS)
def test_expert_layer_static_buffer_against_the_dense_masked_sum(dtype, tol):
    """Held experts 2..3 of 8: the buffer's rows, every one computed, give
    what the reference's per-token sum over the held experts gives. With
    bfloat16 activations the router still reads float32: no choice flips."""
    cfg = _config(expert_start=2)
    module = _expert_layer(cfg, dtype)
    params = _draw(module, _x())
    _forward_and_gradient(
        lambda p, x: module.apply(p, x)[0],
        functools.partial(plain.expert_layer, NX, cfg), params, _x(), tol)
    counters = module.apply(params, _x())[1]
    assert int(counters["expert_overflow"]) == 0
    assert 0 < int(counters["expert_slots"]) \
        < BATCH * N * cfg["num_experts_per_tok"]


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each share holds 2 of 16 experts and routes over all 16; their routed
    parts, with the shared expert counted once, are the uncut reference's
    layer."""
    cfg = _config(router_experts=2 * SHARES)
    whole = _expert_layer(cfg, jnp.float32, start=0, held=2 * SHARES)
    params = _draw(whole, _x())["params"]
    x = _x()
    want = jnp.stack([plain.expert_layer(NX, cfg, params, row, start=0,
                                         held=2 * SHARES) for row in x])
    shared = jnp.stack([plain._swiglu(NX, params["shared_expert"], plain._rms(
        params["expert_router"]["norm"], row, cfg["rms_norm_eps"]))
        for row in x])
    total, slots = -(SHARES - 1) * shared, 0
    for share in range(SHARES):
        mine = dict(params, expert_mlp=jax.tree.map(
            lambda stack: stack[2 * share:2 * share + 2],
            params["expert_mlp"]))
        out, counters = _expert_layer(cfg, jnp.float32, start=2 * share).apply(
            {"params": mine}, x)
        assert int(counters["expert_overflow"]) == 0
        total, slots = total + out, slots + int(counters["expert_slots"])
    _close(total, want, 1e-5, "the shares' sum")
    assert slots == BATCH * N * cfg["num_experts_per_tok"]  # every slot once


@pytest.mark.parametrize("dtype,tol", DTYPES, ids=IDS)
def test_head_and_loss(dtype, tol):
    """Embedding, final norm, head and the next-token loss: a decoder of no
    layers against the reference's."""
    cfg = _config(num_hidden_layers=0, dtype=jnp.dtype(dtype).name)
    model = family.build_model(cfg)
    params = weights.make_params(family, model, 5)
    batch = family.train_batch(5, 0, cfg, dict(batch=BATCH, tokens=N))
    tokens = jnp.asarray(batch["tokens"])

    def program(p):
        logits, _ = model.apply(p, tokens[:, :-1])
        return train.losses.next_token_loss(logits, tokens[:, 1:])

    def reference_loss(p):
        return jnp.mean(jnp.stack([plain.train_loss(p, cfg, one) for one in
                                   family.reference_examples(batch)]))
    got, got_grad = jax.value_and_grad(program)(params)
    want, want_grad = jax.value_and_grad(reference_loss)(params)
    _close(got, want, tol, "loss")
    for g, w in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
        _close(g, w, tol, "gradient")


def _first_step(cfg, seed=3, tokens=16):
    model = family.build_model(cfg)
    params = weights.make_params(family, model, seed)
    state = train.TrainState.create(
        apply_fn=model.apply, params=params, tx=train.adam(3e-4),
        rng=jax.random.PRNGKey(0))
    batch = family.train_batch(seed, 0, cfg,
                               dict(batch=BATCH, tokens=tokens))
    return jax.jit(family.train_step(model))(state, batch)[1], params, batch


def test_the_whole_step_against_the_reference():
    metrics, params, batch = _first_step(CFG)
    want = jnp.mean(jnp.stack([plain.train_loss(params, CFG, one) for one in
                               family.reference_examples(batch)]))
    _close(metrics["loss"], want, 1e-5, "loss")
    assert int(metrics["expert_overflow"]) == 0
    assert 0 < float(metrics["expert_slots"]) <= int(
        metrics["expert_max_load"]) * CFG["n_routed_experts"]


def test_an_overflow_is_counted_and_fails_the_step():
    """A buffer too small for its slots (a tile an expert and one more): the
    slots left out are counted, and the family's step reports a NaN loss;
    none is dropped silently."""
    metrics, _, _ = _first_step(_config(capacity_factor=0.01), tokens=64)
    assert int(metrics["expert_overflow"]) > 0
    assert np.isnan(float(metrics["loss"]))


def test_the_buffer_is_static_and_takes_every_slot_at_the_bound():
    """Rows and row tile from the shapes alone; at `capacity_factor` =
    router_experts / experts_held the buffer holds all of a step's slots and
    a tile of padding an expert: no routing can overflow it. The tile is 128
    rows where a held expert expects 128 slots or more (768 and 320 in the
    decoder cells), 8 below."""
    assert decoder.expert_buffer(16384, 6, 128, 16, 8.0) == (
        16384 * 6 + 16 * 128, 128)
    assert decoder.expert_buffer(16384, 6, 128, 16, 2.0) == (
        24576 + 16 * 128, 128)
    assert decoder.expert_buffer(8192, 10, 256, 8, 25.6) == (
        65536 + 8 * 128, 128)
    assert decoder.expert_buffer(2048, 8, 128, 16, 8.0)[1] == 128
    assert decoder.expert_buffer(2040, 8, 128, 16, 8.0)[1] == 8
    assert decoder.expert_buffer(32, 2, 8, 2, 4.0) == (32 * 2 + 2 * 8, 8)
    model = family.build_model(_config())
    assert model.expert_rows(32) == 32 * 2 + 2 * 8


def test_the_expert_layer_through_the_grouped_kernels():
    """The door a TPU takes, interpreted: the Pallas grouped matmuls give
    what XLA's gathered weights give, forward and gradient."""
    cfg = _config(expert_start=2)
    module = _expert_layer(cfg, jnp.float32)
    params = _draw(module, _x())
    with ops_attn.pallas_attention():
        _forward_and_gradient(
            lambda p, x: module.apply(p, x)[0],
            functools.partial(plain.expert_layer, NX, cfg), params, _x(),
            1e-5)


@pytest.mark.parametrize("dtype,tol", DTYPES, ids=IDS)
def test_the_kernels_compute_only_the_tiles_the_routing_filled(
        dtype, tol, monkeypatch):
    """Held experts 2..3 of 8, a buffer of 66 tiles of 8 rows: the grouped
    kernels and the row moves, under the TPU interpreter (unwritten memory
    reads NaN), compute the filled prefix alone, and the layer's output and
    every parameter's gradient, all finite, are the every-tile path's (XLA's
    gathered weights and gathered rows): nothing reads a row past the
    prefix. `expert_tiles` is the sum over the held experts of
    max(ceil(load / tile), 1)."""
    from jax.experimental.pallas import tpu as pltpu

    from alphafold2_tpu.ops import expert_rows
    from alphafold2_tpu.ops import grouped_matmul as gm
    cfg = _config(expert_start=2)
    module = _expert_layer(cfg, dtype)
    x = _x()
    params = _draw(module, x)

    def run(p):
        y, vjp, counters = jax.vjp(lambda p: module.apply(p, x), p,
                                   has_aux=True)
        cot = jax.random.normal(jax.random.PRNGKey(9), y.shape, y.dtype)
        return y, vjp(cot)[0], counters
    want, want_grad, _ = run(params)
    monkeypatch.setattr(
        decoder, "grouped_matmul",
        lambda x, w, tg, live_tiles=None, interpret=False: gm.grouped_matmul(
            x, w, tg, live_tiles, interpret=pltpu.InterpretParams()))
    moved = []
    for name in ("dispatch_rows", "combine_rows"):
        def interpreted(*args, _move=getattr(expert_rows, name), _name=name,
                        **kwargs):
            moved.append((_name, kwargs["interpret"]))
            return _move(*args, **kwargs)
        monkeypatch.setattr(expert_rows, name, interpreted)
    with ops_attn.pallas_attention():
        got, got_grad, counters = run(params)
    # the row moves ran, interpreted (the TPU interpreter, off the chip)
    assert sorted(moved) == [("combine_rows", True), ("dispatch_rows", True)]
    _close(got, want, tol, "output")
    flat = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    for (path, g), (_, w) in zip(flat(got_grad), flat(want_grad)):
        assert np.isfinite(np.asarray(g, np.float32)).all()
        _close(g, w, tol, jax.tree_util.keystr(path))

    held, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    _, choice, _ = decoder.ExpertRouter(
        cfg["router_experts"], k, cfg["routed_scaling_factor"],
        dtype=dtype).apply(
        {"params": params["params"]["expert_router"]}, x.reshape(-1, DIM))
    local = np.asarray(choice).ravel() - 2
    load = np.bincount(local[(local >= 0) & (local < held)], minlength=held)
    rows, tile = decoder.expert_buffer(BATCH * N, k, cfg["router_experts"],
                                       held, cfg["capacity_factor"])
    tiles = sum(max(-(-int(n) // tile), 1) for n in load)
    assert int(counters["expert_tiles"]) == tiles < rows // tile
