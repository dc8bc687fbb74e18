"""The scanned trunk's remat policy (`model/evoformer.py`): the rule that
chooses which marked values of a block the backward keeps from the forward
pass (a pure function of shapes, depth, dtype and the device's memory), and
the policy itself: keeping moves where a value comes from, never what it is.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.model import evoformer
from alphafold2_tpu.model.evoformer import (GIB, REMAT_HEADROOM, Evoformer,
                                            remat_name_bytes, remat_names)
from alphafold2_tpu.model.primitives import (KEPT_ATTENTION,
                                             KEPT_ATTENTION_OUT)

MIB = 2 ** 20
V5E_LIMIT = int(15.75 * GIB)        # memory_stats()["bytes_limit"], PR 22
DIM, INNER, MSA_ROWS = 256, 512, 128
# per-layer parameters of `af2_evo_d12`'s trunk, float32
LAYER_PARAM_BYTES = 5_200_640 * 4
ALL_NAMES = (KEPT_ATTENTION, KEPT_ATTENTION_OUT)


def _rule(crop, depth, limit, dtype=jnp.bfloat16):
    return remat_names((1, crop, crop, DIM), (1, MSA_ROWS, crop, DIM), depth,
                       dtype, limit, inner=INNER,
                       param_bytes=depth * LAYER_PARAM_BYTES)


def _bytes(crop, dtype=jnp.bfloat16):
    return dict(remat_name_bytes((1, crop, crop, DIM),
                                 (1, MSA_ROWS, crop, DIM), dtype, INNER))


def test_a_blocks_bytes_follow_from_the_shapes():
    """ISSUE 34's table at crop 256, MSA 128, bf16, in the rule's order."""
    assert remat_name_bytes((1, 256, 256, DIM), (1, MSA_ROWS, 256, DIM),
                            jnp.bfloat16, INNER) == (
        (KEPT_ATTENTION, 192 * MIB), (KEPT_ATTENTION_OUT, 96 * MIB))
    assert _bytes(256, jnp.float32)[KEPT_ATTENTION] == 384 * MIB


def test_nothing_is_kept_where_no_limit_is_visible():
    assert _rule(256, 12, None) == ()
    # and this process, on the CPU, sees none
    assert evoformer.device_bytes_limit() is None


def test_nothing_is_kept_under_a_mesh_of_more_than_one_device():
    from alphafold2_tpu.parallel import make_mesh, use_mesh
    from alphafold2_tpu.parallel.mesh import single_device_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    alone = _rule(256, 12, V5E_LIMIT)
    assert alone
    with use_mesh(make_mesh(1, 2, 1, devices=jax.devices()[:2])):
        assert _rule(256, 12, V5E_LIMIT) == ()
    with use_mesh(single_device_mesh()):     # as without a mesh
        assert _rule(256, 12, V5E_LIMIT) == alone


def test_nothing_is_kept_when_nothing_fits():
    assert _rule(256, 12, 4 * GIB) == ()
    assert _rule(256, 12, 0) == ()


@pytest.mark.parametrize("crop,depth", [(256, 12), (384, 12), (256, 48),
                                        (384, 48)])
def test_the_set_grows_with_the_limit_and_never_passes_it(crop, depth):
    """Monotone in the limit: a prefix of the fixed order, growing as the
    limit grows; and what is kept, times the depth, fits under the limit less
    the headroom (the rule reserves what the step needs with nothing kept
    besides, so this bound is loose)."""
    per_block = _bytes(crop)
    previous = ()
    for limit in range(0, 80 * GIB, GIB // 2):
        names = _rule(crop, depth, limit)
        assert names == ALL_NAMES[:len(names)]
        assert len(names) >= len(previous)
        kept = depth * sum(per_block[n] for n in names)
        assert kept <= max(0, limit - REMAT_HEADROOM)
        previous = names
    assert previous == ALL_NAMES          # with memory enough, everything


def test_what_a_v5e_keeps_at_the_shapes_the_compiler_was_asked():
    """The cell's shape keeps both names; larger shapes, where the compiler
    refused a fixed set of four (ISSUE 34: crop 384 / depth 12 "Used 15.92G
    of 15.75G", crop 256 / depth 48 "Used 23.76G"), end on fewer or none. The
    sets here compiled for a described v5e (PERF.md section 6, PR 34)."""
    assert _rule(256, 12, V5E_LIMIT) == ALL_NAMES
    assert _rule(384, 12, V5E_LIMIT) == (KEPT_ATTENTION,)
    assert _rule(256, 48, V5E_LIMIT) == ()
    assert _rule(384, 48, V5E_LIMIT) == ()


def _tiny_trunk(n, rows, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    x = jax.random.normal(ks[0], (1, n, n, 32), jnp.float32) * 0.5
    m = jax.random.normal(ks[1], (1, rows, n, 32), jnp.float32) * 0.5
    model = Evoformer(dim=32, depth=2, heads=2, dim_head=16)
    params = model.init(ks[2], x, m)
    # zero-initialised closers would leave most gradients at zero
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(ks[2], p.shape), params)
    return model, params, x, m


@pytest.fixture
def chosen(monkeypatch):
    """The names every `remat_block` call was given, with the rule steered
    by the memory the trace is told it sees (None: as on the CPU)."""
    calls = []
    real = evoformer.remat_block

    def spy(names=()):
        calls.append(tuple(names))
        return real(names)

    monkeypatch.setattr(evoformer, "remat_block", spy)

    def see(limit):
        monkeypatch.setattr(evoformer, "device_bytes_limit", lambda: limit)
        del calls[:]
        return calls
    return see


@pytest.mark.parametrize("door", ("xla", "kernel"))
def test_loss_and_gradients_are_those_with_nothing_kept(door, chosen,
                                                        monkeypatch):
    """A tiny scanned trunk in float32: with the richest set kept the loss
    and every gradient are those with nothing kept, to float32 rounding; and
    the backward then makes less again (fewer contractions in its jaxpr, and
    through the kernel's door no forward kernel call)."""
    from alphafold2_tpu.ops import attention as ops_attn
    monkeypatch.setattr(
        ops_attn, "fused_attention_merged",
        functools.partial(ops_attn.fused_attention_merged, interpret=True))
    n, rows = (64, 4) if door == "kernel" else (12, 4)
    model, params, x, m = _tiny_trunk(n, rows)

    def loss(p, x, m):
        xo, mo = model.apply(p, x, m)
        return (xo ** 2).mean() + (mo ** 2).mean()

    results, texts = {}, {}
    with ops_attn.pallas_attention(door == "kernel"):
        for limit in (None, 10 ** 15):
            calls = chosen(limit)
            # a function of its own a limit: the rule answers when the
            # program is traced, and a trace is kept by the function traced
            grad = jax.value_and_grad(functools.partial(loss),
                                      argnums=(0, 1, 2))
            texts[limit] = str(jax.make_jaxpr(grad)(params, x, m))
            results[limit] = jax.jit(grad)(params, x, m)
            assert set(calls) == {ALL_NAMES if limit else ()}, calls
    (l0, g0), (l1, g1) = results[None], results[10 ** 15]
    np.testing.assert_allclose(l1, l0, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g1),
                            jax.tree.leaves(g0)):
        scale = max(1e-3, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) <= 2e-5 * scale, \
            jax.tree_util.keystr(path)
    assert texts[10 ** 15].count("dot_general") \
        < texts[None].count("dot_general")
    if door == "kernel":
        # three kernel calls an attention with nothing kept (forward, the
        # forward again, backward), two with its output kept
        assert texts[None].count("pallas_call") * 2 \
            == texts[10 ** 15].count("pallas_call") * 3 > 0


def test_the_scan_and_the_pipeline_build_their_block_through_one_helper(
        chosen):
    """`Evoformer.__call__` and `_pipeline_forward` both call `remat_block`
    with the rule's names: under the pipeline's mesh that is nothing, whatever
    memory the trace sees."""
    from alphafold2_tpu.parallel import make_mesh, use_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 32)) * 0.5
    m = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 8, 32)) * 0.5
    kw = dict(dim=32, depth=2, heads=2, dim_head=16)
    plain, pp = Evoformer(**kw), Evoformer(**kw, pipeline_stages=2)
    params = plain.init(jax.random.PRNGKey(2), x, m)

    calls = chosen(V5E_LIMIT)
    xo, mo = plain.apply(params, x, m)
    assert calls == [ALL_NAMES]

    calls = chosen(V5E_LIMIT)
    with use_mesh(make_mesh(1, 1, 1, devices=jax.devices()[:2], pipe=2)):
        xp, mp = jax.jit(lambda p: pp.apply(p, x, m))(params)
    assert calls == [()]
    np.testing.assert_allclose(xp, xo, atol=1e-4)
    np.testing.assert_allclose(mp, mo, atol=1e-4)
