"""The blocked causal attention of the token decoder
(`ops.attention.causal_attention`: keys wider than values, logits in VMEM
only) against masked dense attention, forward and gradient, interpreted on
the CPU; and the shapes it admits."""

import jax
import jax.numpy as jnp
import pytest

from alphafold2_tpu.ops import attention as ops_attn


def _operands(dtype, n=256, heads=2, dk=48, dv=32, batch=2):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (batch, heads, n, dk)) * dk ** -0.5
    k = jax.random.normal(keys[1], (batch, heads, n, dk))
    v = jax.random.normal(keys[2], (batch, heads, n, dv))
    g = jax.random.normal(keys[3], (batch, heads, n, dv))
    return tuple(t.astype(dtype) for t in (q, k, v, g))


def _out_and_grads(fn, q, k, v, g):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(g)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_causal_attention_matches_masked_dense_attention(dtype, tol):
    """Keys of another width than the values; the output, dq, dk and dv."""
    q, k, v, g = _operands(dtype)
    kernel = jax.jit(lambda *a: _out_and_grads(
        lambda q, k, v: ops_attn.causal_attention(q, k, v, interpret=True),
        *a))(q, k, v, g)
    f32 = lambda t: t.astype(jnp.float32)
    dense = _out_and_grads(ops_attn.causal_attention_reference,
                           *map(f32, (q, k, v, g)))
    for name, got, want in zip(("out", "dq", "dk", "dv"), kernel, dense):
        assert got.shape == want.shape and got.dtype == dtype, name
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(f32(got) - want).max()) <= tol * scale, name


def test_causal_attention_sees_no_later_key():
    """Changing the keys and values after a position leaves every output up
    to it as it was."""
    q, k, v, _ = _operands(jnp.float32)
    cut = 128
    k2 = k.at[:, :, cut:].add(3.0)
    v2 = v.at[:, :, cut:].add(-2.0)
    run = jax.jit(lambda q, k, v: ops_attn.causal_attention(
        q, k, v, interpret=True))
    a, b = run(q, k, v), run(q, k2, v2)
    assert bool(jnp.array_equal(a[:, :, :cut], b[:, :, :cut]))
    assert not bool(jnp.allclose(a[:, :, cut:], b[:, :, cut:]))


@pytest.mark.parametrize("n,admitted", [(128, True), (8192, True),
                                        (384, True), (100, False),
                                        (16, False)])
def test_causal_attention_admits_multiples_of_its_block(n, admitted):
    assert ops_attn.causal_admits(n) is admitted
    if not admitted:
        q = jnp.zeros((1, 1, n, 8))
        with pytest.raises(ValueError, match="multiple"):
            ops_attn.causal_attention(q, q, q, interpret=True)
