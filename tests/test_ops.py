"""Pallas fused-attention kernel tests (interpreter mode on CPU; the same
kernel lowers to Mosaic on TPU) and the model-path backend switch."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.ops import attention as ops_attn


def make_inputs(key, b=4, n=64, d=32):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, n, d)) * 0.5
    k = jax.random.normal(ks[1], (b, n, d)) * 0.5
    v = jax.random.normal(ks[2], (b, n, d))
    bias = jax.random.normal(ks[3], (b, n, n))
    return q, k, v, bias


def axial_inputs(key, batch, rows, heads, n, d, dtype, bias=True,
                 masks=True):
    """q/k/v in the axial layout (batch * rows * heads, n, d), the
    unrepeated bias and one mask for queries and keys whose FIRST row is
    fully padded."""
    ks = jax.random.split(key, 5)
    b_all = batch * rows * heads
    q = (jax.random.normal(ks[0], (b_all, n, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (b_all, n, d)) * 0.5).astype(dtype)
    v = jax.random.normal(ks[2], (b_all, n, d)).astype(dtype)
    bias = jax.random.normal(ks[3], (batch * heads, n, n)).astype(dtype) \
        if bias else None
    mask = None
    if masks:
        lengths = jax.random.randint(ks[4], (batch * rows, 1), n // 2, n + 1)
        mask = (jnp.arange(n)[None, :] < lengths).at[0].set(False)
    return q, k, v, bias, mask


# (batch, rows, heads, n, d, dtype, bias, masks, step overrides)
KERNEL_CASES = {
    "n64-f32": (2, 4, 2, 64, 32, jnp.float32, True, True, {}),
    "n64-bf16": (2, 4, 2, 64, 32, jnp.bfloat16, True, True, {}),
    "n128-bf16-no-bias": (1, 6, 2, 128, 16, jnp.bfloat16, False, True, {}),
    "n128-f32-no-masks": (1, 4, 2, 128, 16, jnp.float32, True, False, {}),
    "n256-bf16": (1, 3, 2, 256, 16, jnp.bfloat16, True, True, {}),
    "n256-f32-query-blocks": (1, 2, 1, 256, 16, jnp.float32, True, True,
                              {"block_q": 128}),
    "n384-bf16": (1, 2, 1, 384, 16, jnp.bfloat16, True, True, {}),
    "n640-bf16": (1, 2, 1, 640, 16, jnp.bfloat16, True, True, {}),
    "n640-f32": (1, 2, 1, 640, 16, jnp.float32, True, True, {}),
    "four-rows-a-step": (2, 8, 2, 64, 16, jnp.float32, True, True,
                         {"block_rows": 4}),
    "rows-the-step-does-not-divide": (1, 7, 2, 64, 16, jnp.float32, True,
                                      True, {"block_rows": 4}),
    "odd-rows-default-step": (1, 5, 2, 128, 16, jnp.bfloat16, True, True,
                              {}),
    "no-bias-no-masks": (1, 4, 2, 64, 16, jnp.float32, False, False, {}),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_matches_reference(case):
    """The kernel in interpret mode against `attention_reference`: both
    dtypes, with and without bias, `bias_repeat` > 1, query and key masks
    with a fully padded row, every bucket length, several rows a step and a
    row count the step does not divide."""
    batch, rows, heads, n, d, dtype, bias, masks, step = KERNEL_CASES[case]
    q, k, v, b, m = axial_inputs(jax.random.PRNGKey(n + rows), batch, rows,
                                 heads, n, d, dtype, bias, masks)
    kw = dict(heads=heads, bias_repeat=rows if bias else 1)
    out = ops_attn.fused_attention(q, k, v, b, m, m, interpret=True, **kw,
                                   **step)
    ref = ops_attn.attention_reference(q, k, v, b, m, m, **kw)
    assert out.dtype == dtype and bool(jnp.isfinite(out).all())
    # bf16: the reference rounds the probabilities of a whole row, the
    # kernel those of its own float32 accumulation: an ulp of O(1) outputs
    atol = 1e-5 if dtype == jnp.float32 else 4e-2
    assert np.allclose(np.asarray(out, np.float32),
                       np.asarray(ref, np.float32), atol=atol)
    if masks:   # the padded row reads the uniform average of v
        first = np.asarray(out, np.float32)[:heads]
        mean_v = np.asarray(v, np.float32)[:heads].mean(axis=1, keepdims=True)
        assert np.allclose(first, np.broadcast_to(mean_v, first.shape),
                           atol=atol)


@pytest.mark.parametrize("heads,d", [(2, 64), (4, 64), (8, 16), (2, 16)])
def test_values_are_read_from_the_key_value_projection(heads, d):
    """`v=None`: k is [k | v] as the Dense layer lays it out, and the index
    map (lane tiles of heads) or a split (heads that fill no tile) finds
    the values in its second half: the same output, and the same gradient,
    as with k and v apart."""
    rows, n = 3, 64
    q, k, v, bias, mask = axial_inputs(jax.random.PRNGKey(d), 1, rows, heads,
                                       n, d, jnp.float32)
    merged = lambda t: ops_attn.merge_heads(
        t.reshape(rows, heads, n, d))
    q, k, v = merged(q), merged(k), merged(v)
    kv = jnp.concatenate([k, v], axis=-1)
    kw = dict(bias=bias, q_mask=mask, k_mask=mask, heads=heads,
              bias_repeat=rows, interpret=True)
    apart = ops_attn.fused_attention_merged(q, k, v, **kw)
    together = ops_attn.fused_attention_merged(q, kv, **kw)
    np.testing.assert_array_equal(np.asarray(apart), np.asarray(together))
    g_apart = jax.grad(lambda k, v: jnp.sum(
        ops_attn.fused_attention_merged(q, k, v, **kw) ** 2),
        argnums=(0, 1))(k, v)
    g_together = jax.grad(lambda kv: jnp.sum(
        ops_attn.fused_attention_merged(q, kv, **kw) ** 2))(kv)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(g_apart, -1)),
                               np.asarray(g_together), rtol=1e-5, atol=1e-6)


def test_step_shape_follows_the_length():
    """Rows per step and the query block come from the shape: whole rows up
    to 640, two query blocks at 1,024, more rows a step the shorter they
    are, never more rows than there are; two heads of 64 lanes share a
    step, narrower heads fill a lane tile or take all their lanes."""
    shape = ops_attn._step_shape
    assert shape(640, 640, 640)[0] == 640
    assert shape(1024, 1024, 1024)[0] == 512
    assert shape(64, 64, 64)[1] >= shape(256, 256, 256)[1] \
        > shape(640, 640, 640)[1] >= 2
    assert shape(640, 640, 3)[1] <= 3 and shape(8, 8, 1)[1] == 1
    for n in (64, 128, 256, 384, 512, 640, 1024):
        assert n % shape(n, n, n)[0] == 0
    group = ops_attn._head_group
    assert group(8, 64) == 2 and group(8, 32) == 4 and group(8, 16) == 8
    assert group(2, 16) == 2 and group(1, 64) == 1 and group(3, 64) == 3


class TestFusedAttention:
    def test_matches_reference(self):
        q, k, v, bias = make_inputs(jax.random.PRNGKey(0))
        out = ops_attn.fused_attention(q, k, v, bias, interpret=True)
        ref = ops_attn.attention_reference(q, k, v, bias)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_blocked_queries(self):
        q, k, v, bias = make_inputs(jax.random.PRNGKey(1), n=128)
        out = ops_attn.fused_attention(q, k, v, bias, block_q=32,
                                       interpret=True)
        ref = ops_attn.attention_reference(q, k, v, bias)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_masked_bias(self):
        q, k, v, bias = make_inputs(jax.random.PRNGKey(2))
        bias = bias.at[:, :, 48:].set(-1e9)  # mask the key tail
        out = ops_attn.fused_attention(q, k, v, bias, interpret=True)
        ref = ops_attn.attention_reference(q, k, v, bias)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        assert bool(jnp.isfinite(out).all())

    def test_bf16_inputs(self):
        q, k, v, bias = make_inputs(jax.random.PRNGKey(3))
        qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
        out = ops_attn.fused_attention(qb, kb, vb, bias, interpret=True)
        ref = ops_attn.attention_reference(qb, kb, vb, bias)
        assert out.dtype == jnp.bfloat16
        assert np.allclose(np.asarray(out, np.float32),
                           np.asarray(ref, np.float32), atol=3e-2)

    def test_cross_attention_lengths(self):
        q, _, _, _ = make_inputs(jax.random.PRNGKey(4), n=64)
        _, k, v, _ = make_inputs(jax.random.PRNGKey(5), n=32)
        bias = jnp.zeros((4, 64, 32))
        out = ops_attn.fused_attention(q, k, v, bias, interpret=True)
        ref = ops_attn.attention_reference(q, k, v, bias)
        assert out.shape == (4, 64, 32)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_no_bias_no_mask(self):
        # the lean path: no dense bias tensor is ever allocated
        q, k, v, _ = make_inputs(jax.random.PRNGKey(6))
        out = ops_attn.fused_attention(q, k, v, interpret=True)
        ref = ops_attn.attention_reference(q, k, v)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_mask_vectors_expand_in_kernel(self):
        # masks arrive as (B//heads, N) vectors; fill happens in VMEM
        b, h, n, d = 2, 2, 64, 32
        q, k, v, _ = make_inputs(jax.random.PRNGKey(7), b=b * h, n=n, d=d)
        km = jnp.arange(n)[None, :] < jnp.array([[40], [56]])  # (b, n)
        qm = jnp.arange(n)[None, :] < jnp.array([[64], [48]])
        out = ops_attn.fused_attention(q, k, v, q_mask=qm, k_mask=km,
                                       heads=h, interpret=True)
        ref = ops_attn.attention_reference(q, k, v, q_mask=qm, k_mask=km,
                                           heads=h)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        # fully-masked query rows are finite (uniform softmax), not NaN
        assert bool(jnp.isfinite(out).all())

    def test_unrepeated_bias_index_map(self):
        # bias (batch*heads, nq, nk) is replayed over the folded axial
        # axis purely via the BlockSpec index map — the axial layout
        # B = batch * repeat * heads, head fastest
        batch, repeat, h, n, d = 2, 4, 2, 32, 16
        b_all = batch * repeat * h
        keys = jax.random.split(jax.random.PRNGKey(8), 4)
        q = jax.random.normal(keys[0], (b_all, n, d)) * 0.5
        k = jax.random.normal(keys[1], (b_all, n, d)) * 0.5
        v = jax.random.normal(keys[2], (b_all, n, d))
        bias = jax.random.normal(keys[3], (batch * h, n, n))
        out = ops_attn.fused_attention(q, k, v, bias, heads=h,
                                       bias_repeat=repeat, interpret=True)
        ref = ops_attn.attention_reference(q, k, v, bias, heads=h,
                                           bias_repeat=repeat)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_bias_and_masks_together(self):
        batch, repeat, h, n, d = 1, 2, 2, 32, 16
        b_all = batch * repeat * h
        keys = jax.random.split(jax.random.PRNGKey(9), 4)
        q = jax.random.normal(keys[0], (b_all, n, d)) * 0.5
        k = jax.random.normal(keys[1], (b_all, n, d)) * 0.5
        v = jax.random.normal(keys[2], (b_all, n, d))
        bias = jax.random.normal(keys[3], (batch * h, n, n))
        km = jnp.arange(n)[None, :] < 24
        km = jnp.broadcast_to(km, (batch * repeat, n))
        out = ops_attn.fused_attention(q, k, v, bias, k_mask=km, heads=h,
                                       bias_repeat=repeat, interpret=True)
        ref = ops_attn.attention_reference(q, k, v, bias, k_mask=km,
                                           heads=h, bias_repeat=repeat)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


class TestBackendSwitch:
    def test_flag_roundtrip(self):
        assert not ops_attn.pallas_attention_enabled()
        with ops_attn.pallas_attention(True):
            assert ops_attn.pallas_attention_enabled()
        assert not ops_attn.pallas_attention_enabled()

    def test_model_runs_with_pallas_backend(self, monkeypatch):
        """Run the full model through the Pallas path (interpreter mode on
        CPU) and compare against the XLA path — numerics must agree."""
        calls = []
        interpreted = functools.partial(ops_attn.fused_attention_merged,
                                        interpret=True)
        monkeypatch.setattr(
            ops_attn, "fused_attention_merged",
            lambda *a, **kw: calls.append(a[0].shape) or interpreted(*a, **kw))
        from alphafold2_tpu import Alphafold2
        model = Alphafold2(dim=32, depth=1, heads=2, dim_head=16)
        # 64 residues: the shortest rows the kernel's rule admits
        seq = jax.random.randint(jax.random.PRNGKey(6), (1, 64), 0, 21)
        msa = jax.random.randint(jax.random.PRNGKey(7), (1, 3, 64), 0, 21)
        params = model.init(jax.random.PRNGKey(8), seq, msa=msa)

        ret_xla = model.apply(params, seq, msa=msa)
        with ops_attn.pallas_attention(True):
            ret_pal = model.apply(params, seq, msa=msa)
        assert calls   # both triangle attentions and the MSA row attention
        assert np.allclose(np.asarray(ret_xla.distance),
                           np.asarray(ret_pal.distance), atol=2e-3)


def _attention_module_case(dropout=0.0):
    from alphafold2_tpu.model.primitives import Attention
    mod = Attention(dim=32, heads=2, dim_head=16, dropout=dropout)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 64, 32))
    mask = jnp.ones((3, 64), bool).at[:, 50:].set(False)
    params = mod.init(jax.random.PRNGKey(1), x, mask=mask)
    return mod, params, x, mask


# what `Attention.__call__` decides, by what the trace can see:
# (on a TPU, the flag, call keywords, module dropout) -> takes the kernel
RULE_CASES = {
    "cpu-flag-off": (False, False, {}, 0.0, False),
    "cpu-flag-on": (False, True, {}, 0.0, True),
    "tpu": (True, False, {}, 0.0, True),
    "tpu-tied-rows": (True, False, {"tie_dim": 3}, 0.0, False),
    "tpu-cross-attention": (True, False, {"context": True}, 0.0, False),
    "tpu-active-dropout": (True, False, {"deterministic": False}, 0.1,
                           False),
    "tpu-idle-dropout": (True, False, {"deterministic": True}, 0.1, True),
    "tpu-short-rows": (True, False, {"length": 24}, 0.0, False),
    "tpu-under-a-mesh": (True, False, {"mesh": 2}, 0.0, False),
    "tpu-under-a-mesh-of-one": (True, False, {"mesh": 1}, 0.0, True),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_attention_takes_the_kernel_by_what_it_can_see(case, monkeypatch):
    """Off the chip with the flag off (the tier-1 suite) the rule is false;
    on a TPU no flag is read and shape, tied rows, context and active
    dropout decide."""
    from alphafold2_tpu import runtime
    on_tpu, flag, call, dropout, expect = RULE_CASES[case]
    mod, params, x, mask = _attention_module_case(dropout)
    call = dict(call)
    length = call.pop("length", x.shape[1])
    x, mask = x[:, :length], mask[:, :length]
    if call.pop("context", False):
        call.update(context=x, context_mask=mask)
    devices = np.array(jax.devices()[:call.pop("mesh", 0)])
    mesh = jax.sharding.Mesh(devices, ("data",)) if devices.size else None
    calls = []
    interpreted = functools.partial(ops_attn.fused_attention_merged,
                                    interpret=True)

    def spy(*args, **kwargs):
        calls.append(kwargs)
        return interpreted(*args, **kwargs)

    monkeypatch.setattr(ops_attn, "fused_attention_merged", spy)
    monkeypatch.setattr(runtime, "on_tpu", lambda: on_tpu)
    from alphafold2_tpu.parallel.sharding import use_mesh
    with ops_attn.pallas_attention(flag), use_mesh(mesh):
        out = mod.apply(params, x, mask=mask,
                        rngs={"dropout": jax.random.PRNGKey(2)}, **call)
    assert bool(calls) == expect
    assert out.shape == x.shape and bool(jnp.isfinite(out).all())


class TestBlockSparseKernel:
    """True block-skipping sparse attention (ops/block_sparse.py) vs the
    dense+mask semantics of the model-level BlockSparseAttention."""

    def _pattern(self, nqb, window=1, num_global=1):
        bi = np.arange(nqb)
        local = np.abs(bi[:, None] - bi[None, :]) <= window
        glob = (bi[None, :] < num_global) | (bi[:, None] < num_global)
        return local | glob

    @pytest.mark.quick
    def test_matches_dense_masked_reference(self):
        from alphafold2_tpu.ops.block_sparse import block_sparse_attention

        rng = np.random.default_rng(0)
        b, n, d, blk = 2, 32, 16, 8
        q, k, v = (jnp.asarray(rng.normal(size=(b, n, d)), jnp.float32)
                   for _ in range(3))
        pattern = self._pattern(n // blk)
        out = block_sparse_attention(q, k, v, pattern, block=blk,
                                     scale=1.0, interpret=True)
        tok = np.repeat(np.repeat(pattern, blk, 0), blk, 1)
        bias = jnp.where(jnp.asarray(tok), 0.0, ops_attn.MASK_VALUE)[None]
        ref = ops_attn.attention_reference(
            q, k, v, bias=jnp.broadcast_to(bias, (b, n, n)))
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    @pytest.mark.quick
    def test_default_scale_is_inv_sqrt_d(self):
        """scale=None applies 1/sqrt(D) inside the kernel — equivalent to
        pre-scaling q (the asymmetric pre-scaled-q-only API invited a
        missing-1/sqrt(d) bug in wiring, round-2 ADVICE)."""
        from alphafold2_tpu.ops.block_sparse import block_sparse_attention

        rng = np.random.default_rng(7)
        b, n, d, blk = 1, 32, 16, 8
        q, k, v = (jnp.asarray(rng.normal(size=(b, n, d)), jnp.float32)
                   for _ in range(3))
        pattern = self._pattern(n // blk)
        out_default = block_sparse_attention(q, k, v, pattern, block=blk,
                                             interpret=True)
        out_prescaled = block_sparse_attention(
            q * d ** -0.5, k, v, pattern, block=blk, scale=1.0,
            interpret=True)
        assert np.allclose(np.asarray(out_default),
                           np.asarray(out_prescaled), atol=1e-6)

    def test_module_kernel_backend_matches_dense(self):
        """BlockSparseAttention with the Pallas backend on (interpret mode
        under CPU) equals its dense+mask path — one params tree, two
        compute backends (mirrors TestBackendSwitch for ops/attention)."""
        from alphafold2_tpu.model import BlockSparseAttention
        from alphafold2_tpu.ops.attention import pallas_attention

        rng = jax.random.PRNGKey(11)
        b, n, dim = 2, 32, 24
        x = jax.random.normal(rng, (b, n, dim), jnp.float32)
        mod = BlockSparseAttention(dim=dim, heads=2, dim_head=8, block=8,
                                   num_global=1, window=1)
        from conftest import perturb_params
        params = perturb_params(mod.init(jax.random.PRNGKey(12), x),
                                jax.random.PRNGKey(13))
        out_dense = mod.apply(params, x)
        assert float(np.abs(np.asarray(out_dense)).max()) > 0
        with pallas_attention(True):
            out_kernel = mod.apply(params, x)
        assert np.allclose(np.asarray(out_dense), np.asarray(out_kernel),
                           atol=1e-4), np.abs(
            np.asarray(out_dense) - np.asarray(out_kernel)).max()

    @pytest.mark.quick
    def test_k_mask_matches_dense(self):
        """Per-key masks inside live blocks (padded crop tails, gaps)
        match the dense -1e9 semantics at valid-query positions."""
        from alphafold2_tpu.ops.block_sparse import block_sparse_attention

        rng = np.random.default_rng(3)
        b, n, d, blk = 2, 32, 16, 8
        q, k, v = (jnp.asarray(rng.normal(size=(b, n, d)), jnp.float32)
                   for _ in range(3))
        # ragged per-sequence validity incl. a fully-masked block
        k_mask = (jnp.ones((b, n), bool)
                  .at[0, 21:].set(False)
                  .at[1, 12:].set(False))
        pattern = self._pattern(n // blk)
        out = block_sparse_attention(q, k, v, pattern, k_mask=k_mask,
                                     block=blk, scale=1.0, interpret=True)
        tok = np.repeat(np.repeat(pattern, blk, 0), blk, 1)
        bias = jnp.where(jnp.asarray(tok), 0.0, ops_attn.MASK_VALUE)[None]
        logits = jnp.einsum("bnd,bmd->bnm", q, k) + bias
        logits = jnp.where(k_mask[:, None, :], logits,
                           ops_attn.MASK_VALUE)
        ref = jnp.einsum("bnm,bmd->bnd", jax.nn.softmax(logits, -1), v)
        # compare only valid-QUERY rows (masked-query rows unspecified)
        for bi, nv in ((0, 21), (1, 12)):
            assert np.allclose(np.asarray(out)[bi, :nv],
                               np.asarray(ref)[bi, :nv], atol=1e-5)

    def test_module_kernel_backend_matches_dense_masked(self):
        """BlockSparseAttention with a token mask no longer falls back:
        kernel path equals the dense+mask path at valid positions."""
        from conftest import perturb_params

        from alphafold2_tpu.model import BlockSparseAttention
        from alphafold2_tpu.ops.attention import pallas_attention

        b, n, dim = 2, 32, 24
        x = jax.random.normal(jax.random.PRNGKey(21), (b, n, dim))
        mask = (jnp.ones((b, n), bool)
                .at[0, 25:].set(False)
                .at[1, 17:].set(False))
        mod = BlockSparseAttention(dim=dim, heads=2, dim_head=8, block=8,
                                   num_global=1, window=1)
        params = perturb_params(mod.init(jax.random.PRNGKey(22), x, mask),
                                jax.random.PRNGKey(23))
        out_dense = mod.apply(params, x, mask)
        with pallas_attention(True):
            out_kernel = mod.apply(params, x, mask)
        valid = np.asarray(mask)[..., None]
        assert float(np.abs(np.asarray(out_dense) * valid).max()) > 0
        assert np.allclose(np.asarray(out_dense) * valid,
                           np.asarray(out_kernel) * valid, atol=1e-4)

    def test_plan_compresses(self):
        from alphafold2_tpu.ops.block_sparse import plan_block_pattern

        # window-only band: every row has <= 3 live blocks of 8, so the
        # schedule runs 3 steps, not 8 — real compute savings
        pattern = self._pattern(8, window=1, num_global=0)
        cols, valid = plan_block_pattern(pattern)
        assert cols.shape[1] == 3
        assert valid.max() == 1

        # with a global row the schedule is bounded by that row's count
        # (it attends everything) but sparse rows stay mostly invalid
        pattern = self._pattern(8, window=1, num_global=1)
        cols, valid = plan_block_pattern(pattern)
        assert cols.shape[1] == 8
        assert valid[4].sum() == 4  # interior row: self, +-1, global

    def test_empty_row_rejected(self):
        from alphafold2_tpu.ops.block_sparse import plan_block_pattern

        bad = np.zeros((4, 4), bool)
        bad[0, 0] = True
        with pytest.raises(ValueError):
            plan_block_pattern(bad)

    def test_wide_pattern_and_bf16(self):
        from alphafold2_tpu.ops.block_sparse import block_sparse_attention

        rng = np.random.default_rng(1)
        b, n, d, blk = 1, 64, 8, 8
        q, k, v = (jnp.asarray(rng.normal(size=(b, n, d)), jnp.bfloat16)
                   for _ in range(3))
        pattern = self._pattern(n // blk, window=2, num_global=2)
        out = block_sparse_attention(q, k, v, pattern, block=blk,
                                     scale=1.0, interpret=True)
        tok = np.repeat(np.repeat(pattern, blk, 0), blk, 1)
        bias = jnp.where(jnp.asarray(tok), 0.0, ops_attn.MASK_VALUE)[None]
        ref = ops_attn.attention_reference(
            q, k, v, bias=jnp.broadcast_to(bias, (b, n, n)))
        # bf16 end-to-end: reference rounds attn weights to bf16 before
        # the PV matmul, the kernel keeps f32 accumulators — one-ulp-of-
        # bf16 disagreement on O(1) outputs
        assert np.allclose(np.asarray(out, jnp.float32),
                           np.asarray(ref, jnp.float32), atol=5e-2)


class TestFusedAttentionGrad:
    """The kernel's custom_vjp: Pallas forward, Pallas backward (the
    logits made again in VMEM, the bias cotangent summed over the rows in
    the same grid): grads must match plain autodiff of the reference."""

    def test_grads_match_reference(self):
        q, k, v, bias = make_inputs(jax.random.PRNGKey(7))
        qm = jnp.ones((q.shape[0], q.shape[1])).at[:, -3:].set(0.0)

        def f_kernel(q, k, v, bias):
            out = ops_attn.fused_attention(q, k, v, bias, q_mask=qm,
                                           k_mask=qm, interpret=True)
            return jnp.sum(out * out)

        def f_ref(q, k, v, bias):
            out = ops_attn.attention_reference(q, k, v, bias, q_mask=qm,
                                               k_mask=qm)
            return jnp.sum(out * out)

        gk = jax.grad(f_kernel, argnums=(0, 1, 2, 3))(q, k, v, bias)
        gr = jax.grad(f_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_unrepeated_bias_grad_sums_over_fold(self):
        """d_bias must accumulate over the folded axial axis the index
        map replays the bias across."""
        b, rep, h, n, d = 1, 3, 2, 16, 8
        key = jax.random.PRNGKey(8)
        ks = jax.random.split(key, 4)
        q = jax.random.normal(ks[0], (b * rep * h, n, d)) * 0.5
        k = jax.random.normal(ks[1], (b * rep * h, n, d)) * 0.5
        v = jax.random.normal(ks[2], (b * rep * h, n, d))
        bias = jax.random.normal(ks[3], (b * h, n, n))

        def f_kernel(bias):
            out = ops_attn.fused_attention(q, k, v, bias, heads=h,
                                           bias_repeat=rep, interpret=True)
            return jnp.sum(out * out)

        def f_ref(bias):
            out = ops_attn.attention_reference(q, k, v, bias, heads=h,
                                               bias_repeat=rep)
            return jnp.sum(out * out)

        np.testing.assert_allclose(
            np.asarray(jax.grad(f_kernel)(bias)),
            np.asarray(jax.grad(f_ref)(bias)), rtol=1e-4, atol=1e-5)

    def test_differentiated_call_is_the_kernel_forward_and_backward(self):
        """Under differentiation the custom_vjp's `fwd` is the forward
        kernel and its `bwd` the backward kernel: the traced gradient holds
        two Pallas calls, three under `jax.checkpoint` (the forward runs
        again), and no XLA attention; where `backward_admits` says no (here:
        the queries are blocked) the rule is the one such a trace always had:
        no Pallas call, and the inline XLA path's values to the bit."""
        heads, rows = 2, 3
        q, k, v, bias, mask = axial_inputs(
            jax.random.PRNGKey(5), 1, rows, heads, 64, 16, jnp.float32)
        unfold = lambda t: t.reshape(-1, heads, *t.shape[1:])

        def through_kernel(q, k, v, bias, **step):
            out = ops_attn.fused_attention(
                q, k, v, bias, mask, mask, heads=heads, bias_repeat=rows,
                interpret=True, **step)
            return jnp.sum(out * out)

        def inline(q, k, v, bias):
            out = ops_attn.xla_attention(
                unfold(q), unfold(k), unfold(v), unfold(bias), mask, mask,
                bias_repeat=rows)
            return jnp.sum(out * out)

        calls = lambda f: str(jax.make_jaxpr(f)(q, k, v, bias)).count(
            "pallas_call")
        grad = jax.grad(through_kernel, argnums=(0, 1, 2, 3))
        assert calls(through_kernel) == 1
        assert calls(grad) == 2
        assert calls(jax.value_and_grad(jax.checkpoint(through_kernel))) == 3
        blocked = jax.grad(functools.partial(through_kernel, block_q=32),
                           argnums=(0, 1, 2, 3))
        assert not ops_attn.backward_admits(64, 64, 32)
        assert calls(blocked) == 0
        want = jax.grad(inline, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b, c in zip(grad(q, k, v, bias), blocked(q, k, v, bias), want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_array_equal(np.asarray(b), np.asarray(c))

    def test_degenerate_tiles_fall_back(self):
        """Nq/Nk < 8 (e.g. 1x1 init-coverage pair maps) route to the XLA
        reference — Mosaic refuses those dots on-chip (r05)."""
        q = jnp.ones((4, 1, 16))
        k = jnp.ones((4, 1, 16))
        v = jnp.ones((4, 1, 16))
        # interpret=False on a CPU host: would fail inside pallas_call,
        # so passing proves the fallback took the XLA path
        out = ops_attn.fused_attention(q, k, v, interpret=False)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(
                ops_attn.attention_reference(q, k, v)), atol=1e-6)


# (batch, rows, heads, d, dtype, bias, masks, [k | v] whole, block_rows);
# n = 64. masks: "both" = one mask for queries and keys whose first row is
# fully padded, "keys" = a key mask alone whose first row has NO valid key
BACKWARD_CASES = {
    "head-pair-f32": (1, 3, 2, 64, jnp.float32, True, "both", True, None),
    "head-pair-bf16": (2, 4, 2, 64, jnp.bfloat16, True, "both", True, None),
    "head-pair-k-and-v-apart": (1, 3, 2, 64, jnp.float32, True, "both",
                                False, None),
    "two-head-pairs": (1, 2, 4, 64, jnp.float32, True, "both", True, None),
    "heads-that-fill-no-lane-tile-f32": (1, 3, 2, 16, jnp.float32, True,
                                         "both", True, None),
    "heads-that-fill-no-lane-tile-bf16-apart": (1, 3, 2, 16, jnp.bfloat16,
                                                True, "both", False, None),
    "no-bias": (1, 4, 2, 64, jnp.float32, False, "both", True, None),
    "no-masks": (1, 3, 2, 16, jnp.float32, True, None, True, None),
    "no-bias-no-masks-bf16": (1, 4, 2, 16, jnp.bfloat16, False, None, False,
                              None),
    "bias-repeat-1": (3, 1, 2, 16, jnp.float32, True, "both", True, None),
    "several-row-groups": (1, 8, 2, 16, jnp.float32, True, "both", True, 2),
    "row-groups-that-do-not-divide": (2, 5, 2, 16, jnp.float32, True, "both",
                                      True, 2),
    "several-row-groups-head-pair-bf16": (1, 6, 2, 64, jnp.bfloat16, True,
                                          "both", True, 4),
    "a-row-with-no-valid-key": (1, 3, 2, 16, jnp.float32, True, "keys", True,
                                None),
}


@pytest.mark.parametrize("case", sorted(BACKWARD_CASES))
def test_backward_kernel_matches_reference_grads(case):
    """The backward kernel in interpret mode against `jax.grad` of
    `attention_reference`, for q, k, v and the unrepeated bias: the bias
    cotangent accumulates over every row that shares the bias, over several
    grid steps where the rows take more than one, and a partial last step's
    missing rows add nothing."""
    batch, rows, heads, d, dtype, bias, masks, whole, block_rows = \
        BACKWARD_CASES[case]
    n = 64
    q, k, v, b, mask = axial_inputs(jax.random.PRNGKey(rows + d), batch, rows,
                                    heads, n, d, dtype, bias, bool(masks))
    q_mask = mask if masks == "both" else None
    kw = dict(heads=heads, bias_repeat=rows if bias else 1)
    merged = lambda t: ops_attn.merge_heads(t.reshape(-1, heads, n, d))
    weight = jax.random.normal(jax.random.PRNGKey(1), (batch * rows, n,
                                                       heads * d))

    def through_kernel(q, k, v, b):
        k, v = (jnp.concatenate([k, v], axis=-1), None) if whole else (k, v)
        out = ops_attn.fused_attention_merged(
            q, k, v, b, q_mask, mask, interpret=True, block_rows=block_rows,
            **kw)
        return jnp.sum(out.astype(jnp.float32) * weight)

    def reference(q, k, v, b):
        out = ops_attn.attention_reference(q, k, v, b, q_mask, mask, **kw)
        return jnp.sum(merged(out).astype(jnp.float32) * weight)

    argnums = (0, 1, 2, 3) if bias else (0, 1, 2)
    # jitted: one compile a side, not one for each of the reference's ops
    got = jax.jit(jax.grad(through_kernel, argnums))(
        merged(q), merged(k), merged(v), b)
    want = jax.jit(jax.grad(reference, argnums))(q, k, v, b)
    want = [merged(t) for t in want[:3]] + list(want[3:])
    for name, a, w in zip("qkvb", got, want):
        assert a.dtype == w.dtype and a.shape == w.shape, name
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(a).all(), name
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-4,
                                       err_msg=name)
        else:   # bf16 operands into both programs' contractions
            assert np.linalg.norm(a - w) < 0.03 * np.linalg.norm(w), name
    if masks == "keys":
        # every logit of the first row is the fill: no cotangent reaches q,
        # k or the bias from it, and v has the uniform weights' share
        dq, dk, dv = (np.asarray(t)[0] for t in got[:3])
        assert not dq.any() and not dk.any() and dv.any()


def test_backward_rule_follows_the_length():
    """The backward kernel takes every length whose row of queries is one
    grid step, which is every bucket of the cells and not 1,024."""
    admits = ops_attn.backward_admits
    assert [n for n in (64, 128, 192, 256, 384, 512, 640, 1024)
            if admits(n, n)] == [64, 128, 192, 256, 384, 512, 640]
    assert not admits(256, 256, 128)


# -- the fused triangle multiply (ops/triangle_multiply.py) -------------------

from alphafold2_tpu.ops import triangle_multiply as ops_tm  # noqa: E402

TM_DIM, TM_HIDDEN = 32, 128


def triangle_multiply_params(key, dim=TM_DIM, hidden=TM_HIDDEN):
    """The module's parameter leaves, every one drawn (the module's own
    initialisers make the gates pass-through and hide their arithmetic)."""
    keys = iter(jax.random.split(key, 32))
    dense = lambda i, o: {
        "kernel": jax.random.normal(next(keys), (i, o)) / np.sqrt(i),
        "bias": 0.1 * jax.random.normal(next(keys), (o,))}
    norm = lambda w: {"LayerNorm_0": {
        "scale": 1 + 0.1 * jax.random.normal(next(keys), (w,)),
        "bias": 0.1 * jax.random.normal(next(keys), (w,))}}
    p = {name: dense(dim, hidden) for name in ops_tm.PROJECTIONS}
    p.update(LayerNorm_0=norm(dim), LayerNorm_1=norm(hidden),
             to_out=dense(hidden, dim))
    return p


def triangle_multiply_inputs(n, batch, dtype, masked):
    x = jax.random.normal(jax.random.PRNGKey(n + batch),
                          (batch, n, n, TM_DIM)).astype(dtype)
    mask = None
    if masked:   # ragged lengths, and one row of the map fully padded
        lengths = jax.random.randint(jax.random.PRNGKey(3), (batch, 1),
                                     n // 2, n + 1)
        valid = jnp.arange(n)[None, :] < lengths
        mask = (valid[:, :, None] & valid[:, None, :]).at[:, 0].set(False)
    return x, mask


# (n, batch, dtype, mix, masked): both mixes, with and without a mask, batch
# 1 and 8, a length that is no power of two
TM_CASES = {
    "n64-f32-outgoing-mask": (64, 1, jnp.float32, "outgoing", True),
    "n64-f32-ingoing-b8": (64, 8, jnp.float32, "ingoing", False),
    "n128-f32-ingoing-mask": (128, 1, jnp.float32, "ingoing", True),
    "n192-f32-outgoing": (192, 1, jnp.float32, "outgoing", False),
    "n192-f32-ingoing-mask": (192, 1, jnp.float32, "ingoing", True),
    "n64-bf16-outgoing-mask-b8": (64, 8, jnp.bfloat16, "outgoing", True),
    "n128-bf16-ingoing": (128, 1, jnp.bfloat16, "ingoing", False),
    "n192-bf16-outgoing-mask": (192, 1, jnp.bfloat16, "outgoing", True),
}


def _middle(t):
    """(b, i, k, hidden) -> (b * i, hidden, k), the layout the stages hand
    each other: a row of the map is a (hidden, k) tile."""
    b, n, _, hidden = t.shape
    return t.swapaxes(-1, -2).reshape(b * n, hidden, n)


def _close(got, want, dtype, what):
    """float32: 1e-5 of the tensor's scale; bf16: the attention kernel's
    tolerance (the stages keep float32 where XLA rounds to bf16 between a
    matmul and its gate)."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert got.shape == want.shape and np.isfinite(got).all(), what
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max()), \
        (what, np.abs(got - want).max())


@pytest.mark.parametrize("stage", ("project", "contract", "finish", "whole"))
@pytest.mark.parametrize("case", sorted(TM_CASES))
def test_fused_triangle_multiply_matches_xla(case, stage):
    """Each fused stage, interpreted, against the XLA formulation of the same
    stage on the same inputs (on the layout the stages hand each other), and
    the whole update + its residual against `triangle_multiply_xla`."""
    n, batch, dtype, mix, masked = TM_CASES[case]
    p = triangle_multiply_params(jax.random.PRNGKey(1))
    x, mask = triangle_multiply_inputs(n, batch, dtype, masked)
    if stage == "whole":
        got = jax.jit(lambda p, x, m: ops_tm.fused_triangle_multiply(
            p, x, m, x, mix=mix, dtype=dtype, interpret=True))(p, x, mask)
        want = ops_tm.triangle_multiply_xla(p, x, mask, mix=mix,
                                            dtype=dtype) + x
        assert got.dtype == dtype
        return _close(got, want, dtype, case)
    left, right, gate = ops_tm.project_xla(p, x, mask, dtype)
    if stage == "project":
        got = ops_tm._project_pallas(
            p, x, None if mask is None else mask.astype(jnp.float32),
            dtype=dtype, interpret=True)
        want = (left, right, gate)
    elif stage == "contract":
        got = (ops_tm._contract_pallas(_middle(left), _middle(right), mix,
                                       interpret=True),)
        want = (ops_tm.contract_xla(left, right, mix),)
    else:
        out = ops_tm.contract_xla(left, right, mix)
        got = ops_tm._finish_pallas(p, _middle(out), _middle(gate), x,
                                    dtype=dtype, interpret=True)
        return _close(got, ops_tm.finish_xla(p, out, gate, dtype) + x,
                      dtype, case)
    for g, w in zip(got, want):
        assert g.dtype == dtype
        _close(g, _middle(w), dtype, case)


@pytest.mark.parametrize("mix", ("outgoing", "ingoing"))
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16))
def test_contraction_in_blocks_matches_xla(mix, dtype, monkeypatch):
    """A map longer than a block of the contraction (1,024 on the chip: two
    blocks of 512 a side; here 256 in blocks of 128), batch 2: every block of
    the result from its own blocks of the operands."""
    monkeypatch.setattr(ops_tm, "_CONTRACT_BLOCK", 128)
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    left, right = (jax.random.normal(k, (2, 256, 256, 32)).astype(dtype)
                   for k in keys)
    got = ops_tm._contract_pallas(_middle(left), _middle(right), mix,
                                  interpret=True)
    want = _middle(ops_tm.contract_xla(left, right, mix))
    assert got.dtype == dtype
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    # the sums of 256 products of unit normals: an ulp of bf16 at ~50
    tol = 1e-4 if dtype == jnp.float32 else 0.5
    assert np.abs(got - want).max() <= tol


@pytest.mark.parametrize("mix", ("outgoing", "ingoing"))
def test_fused_triangle_multiply_gradient_is_the_xla_formulations(mix):
    """`jax.grad` through the update's `custom_vjp` against the gradient of
    `triangle_multiply_xla`, with respect to every parameter leaf and the
    input; and the value beside the gradient is the fused forward's."""
    p = triangle_multiply_params(jax.random.PRNGKey(2))
    x, mask = triangle_multiply_inputs(64, 2, jnp.float32, True)
    weight = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    fused = lambda p, x: ops_tm.fused_triangle_multiply(
        p, x, mask, x, mix=mix, dtype=jnp.float32, interpret=True)
    xla = lambda p, x: ops_tm.triangle_multiply_xla(
        p, x, mask, mix=mix, dtype=jnp.float32) + x
    grad = lambda fn: jax.jit(jax.grad(
        lambda p, x: jnp.sum(fn(p, x) * weight), argnums=(0, 1)))(p, x)
    got, want = grad(fused), grad(xla)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        a, w = np.asarray(a), np.asarray(w)
        assert np.abs(w).max() > 0, path
        assert np.abs(a - w).max() <= 1e-4 * max(1.0, np.abs(w).max()), path


def test_triangle_multiply_admits():
    # the three cells' buckets at their batch, bf16, hidden 256: a pair
    # tensor of 32 MiB or less is XLA's (it keeps it on the chip)
    for n, batch, fused in ((64, 8, False), (128, 8, True), (256, 8, True),
                            (256, 1, False), (384, 1, True), (512, 1, True),
                            (640, 1, True), (1024, 1, True)):
        assert ops_tm.admits(n, 256, batch) is fused, (n, batch)
    assert ops_tm.admits(256, 256, 1, itemsize=4)      # 64 MiB in float32
    assert not ops_tm.admits(520, 256)     # no multiple of 64
    assert not ops_tm.admits(512, 32, 64)  # a hidden width under a lane tile
    assert not ops_tm.admits(512, 192, 8)
    # rows of the map a step of the first and the last stage takes
    assert [ops_tm._rows_a_step(n) for n in (64, 192, 256, 640, 1024)] \
        == [32, 8, 8, 2, 2]


# what `TriangleMultiplicativeModule.__call__` decides, by what the trace can
# see: (on a TPU, the flag, length, hidden width, mesh size) -> fused
TM_RULE_CASES = {
    "tpu-small-pair-tensor": (True, False, 64, 128, 0, False),
    "cpu-flag-off": (False, False, 64, 128, 0, False),
    "cpu-flag-on": (False, True, 64, 128, 0, True),
    "tpu": (True, False, 64, 128, 0, True),
    "tpu-short-map": (True, False, 32, 128, 0, False),
    "tpu-length-no-multiple-of-64": (True, False, 72, 128, 0, False),
    "tpu-narrow-hidden": (True, False, 64, 32, 0, False),
    "tpu-under-a-mesh": (True, False, 64, 128, 2, False),
    "tpu-under-a-mesh-of-one": (True, False, 64, 128, 1, True),
}


@pytest.mark.parametrize("case", sorted(TM_RULE_CASES))
def test_triangle_multiply_takes_the_kernels_by_what_it_can_see(
        case, monkeypatch):
    """The attention's rule: a TPU (or the CPU tests' door), one device, a
    shape `admits` accepts (a side Mosaic tiles, whole lane tiles of hidden
    channels, a pair tensor larger than XLA keeps on the chip); everything
    else falls back to the XLA formulation, and both give the same update +
    residual."""
    from alphafold2_tpu import runtime
    from alphafold2_tpu.model.primitives import TriangleMultiplicativeModule
    from alphafold2_tpu.parallel.sharding import use_mesh
    on_tpu, flag, n, hidden, mesh_size, expect = TM_RULE_CASES[case]
    if case != "tpu-small-pair-tensor":
        # the test's maps are far under the size XLA keeps on the chip
        monkeypatch.setattr(ops_tm, "_MIN_PAIR_BYTES", 0)
    mod = TriangleMultiplicativeModule(dim=TM_DIM, hidden_dim=hidden,
                                       mix="outgoing")
    x = jax.random.normal(jax.random.PRNGKey(0), (1, n, n, TM_DIM))
    mask = jnp.ones((1, n, n), bool).at[:, :, n - 5:].set(False)
    params = {"params": triangle_multiply_params(jax.random.PRNGKey(1),
                                                 hidden=hidden)}
    want = mod.apply(params, x, mask=mask) + x      # the suite's XLA path
    calls, fused = [], ops_tm.fused_triangle_multiply

    def spy(*args, **kwargs):
        calls.append(kwargs)
        return fused(*args, **dict(kwargs, interpret=True))

    monkeypatch.setattr(ops_tm, "fused_triangle_multiply", spy)
    monkeypatch.setattr(runtime, "on_tpu", lambda: on_tpu)
    devices = np.array(jax.devices()[:mesh_size])
    mesh = jax.sharding.Mesh(devices, ("data",)) if devices.size else None
    with ops_attn.pallas_attention(flag), use_mesh(mesh):
        out = mod.apply(params, x, mask=mask, residual=x)
    assert bool(calls) == expect
    _close(out, want, jnp.float32, case)


def test_triangle_multiply_parameter_tree_is_the_dense_layers():
    """The module holds its leaves itself; the tree is the one the five
    Dense layers, `to_out` and the two layer norms gave it (the benchmark
    draws weights by these names), and so are the initial values: gates that
    pass through, unit layer norms."""
    from alphafold2_tpu.model.primitives import TriangleMultiplicativeModule
    mod = TriangleMultiplicativeModule(dim=TM_DIM, hidden_dim=TM_HIDDEN)
    params = mod.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, TM_DIM)))
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype.name), params)
    dense = lambda i, o: {"kernel": ((i, o), "float32"),
                          "bias": ((o,), "float32")}
    norm = lambda w: {"LayerNorm_0": {"scale": ((w,), "float32"),
                                      "bias": ((w,), "float32")}}
    assert shapes == {"params": {
        "LayerNorm_0": norm(TM_DIM), "LayerNorm_1": norm(TM_HIDDEN),
        "left_proj": dense(TM_DIM, TM_HIDDEN),
        "right_proj": dense(TM_DIM, TM_HIDDEN),
        "left_gate": dense(TM_DIM, TM_HIDDEN),
        "right_gate": dense(TM_DIM, TM_HIDDEN),
        "out_gate": dense(TM_DIM, TM_HIDDEN),
        "to_out": dense(TM_HIDDEN, TM_DIM)}}
    p = params["params"]
    for name in ("left_gate", "right_gate", "out_gate"):
        assert not np.asarray(p[name]["kernel"]).any()
        assert (np.asarray(p[name]["bias"]) == 1).all()
    for name in ("left_proj", "right_proj", "to_out"):
        kernel = np.asarray(p[name]["kernel"])
        assert not np.asarray(p[name]["bias"]).any()
        # LeCun normal: variance 1 / fan-in
        assert 0.5 < kernel.std() * np.sqrt(kernel.shape[0]) < 1.5
    for name in ("LayerNorm_0", "LayerNorm_1"):
        assert (np.asarray(p[name]["LayerNorm_0"]["scale"]) == 1).all()
        assert not np.asarray(p[name]["LayerNorm_0"]["bias"]).any()
