"""Attention-variant tests (README-era menu): linear, memory-compressed,
Kronecker-pooled, block-sparse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.model.attention_variants import (
    BlockSparseAttention,
    KroneckerAttention,
    LinearAttention,
    MemoryCompressedAttention,
    block_sparse_mask,
    kronecker_pool_2d,
)


def x_mask(key, b=2, n=32, d=16):
    x = jax.random.normal(key, (b, n, d))
    mask = jnp.ones((b, n), dtype=bool).at[:, -8:].set(False)
    return x, mask


class TestLinearAttention:
    def test_shapes_and_finite(self):
        x, mask = x_mask(jax.random.PRNGKey(0))
        mod = LinearAttention(dim=16, heads=2, dim_head=8)
        params = mod.init(jax.random.PRNGKey(1), x, mask=mask)
        out = mod.apply(params, x, mask=mask)
        assert out.shape == x.shape
        assert bool(jnp.isfinite(out).all())

    def test_masked_keys_have_no_influence(self):
        x, mask = x_mask(jax.random.PRNGKey(2))
        mod = LinearAttention(dim=16, heads=2, dim_head=8)
        params = mod.init(jax.random.PRNGKey(3), x, mask=mask)
        out1 = mod.apply(params, x, mask=mask)
        x2 = x.at[:, -8:].add(50.0)  # corrupt masked keys
        out2 = mod.apply(params, x2, mask=mask)
        assert np.allclose(out1[:, :24], out2[:, :24], atol=1e-4)

    def test_cross_attention(self):
        x, _ = x_mask(jax.random.PRNGKey(4), n=8)
        ctx = jax.random.normal(jax.random.PRNGKey(5), (2, 20, 16))
        cmask = jnp.ones((2, 20), dtype=bool)
        mod = LinearAttention(dim=16, heads=2, dim_head=8)
        params = mod.init(jax.random.PRNGKey(6), x, context=ctx,
                          context_mask=cmask)
        out = mod.apply(params, x, context=ctx, context_mask=cmask)
        assert out.shape == x.shape


class TestMemoryCompressed:
    def test_ratios(self):
        for r in (2, 4):
            x, mask = x_mask(jax.random.PRNGKey(7))
            mod = MemoryCompressedAttention(dim=16, heads=2, dim_head=8,
                                            compress_ratio=r)
            params = mod.init(jax.random.PRNGKey(8), x, mask=mask)
            out = mod.apply(params, x, mask=mask)
            assert out.shape == x.shape
            assert bool(jnp.isfinite(out).all())

    def test_non_divisible_length(self):
        x = jax.random.normal(jax.random.PRNGKey(9), (1, 13, 16))
        mod = MemoryCompressedAttention(dim=16, heads=2, dim_head=8,
                                        compress_ratio=4)
        params = mod.init(jax.random.PRNGKey(10), x)
        out = mod.apply(params, x)
        assert out.shape == x.shape
        # unmasked call must equal an explicit all-ones mask (padding must
        # not dilute the last compressed block)
        out_ones = mod.apply(params, x, mask=jnp.ones((1, 13), dtype=bool))
        assert np.allclose(np.asarray(out), np.asarray(out_ones),
                           atol=1e-5)


class TestKronecker:
    def test_pool_axial_tokens(self):
        ctx = jnp.arange(2 * 4 * 6 * 3, dtype=jnp.float32
                         ).reshape(2, 4, 6, 3)
        pooled, token_mask = kronecker_pool_2d(ctx)
        assert pooled.shape == (2, 4 + 6, 3)   # H + W tokens
        assert token_mask.shape == (2, 10)
        assert np.isclose(float(pooled[0, 0, 0]),
                          float(ctx[0, 0, :, 0].mean()))   # row mean
        assert np.isclose(float(pooled[0, 4, 0]),
                          float(ctx[0, :, 0, 0].mean()))   # col mean

    def test_pool_masked(self):
        ctx = jnp.ones((1, 4, 4, 2))
        cmask = jnp.ones((1, 4, 4), dtype=bool).at[:, 2:, :].set(False)
        ctx = ctx.at[:, 2:, :].set(100.0)  # garbage in masked rows
        pooled, token_mask = kronecker_pool_2d(ctx, cmask)
        # valid row tokens unaffected by masked garbage
        assert np.allclose(pooled[0, :2], 1.0)
        # fully-masked rows produce invalid tokens
        assert not bool(token_mask[0, 2]) and not bool(token_mask[0, 3])

    def test_cross_attention(self):
        x = jax.random.normal(jax.random.PRNGKey(11), (2, 8, 16))
        pair = jax.random.normal(jax.random.PRNGKey(12), (2, 8, 8, 16))
        cmask = jnp.ones((2, 8, 8), dtype=bool)
        mod = KroneckerAttention(dim=16, heads=2, dim_head=8)
        params = mod.init(jax.random.PRNGKey(13), x, pair,
                          context_mask=cmask)
        out = mod.apply(params, x, pair, context_mask=cmask)
        assert out.shape == x.shape


class TestBlockSparse:
    @pytest.mark.quick
    def test_mask_pattern(self):
        m = block_sparse_mask(64, block=16, num_global=1, window=1)
        assert m.shape == (64, 64)
        assert bool(m[0, 0])          # diagonal
        assert bool(m[63, 0])         # global block reachable
        assert not bool(m[63, 18])    # far block, not global
        assert bool(m[17, 40])        # within window? 17//16=1, 40//16=2 -> yes
        assert not bool(m[17, 60])    # 1 vs 3 blocks apart

    def test_module(self):
        x, mask = x_mask(jax.random.PRNGKey(14), n=64)
        mod = BlockSparseAttention(dim=16, heads=2, dim_head=8, block=16)
        params = mod.init(jax.random.PRNGKey(15), x, mask=mask)
        out = mod.apply(params, x, mask=mask)
        assert out.shape == x.shape
        assert bool(jnp.isfinite(out).all())

    def test_pallas_path_broadcast_bias(self, monkeypatch):
        # BlockSparseAttention passes a (1, 1, n, n) broadcast bias; the
        # fused path must expand it to the kernel's (b, heads) contract
        # (regression: round-2 review finding)
        import functools

        from alphafold2_tpu.ops import attention as ops_attn

        monkeypatch.setattr(
            ops_attn, "fused_attention_merged",
            functools.partial(ops_attn.fused_attention_merged,
                              interpret=True))
        x, mask = x_mask(jax.random.PRNGKey(16), n=64)
        mod = BlockSparseAttention(dim=16, heads=2, dim_head=8, block=16)
        params = mod.init(jax.random.PRNGKey(17), x, mask=mask)
        ref = mod.apply(params, x, mask=mask)
        with ops_attn.pallas_attention(True):
            out = mod.apply(params, x, mask=mask)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


class TestMultiKernelConv:
    """trRosetta2-style conv blocks (reference README.md:271-340
    `use_conv` / conv_seq_kernels / conv_msa_kernels / dilations)."""

    @pytest.mark.quick
    def test_identity_at_init_and_shapes(self):
        from alphafold2_tpu.model import MultiKernelConvBlock

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 12, 16))
        blk = MultiKernelConvBlock(dim=16, kernels=((3, 3), (1, 9)),
                                   dilations=(1, 2))
        params = blk.init(jax.random.PRNGKey(1), x)
        out = blk.apply(params, x)
        assert out.shape == x.shape
        # zero-init output projection: the residual branch starts as 0
        assert float(jnp.abs(out).max()) == 0.0

    @pytest.mark.quick
    def test_mask_blocks_leakage(self):
        """Values in masked cells must not influence valid outputs —
        the conv window sees zeros there, not garbage."""
        from conftest import perturb_params

        from alphafold2_tpu.model import MultiKernelConvBlock

        key = jax.random.PRNGKey(2)
        x = jax.random.normal(key, (1, 8, 8, 16))
        mask = jnp.ones((1, 8, 8), bool).at[:, 5:].set(False)
        blk = MultiKernelConvBlock(dim=16, kernels=((3, 3),))
        params = perturb_params(blk.init(jax.random.PRNGKey(3), x, mask),
                                jax.random.PRNGKey(4))
        out1 = blk.apply(params, x, mask)
        x2 = x.at[:, 5:].set(99.0)  # garbage in the masked region
        out2 = blk.apply(params, x2, mask)
        valid = np.asarray(mask)[..., None]
        assert np.allclose(np.asarray(out1) * valid,
                           np.asarray(out2) * valid, atol=1e-6)

    def test_model_use_conv_forward_and_step(self):
        from alphafold2_tpu import Alphafold2
        from alphafold2_tpu.data.synthetic import synthetic_batch
        from alphafold2_tpu.train import TrainState, adam, make_train_step

        model = Alphafold2(dim=32, depth=2, heads=2, dim_head=16,
                           use_conv=True,
                           conv_seq_kernels=((3, 1), (1, 3)),
                           conv_msa_kernels=((1, 3),))
        batch = synthetic_batch(jax.random.PRNGKey(5), batch=1, seq_len=16,
                                msa_depth=3, with_coords=True)
        params = model.init(jax.random.PRNGKey(6), batch["seq"],
                            msa=batch["msa"], mask=batch["mask"],
                            msa_mask=batch["msa_mask"])
        # conv params actually exist in the tree
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        names = ["/".join(str(getattr(k, "key", k)) for k in p)
                 for p, _ in flat]
        assert any("pair_conv" in n for n in names)
        assert any("msa_conv" in n for n in names)

        ret = model.apply(params, batch["seq"], msa=batch["msa"],
                          mask=batch["mask"], msa_mask=batch["msa_mask"])
        assert bool(jnp.isfinite(ret.distance).all())

        state = TrainState.create(apply_fn=model.apply, params=params,
                                  tx=adam(1e-3), rng=jax.random.PRNGKey(7))
        step = jax.jit(make_train_step(model), donate_argnums=(0,))
        _, metrics = step(state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
