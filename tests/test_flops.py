"""Analytic FLOP model tests (round-4 VERDICT #2): the jaxpr-walking
counter must match closed-form counts on known programs, be invariant to
remat and to which backend kernels are enabled (the property XLA
cost_analysis lacks), and agree with an independent closed-form
derivation of the Evoformer step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.utils.flops import (count_jaxpr_flops,
                                        evoformer_step_flops_formula,
                                        forward_flops, train_step_flops)


class TestCounterPrimitives:
    @pytest.mark.quick
    def test_plain_matmul(self):
        x, w = jnp.ones((8, 16)), jnp.ones((16, 32))
        assert forward_flops(lambda x, w: x @ w, x, w) == 2 * 8 * 16 * 32

    @pytest.mark.quick
    def test_batched_einsum(self):
        a = jnp.ones((4, 8, 16))
        b = jnp.ones((4, 16, 32))
        got = forward_flops(lambda a, b: jnp.einsum("bik,bkj->bij", a, b),
                            a, b)
        assert got == 2 * 4 * 8 * 16 * 32

    @pytest.mark.quick
    def test_scan_multiplies_by_length(self):
        w = jnp.ones((16, 16))

        def f(x):
            return jax.lax.scan(lambda c, _: (c @ w, None), x, None,
                                length=5)[0]

        assert forward_flops(f, jnp.ones((8, 16))) == 5 * 2 * 8 * 16 * 16

    @pytest.mark.quick
    def test_cond_charges_max_branch(self):
        w_small = jnp.ones((16, 8))
        w_big = jnp.ones((16, 64))

        def f(x, pred):
            return jax.lax.cond(pred,
                                lambda x: (x @ w_big).sum(),
                                lambda x: (x @ w_small).sum(), x)

        got = forward_flops(f, jnp.ones((8, 16)), jnp.array(True))
        assert got == 2 * 8 * 16 * 64

    @pytest.mark.quick
    def test_remat_counted_once(self):
        """Forward trace contains each op once — remat recompute is
        excluded by construction (MFU, not HFU)."""
        w = jnp.ones((16, 32))
        plain = forward_flops(lambda x: x @ w, jnp.ones((8, 16)))
        rematd = forward_flops(
            lambda x: jax.checkpoint(lambda y: y @ w)(x), jnp.ones((8, 16)))
        assert plain == rematd == 2 * 8 * 16 * 32

    @pytest.mark.quick
    def test_conv(self):
        x = jnp.ones((1, 8, 16))   # N C W
        k = jnp.ones((4, 8, 3))    # O I W
        f = lambda x, k: jax.lax.conv_general_dilated(
            x, k, (1,), "SAME", dimension_numbers=("NCH", "OIH", "NCH"))
        # out (1, 4, 16): 2 * prod(out) * C_in * kernel_w
        assert forward_flops(f, x, k) == 2 * (1 * 4 * 16) * 8 * 3

    def test_shard_map_counts_all_devices(self):
        from jax.sharding import Mesh, PartitionSpec as P
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
        w = jnp.ones((16, 16))

        def f(x):
            return jax.shard_map(lambda xi: xi @ w, mesh=mesh,
                                 in_specs=P("x"), out_specs=P("x"))(x)

        # per-device (2,16)@(16,16), times 4 devices = global (8,16) work
        assert forward_flops(f, jnp.ones((8, 16))) == 2 * 8 * 16 * 16

    def test_shard_map_excludes_replicated_axes(self):
        """Axes the operands are not sharded over hold replicas; the
        redundant compute is hardware work, not model FLOPs (the MFU
        numerator must not inflate with them)."""
        from jax.sharding import Mesh, PartitionSpec as P
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
        w = jnp.ones((16, 16))

        def f(x):
            # sharded over 'a' only; the 'b' axis computes replicas
            return jax.shard_map(lambda xi: xi @ w, mesh=mesh,
                                 in_specs=P("a"), out_specs=P("a"))(x)

        assert forward_flops(f, jnp.ones((8, 16))) == 2 * 8 * 16 * 16


class TestModelLevel:
    def _model_batch(self):
        from alphafold2_tpu import Alphafold2
        from alphafold2_tpu.data.synthetic import synthetic_batch
        model = Alphafold2(dim=64, depth=2, heads=4, dim_head=16)
        batch = synthetic_batch(jax.random.PRNGKey(0), batch=1,
                                seq_len=64, msa_depth=5)
        params = model.init(jax.random.PRNGKey(1), batch["seq"],
                            msa=batch["msa"], mask=batch["mask"],
                            msa_mask=batch["msa_mask"])
        return model, params, batch

    def test_matches_closed_form_evoformer(self):
        """Independent derivation (einsum inventory) within 15%."""
        model, params, batch = self._model_batch()
        jaxpr_count = train_step_flops(model, params, batch)
        formula = evoformer_step_flops_formula(64, 2, 64, 5, heads=4,
                                               dim_head=16)
        assert abs(jaxpr_count / formula - 1.0) < 0.15, \
            (jaxpr_count, formula)

    def test_invariant_to_pallas_routing(self):
        from alphafold2_tpu.ops.attention import (pallas_attention_enabled,
                                                  use_pallas_attention)
        model, params, batch = self._model_batch()
        prev = pallas_attention_enabled()
        try:
            use_pallas_attention(True)
            with_pallas = train_step_flops(model, params, batch)
            use_pallas_attention(False)
            without = train_step_flops(model, params, batch)
        finally:
            use_pallas_attention(prev)
        assert with_pallas == without > 0

    def test_scales_with_depth(self):
        """Trunk dominates: doubling depth should roughly double FLOPs."""
        from alphafold2_tpu import Alphafold2
        from alphafold2_tpu.data.synthetic import synthetic_batch
        batch = synthetic_batch(jax.random.PRNGKey(0), batch=1,
                                seq_len=48, msa_depth=4)

        def flops_at(depth):
            m = Alphafold2(dim=32, depth=depth, heads=2, dim_head=16)
            p = m.init(jax.random.PRNGKey(1), batch["seq"],
                       msa=batch["msa"], mask=batch["mask"],
                       msa_mask=batch["msa_mask"])
            return train_step_flops(m, p, batch)

        f2, f4 = flops_at(2), flops_at(4)
        trunk_ratio = f4 / f2
        assert 1.6 < trunk_ratio < 2.05, trunk_ratio
