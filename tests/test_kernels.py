"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype swept."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.gating import GateConfig, capacity, topk_gate
from repro.kernels import ops, ref


class TestFlashAttention:
    @pytest.mark.parametrize("B,L,H,K,hd", [
        (2, 256, 4, 2, 64), (1, 512, 8, 1, 32), (2, 128, 4, 4, 128),
        (1, 384, 6, 6, 64),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_gqa(self, B, L, H, K, hd, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, L, H, hd), dtype)
        k = jax.random.normal(ks[1], (B, L, K, hd), dtype)
        v = jax.random.normal(ks[2], (B, L, K, hd), dtype)
        out = ops.flash_attention(q, k, v, causal=True)
        kk, vv = jnp.repeat(k, H // K, 2), jnp.repeat(v, H // K, 2)
        exp = ref.flash_attention_ref(qq := q, kk, vv, causal=True)
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("window", [32, 128])
    def test_sliding_window(self, window):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 64))
        k = jax.random.normal(ks[1], (1, 256, 4, 64))
        v = jax.random.normal(ks[2], (1, 256, 4, 64))
        out = ops.flash_attention(q, k, v, causal=True, window=window)
        exp = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)

    def test_non_causal(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (2, 128, 2, 32))
        k = jax.random.normal(ks[1], (2, 128, 2, 32))
        v = jax.random.normal(ks[2], (2, 128, 2, 32))
        out = ops.flash_attention(q, k, v, causal=False)
        exp = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)

    @settings(max_examples=10, deadline=None)
    @given(L=st.sampled_from([64, 192, 320]),
           hd=st.sampled_from([32, 64]),
           seed=st.integers(0, 100))
    def test_property_sweep(self, L, hd, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (1, L, 2, hd))
        k = jax.random.normal(ks[1], (1, L, 2, hd))
        v = jax.random.normal(ks[2], (1, L, 2, hd))
        out = ops.flash_attention(q, k, v)
        exp = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("B,L,H,K,hd,causal,window,grad", [
        (1, 256, 12, 12, 64, True, None, False),   # the cells' heads
        (2, 128, 8, 4, 32, True, None, True),      # rep 2, one tile
        (1, 1024, 4, 1, 32, True, None, False),    # rep 4, 8 x 8 tiles
        (1, 256, 6, 6, 32, True, 64, True),        # four-chip shard
        (2, 192, 3, 3, 32, False, None, False),    # shard, L off 128
        (1, 320, 8, 2, 64, True, 100, True),       # rep 4, window, L off
    ])
    def test_heads_per_step(self, B, L, H, K, hd, causal, window, grad):
        """Several query heads a grid step match the oracle, forward
        and through the op's ref-recompute VJP."""
        from repro.kernels import flash_attention as fa
        assert fa.heads_per_step(H, K, hd, 128, 128) > 1
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (B, L, H, hd))
        k = jax.random.normal(ks[1], (B, L, K, hd))
        v = jax.random.normal(ks[2], (B, L, K, hd))
        w = jax.random.normal(ks[3], (B, L, H, hd))

        def kernel(q, k, v):
            return ops.flash_attention(q, k, v, causal=causal,
                                       window=window)

        def oracle(q, k, v):
            kk, vv = jnp.repeat(k, H // K, 2), jnp.repeat(v, H // K, 2)
            return ref.flash_attention_ref(q, kk, vv, causal=causal,
                                           window=window)

        np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                                   np.asarray(oracle(q, k, v)),
                                   atol=2e-5, rtol=2e-5)
        if grad:
            g = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), (0, 1, 2))
            g_ref = jax.grad(lambda *a: jnp.sum(oracle(*a) * w), (0, 1, 2))
            for a, b in zip(g(q, k, v), g_ref(q, k, v)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=2e-5, rtol=2e-5)

    def test_heads_per_step_rule(self, tmp_path):
        """``hb`` divides H, is a multiple of rep and fits the VMEM
        budget; the cells' shapes take all 12 heads a step, and the
        ``kernel.grid`` event says so."""
        from repro import obs
        from repro.kernels import flash_attention as fa
        from repro.obs.sink import read_events
        for H, K, hd in [(12, 12, 64), (6, 6, 64), (3, 3, 64), (32, 8, 128),
                         (32, 4, 128), (64, 8, 128), (40, 8, 128),
                         (16, 16, 256), (8, 1, 512)]:
            hb = fa.heads_per_step(H, K, hd, 128, 128)
            assert H % hb == 0 and hb % (H // K) == 0, (H, K, hd, hb)
            assert (fa.step_vmem_bytes(hb, H // K, hd, 128, 128, 4)
                    <= fa.VMEM_BUDGET or hb == H // K), (H, K, hd, hb)
        assert fa.heads_per_step(12, 12, 64, 128, 128) == 12

        sink = obs.configure(str(tmp_path))
        try:
            for shape in [(16, 1024, 12, 64), (128, 128, 12, 64)]:
                x = jax.ShapeDtypeStruct(shape, jnp.float32)
                jax.eval_shape(fa.flash_attention, x, x, x)
            obs.flush()
            ev = [e for e in read_events(sink.paths)
                  if e["event"] == "kernel.grid"]
        finally:
            obs.close()
        assert [e["kernel"] for e in ev] == ["flash_attention"] * 2
        assert [e["heads_per_step"] for e in ev] == [12, 12]
        assert [e["grid_steps"] for e in ev] == [16 * 8 * 8, 128]
        assert [e["in_band_steps"] for e in ev] == [16 * 36, 128]


class TestExpertFFN:
    @pytest.mark.parametrize("E,T,M,F", [
        (4, 64, 96, 160), (8, 128, 64, 256), (2, 256, 128, 128),
    ])
    @pytest.mark.parametrize("glu", [True, False])
    def test_vs_ref(self, E, T, M, F, glu):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(ks[0], (E, T, M))
        w1 = jax.random.normal(ks[1], (E, M, F)) * 0.1
        w3 = jax.random.normal(ks[2], (E, M, F)) * 0.1 if glu else None
        w2 = jax.random.normal(ks[3], (E, F, M)) * 0.1
        act = "silu" if glu else "gelu"
        out = ops.expert_ffn(x, w1, w3, w2, act=act)
        exp = ref.expert_ffn_ref(x, w1, w3, w2, act=act)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=5e-4, rtol=5e-4)

    def test_bf16(self):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(ks[0], (2, 64, 64), jnp.bfloat16)
        w1 = (jax.random.normal(ks[1], (2, 64, 128)) * 0.1).astype(
            jnp.bfloat16)
        w3 = (jax.random.normal(ks[2], (2, 64, 128)) * 0.1).astype(
            jnp.bfloat16)
        w2 = (jax.random.normal(ks[3], (2, 128, 64)) * 0.1).astype(
            jnp.bfloat16)
        out = ops.expert_ffn(x, w1, w3, w2)
        exp = ref.expert_ffn_ref(x, w1, w3, w2)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   atol=3e-2, rtol=3e-2)


class TestMoEDispatchCombine:
    def _routing(self, S, M, E, k, cap, seed=0):
        rng = jax.random.PRNGKey(seed)
        x = jax.random.normal(rng, (S, M))
        wg = jax.random.normal(jax.random.PRNGKey(seed + 1), (M, E)) * 0.3
        eidx, slot, w, _ = topk_gate(
            x, wg, GateConfig(n_experts=E, top_k=k, capacity_factor=4.0),
            cap)
        flat = jnp.where(slot < cap, eidx * cap + slot, E * cap)
        return x, flat.astype(jnp.int32), w

    @pytest.mark.parametrize("S,M,E,k,cap", [
        (128, 64, 8, 2, 48), (256, 128, 4, 1, 96), (64, 32, 16, 4, 24),
    ])
    def test_dispatch_combine_vs_ref(self, S, M, E, k, cap):
        x, flat, w = self._routing(S, M, E, k, cap)
        n_slots = E * cap
        buf = ops.moe_dispatch(x, flat, n_slots)
        bref = ref.moe_dispatch_ref(x, flat, n_slots)
        np.testing.assert_allclose(np.asarray(buf), np.asarray(bref),
                                   atol=1e-6)
        y = ops.moe_combine(bref, flat, w)
        yref = ref.moe_combine_ref(bref, flat, w)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                                   atol=1e-5, rtol=1e-5)

    def test_dispatch_drops(self):
        S, M, E, cap = 128, 32, 2, 8   # force drops
        x, flat, w = self._routing(S, M, E, 1, cap)
        assert (np.asarray(flat) == E * cap).any()
        buf = ops.moe_dispatch(x, flat, E * cap)
        bref = ref.moe_dispatch_ref(x, flat, E * cap)
        np.testing.assert_allclose(np.asarray(buf), np.asarray(bref),
                                   atol=1e-6)


class TestRMSNorm:
    @settings(max_examples=10, deadline=None)
    @given(R=st.sampled_from([32, 128]), D=st.sampled_from([64, 96, 256]),
           seed=st.integers(0, 50))
    def test_vs_ref(self, R, D, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (R, D))
        s = jax.random.uniform(jax.random.PRNGKey(seed + 1), (D,))
        np.testing.assert_allclose(np.asarray(ops.rmsnorm(x, s)),
                                   np.asarray(ref.rmsnorm_ref(x, s)),
                                   atol=2e-6, rtol=2e-6)
