"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + gradient path.

Every kernel runs in interpret mode (CPU container); the same pallas_call
lowers to Mosaic on TPU. Tolerances: f32 ≈ 1e-5 absolute; bf16 inputs get
looser bounds (bf16 has ~3 decimal digits)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip("hypothesis")
given, settings, st = hypothesis.given, hypothesis.settings, hypothesis.strategies

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import attention_ref, lru_ref, rmsnorm_ref, wkv6_ref
from repro.kernels.rglru_scan import lru_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.rwkv6_scan import wkv6_pallas

RNG = np.random.default_rng(42)


def rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


class TestFlashAttention:
    @pytest.mark.parametrize(
        "B,Hq,Hkv,Sq,Skv,d",
        [
            (1, 2, 2, 64, 64, 32),
            (2, 4, 1, 128, 128, 64),   # GQA 4:1
            (1, 8, 2, 96, 160, 32),    # ragged + GQA
            (1, 2, 2, 33, 65, 16),     # pad-needing odd sizes
            (1, 1, 1, 256, 256, 128),  # MXU-aligned
        ],
    )
    def test_shape_sweep_causal(self, B, Hq, Hkv, Sq, Skv, d):
        q, k, v = rand((B, Hq, Sq, d)), rand((B, Hkv, Skv, d)), rand((B, Hkv, Skv, d))
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(attention_ref(q, k, v, causal=True)),
            atol=2e-5, rtol=1e-4,
        )

    @pytest.mark.parametrize("window", [16, 64])
    def test_local_window(self, window):
        q, k, v = rand((1, 2, 128, 32)), rand((1, 2, 128, 32)), rand((1, 2, 128, 32))
        out = flash_attention(q, k, v, causal=True, window=window, block_q=32, block_k=32)
        ref = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)

    def test_non_causal_cross_attention(self):
        q, k, v = rand((2, 2, 40, 32)), rand((2, 2, 100, 32)), rand((2, 2, 100, 32))
        out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
        ref = attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)

    def test_logit_cap(self):
        q, k, v = rand((1, 2, 64, 32), scale=4), rand((1, 2, 64, 32), scale=4), rand((1, 2, 64, 32))
        out = flash_attention(q, k, v, logit_cap=30.0, block_q=32, block_k=32)
        ref = attention_ref(q, k, v, logit_cap=30.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)

    def test_bf16(self):
        q = rand((1, 2, 64, 64), jnp.bfloat16)
        k = rand((1, 2, 64, 64), jnp.bfloat16)
        v = rand((1, 2, 64, 64), jnp.bfloat16)
        out = flash_attention(q, k, v)
        ref = attention_ref(q, k, v)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=0.05
        )

    def test_gradient_via_custom_vjp(self):
        """ops.attention(use_pallas=True) must match XLA-path gradients."""
        q, k, v = rand((1, 2, 64, 32)), rand((1, 2, 64, 32)), rand((1, 2, 64, 32))

        def loss_pallas(q, k, v):
            return ops.attention(q, k, v, use_pallas=True).sum()

        def loss_xla(q, k, v):
            return ops.attention(q, k, v, use_pallas=False, kv_chunk=32).sum()

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    @settings(max_examples=15, deadline=None)
    @given(
        sq=st.integers(8, 96), skv=st.integers(8, 96),
        d=st.sampled_from([8, 16, 32]), g=st.sampled_from([1, 2, 4]),
    )
    def test_property_random_shapes(self, sq, skv, d, g):
        q = rand((1, 2 * g, sq, d))
        k = rand((1, 2, skv, d))
        v = rand((1, 2, skv, d))
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        ref = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=1e-3)


class TestWKV6:
    @pytest.mark.parametrize(
        "B,H,T,dk,dv,chunk",
        [
            (1, 2, 64, 32, 32, 32),
            (2, 2, 128, 64, 64, 64),
            (1, 1, 192, 16, 64, 64),   # dk != dv
            (1, 3, 64, 64, 64, 16),    # small chunks
        ],
    )
    def test_shape_sweep(self, B, H, T, dk, dv, chunk):
        r, k = rand((B, H, T, dk)), rand((B, H, T, dk))
        v = rand((B, H, T, dv))
        w = jnp.asarray(RNG.uniform(0.3, 0.999, (B, H, T, dk)), jnp.float32)
        u = rand((H, dk))
        s0 = rand((B, H, dk, dv))
        y, sf = wkv6_pallas(r, k, v, w, u, s0, chunk=chunk)
        yr, sr = wkv6_ref(r, k, v, w, u, s0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(sf), np.asarray(sr), atol=5e-4, rtol=1e-3)

    def test_chunking_invariance(self):
        """Same answer for any chunk size — the blocking must be invisible."""
        shapes = (1, 2, 128, 32, 32)
        r, k = rand(shapes[:3] + (32,)), rand(shapes[:3] + (32,))
        v = rand((1, 2, 128, 32))
        w = jnp.asarray(RNG.uniform(0.5, 0.99, (1, 2, 128, 32)), jnp.float32)
        u, s0 = rand((2, 32)), rand((1, 2, 32, 32))
        outs = [wkv6_pallas(r, k, v, w, u, s0, chunk=c)[0] for c in (16, 32, 64, 128)]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=5e-4)

    def test_matches_model_xla_path(self):
        from repro.models.rwkv6 import wkv6_chunked

        r, k = rand((1, 2, 128, 64)), rand((1, 2, 128, 64))
        v = rand((1, 2, 128, 64))
        w = jnp.asarray(RNG.uniform(0.3, 0.999, (1, 2, 128, 64)), jnp.float32)
        u, s0 = rand((2, 64)), jnp.zeros((1, 2, 64, 64), jnp.float32)
        y_p, s_p = wkv6_pallas(r, k, v, w, u, s0)
        y_x, s_x = wkv6_chunked(r, k, v, w, u, s0)
        np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x), atol=5e-4)
        np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_x), atol=5e-4)

    def test_gradients_match_xla(self):
        r, k = rand((1, 1, 64, 16)), rand((1, 1, 64, 16))
        v = rand((1, 1, 64, 16))
        w = jnp.asarray(RNG.uniform(0.5, 0.99, (1, 1, 64, 16)), jnp.float32)
        u, s0 = rand((1, 16)), jnp.zeros((1, 1, 16, 16), jnp.float32)

        def f(use_pallas):
            def loss(r, k, v, u):
                y, _ = ops.wkv6(r, k, v, w, u, s0, chunk=16, use_pallas=use_pallas)
                return (y**2).sum()

            return jax.grad(loss, argnums=(0, 1, 2, 3))(r, k, v, u)

        for a, b in zip(f(True), f(False)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3)


class TestLRU:
    @pytest.mark.parametrize(
        "B,T,W,chunk,bw",
        [(1, 64, 128, 32, 128), (2, 128, 256, 64, 64), (1, 256, 64, 128, 64)],
    )
    def test_shape_sweep(self, B, T, W, chunk, bw):
        a = jnp.asarray(RNG.uniform(0.2, 0.999, (B, T, W)), jnp.float32)
        b = rand((B, T, W), scale=0.3)
        h0 = rand((B, W))
        y, hf = lru_pallas(a, b, h0, chunk=chunk, block_w=bw)
        yr, hr = lru_ref(a, b, h0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(hf), np.asarray(hr), atol=1e-5, rtol=1e-5)

    def test_xla_associative_scan_matches(self):
        a = jnp.asarray(RNG.uniform(0.2, 0.999, (2, 64, 32)), jnp.float32)
        b = rand((2, 64, 32), scale=0.3)
        h0 = rand((2, 32))
        y_p, h_p = ops.lru_scan(a, b, h0, use_pallas=True)
        y_x, h_x = ops.lru_scan(a, b, h0, use_pallas=False)
        np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x), atol=1e-5)
        np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_x), atol=1e-5)

    def test_gradients_match(self):
        a = jnp.asarray(RNG.uniform(0.3, 0.99, (1, 32, 16)), jnp.float32)
        b = rand((1, 32, 16), scale=0.3)
        h0 = rand((1, 16))

        def mk(use_pallas):
            def loss(a, b, h0):
                y, hf = ops.lru_scan(a, b, h0, use_pallas=use_pallas)
                return (y**2).sum() + (hf**2).sum()

            return jax.grad(loss, argnums=(0, 1, 2))(a, b, h0)

        for g1, g2 in zip(mk(True), mk(False)):
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


class TestRMSNorm:
    @pytest.mark.parametrize(
        "shape,dtype",
        [
            ((64, 768), jnp.float32),
            ((7, 33), jnp.float32),        # ragged rows/width
            ((4, 16, 256), jnp.float32),   # 3-D input
            ((128, 512), jnp.bfloat16),
        ],
    )
    def test_sweep(self, shape, dtype):
        x = rand(shape, dtype)
        w = rand(shape[-1:], dtype)
        out = rmsnorm_pallas(x, w, block_rows=16)
        ref = rmsnorm_ref(x, w)
        atol = 1e-5 if dtype == jnp.float32 else 0.05
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=atol
        )

    def test_gradients_match(self):
        x, w = rand((8, 64)), rand((64,))

        def mk(use_pallas):
            return jax.grad(
                lambda x, w: (ops.rmsnorm(x, w, use_pallas=use_pallas) ** 2).sum(),
                argnums=(0, 1),
            )(x, w)

        for a, b in zip(mk(True), mk(False)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


class TestMoEGating:
    @pytest.mark.parametrize(
        "G,N,E,k,cap",
        [(2, 64, 16, 2, 12), (1, 128, 32, 4, 20), (3, 32, 8, 1, 5)],
    )
    def test_vs_oracle(self, G, N, E, k, cap):
        from repro.kernels.moe_gating import moe_gating_pallas
        from repro.kernels.ref import moe_gating_ref

        logits = rand((G, N, E))
        ip, gp, pp = moe_gating_pallas(logits, top_k=k, capacity=cap)
        ir, gr, pr = moe_gating_ref(logits, top_k=k, capacity=cap)
        np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))
        np.testing.assert_array_equal(np.asarray(pp), np.asarray(pr))
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr), atol=1e-6)

    def test_matches_model_routing(self):
        """dispatch/combine rebuilt from (idx, gate, pos) == top_k_routing."""
        from repro.configs import get_smoke_config
        from repro.kernels.moe_gating import moe_gating_pallas
        from repro.models.moe import top_k_routing

        cfg = get_smoke_config("deepseek-moe-16b").replace(n_experts=16, top_k=3)
        logits = rand((2, 64, 16))
        cap = 16
        dispatch, combine, _ = top_k_routing(logits, cfg, cap)
        ip, gp, pp = moe_gating_pallas(
            jax.nn.log_softmax(logits), top_k=3, capacity=cap
        )
        d2 = np.zeros(dispatch.shape, bool)
        c2 = np.zeros(combine.shape, np.float32)
        ipn, gpn, ppn = map(np.asarray, (ip, gp, pp))
        for g in range(2):
            for n in range(64):
                for j in range(3):
                    if ppn[g, n, j] >= 0:
                        d2[g, n, ipn[g, n, j], ppn[g, n, j]] = True
                        c2[g, n, ipn[g, n, j], ppn[g, n, j]] += gpn[g, n, j]
        np.testing.assert_array_equal(np.asarray(dispatch), d2)
        np.testing.assert_allclose(np.asarray(combine), c2, atol=1e-5)

    def test_drops_marked_minus_one(self):
        from repro.kernels.moe_gating import moe_gating_pallas

        # everyone wants expert 0 → only `cap` survive at rank 0
        logits = jnp.zeros((1, 32, 4)).at[:, :, 0].set(10.0)
        _, _, pos = moe_gating_pallas(logits, top_k=1, capacity=5)
        p = np.asarray(pos)[0, :, 0]
        assert (p >= 0).sum() == 5
        assert np.array_equal(np.sort(p[p >= 0]), np.arange(5))


class TestInterpretDefault:
    """Each kernel interprets by default off the TPU and never on it."""

    @staticmethod
    def _kernels():
        from repro.kernels.moe_gating import moe_gating_pallas

        x = rand((1, 1, 8, 128))
        return {
            "flash_attention": lambda: flash_attention(x, x, x),
            "rmsnorm": lambda: rmsnorm_pallas(rand((8, 128)), rand((128,))),
            "lru": lambda: lru_pallas(rand((1, 8, 128)), rand((1, 8, 128)), rand((1, 128))),
            "wkv6": lambda: wkv6_pallas(x, x, x, x, rand((1, 128)), rand((1, 1, 128, 128))),
            "moe_gating": lambda: moe_gating_pallas(rand((1, 8, 4)), top_k=2, capacity=4),
        }

    @pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True)])
    @pytest.mark.parametrize(
        "kernel", ["flash_attention", "rmsnorm", "lru", "wkv6", "moe_gating"]
    )
    def test_default_follows_backend(self, monkeypatch, kernel, backend, interpret):
        from jax.experimental import pallas as pl

        class Called(Exception):
            pass

        seen = []

        def spy(*args, interpret=None, **kwargs):
            seen.append(interpret)
            raise Called

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(pl, "pallas_call", spy)
        jax.clear_caches()  # the kernels are jitted: trace them afresh
        with pytest.raises(Called):
            self._kernels()[kernel]()
        assert seen == [interpret]
