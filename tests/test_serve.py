"""Serving engine: generation correctness, batching determinism, cache pad."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.serve import ServeEngine, pad_cache_to, sample_tokens
from repro.models.registry import build_model


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("codeqwen1.5-7b")
    return ServeEngine(cfg, batch=2, max_seq=48, seed=0)


class TestPadCache:
    def test_pads_seq_axis(self):
        cfg = get_smoke_config("codeqwen1.5-7b")
        model = build_model(cfg)
        small = jax.tree_util.tree_map(
            lambda sd: jnp.ones((*sd.shape[:-2], 8, sd.shape[-1]), sd.dtype),
            model.cache_defs_fn(1, 8),
        )
        target = model.cache_defs_fn(1, 32)
        padded = pad_cache_to(small, target)
        for leaf, want in zip(
            jax.tree_util.tree_leaves(padded), jax.tree_util.tree_leaves(target)
        ):
            assert leaf.shape == want.shape
            np.testing.assert_array_equal(np.asarray(leaf)[..., 8:, :], 0)

    def test_oversize_rejected(self):
        cfg = get_smoke_config("codeqwen1.5-7b")
        model = build_model(cfg)
        big = jax.tree_util.tree_map(
            lambda sd: jnp.ones(sd.shape, sd.dtype), model.cache_defs_fn(1, 64)
        )
        with pytest.raises(ValueError, match="exceeds"):
            pad_cache_to(big, model.cache_defs_fn(1, 32))


class TestSampling:
    """Tokens come from the real vocabulary, never the embedding's padding."""

    @pytest.mark.parametrize("temperature", [0.0, 1e-3])
    def test_padded_vocab_never_sampled(self, temperature):
        logits = jnp.zeros((3, 512)).at[:, 500:].set(50.0).at[:, 7].set(10.0)
        tok = sample_tokens(logits, 500, temperature, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(tok), 7)


class TestGeneration:
    def test_greedy_matches_step_by_step_forward(self, engine):
        """Engine generation must equal naive full-recompute greedy decode."""
        cfg = engine.cfg
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size, (2, 12)).astype(np.int32)
        out = engine.generate_batch(prompts.copy(), gen_len=6)

        # oracle: recompute the full forward for every generated token
        model = engine.model
        params = engine.params
        toks = jnp.asarray(prompts)
        want = []
        for _ in range(6):
            logits, _ = jax.jit(model.prefill_fn)(params, {"tokens": toks})
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            want.append(np.asarray(nxt))
            toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(out, np.stack(want, axis=1))

    def test_batch_independence(self, engine):
        """A row's output never depends on its batch-mates."""
        cfg = engine.cfg
        rng = np.random.default_rng(1)
        a = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
        b1 = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
        b2 = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
        out1 = engine.generate_batch(np.stack([a, b1]), gen_len=5)
        out2 = engine.generate_batch(np.stack([a, b2]), gen_len=5)
        np.testing.assert_array_equal(out1[0], out2[0])

    def test_serve_requests_order_and_determinism(self, engine):
        cfg = engine.cfg
        rng = np.random.default_rng(2)
        reqs = [
            rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in (5, 9, 5, 13, 9)
        ]
        outs = engine.serve_requests(reqs, gen_len=4)
        assert len(outs) == 5
        solo = engine.serve_requests([reqs[3]], gen_len=4)[0]
        np.testing.assert_array_equal(solo, outs[3])

    def test_temperature_sampling_valid_tokens(self, engine):
        cfg = engine.cfg
        prompts = np.ones((2, 8), np.int32)
        out = engine.generate_batch(prompts, gen_len=4, temperature=1.0)
        assert out.min() >= 0 and out.max() < cfg.vocab_size  # padded vocab ok

    def test_capacity_guard(self, engine):
        with pytest.raises(AssertionError):
            engine.generate_batch(np.ones((2, 47), np.int32), gen_len=5)


class TestRecurrentServing:
    def test_rwkv_generation_matches_full_forward(self):
        cfg = get_smoke_config("rwkv6-7b")
        engine = ServeEngine(cfg, batch=1, max_seq=32, seed=0)
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, cfg.vocab_size, (1, 10)).astype(np.int32)
        out = engine.generate_batch(prompt.copy(), gen_len=4)

        model, params = engine.model, engine.params
        toks = jnp.asarray(prompt)
        for i in range(4):
            logits, _ = jax.jit(model.prefill_fn)(params, {"tokens": toks})
            nxt = int(jnp.argmax(logits[0, -1]))
            assert nxt == int(out[0, i])
            toks = jnp.concatenate([toks, jnp.full((1, 1), nxt, jnp.int32)], axis=1)


class TestContinuousBatching:
    def test_exact_vs_full_recompute(self):
        """Slot-based continuous batching must be bit-identical to greedy
        full-recompute decoding for every request, regardless of slot
        assignment and arrival order."""
        from repro.launch.serve import ContinuousBatchingEngine

        cfg = get_smoke_config("codeqwen1.5-7b")
        rng = np.random.default_rng(3)
        reqs = [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
                for n in (12, 5, 9, 12, 7)]
        cb = ContinuousBatchingEngine(cfg, batch=2, max_seq=48, seed=0)
        outs = cb.serve(reqs, gen_len=4)

        model, params = cb.model, cb.params
        for i, req in enumerate(reqs):
            toks = jnp.asarray(req[None, :])
            want = []
            for _ in range(4):
                logits, _ = jax.jit(model.prefill_fn)(params, {"tokens": toks})
                nxt = int(jnp.argmax(logits[0, -1]))
                want.append(nxt)
                toks = jnp.concatenate(
                    [toks, jnp.full((1, 1), nxt, jnp.int32)], axis=1
                )
            assert outs[i].tolist() == want, i

    def test_beats_static_batching_steps(self):
        """Mixed lengths through fixed slots: fewer decode steps than the
        static lower bound ceil(R/B)·gen (no waiting on batch-mates)."""
        from repro.launch.serve import ContinuousBatchingEngine

        cfg = get_smoke_config("codeqwen1.5-7b")
        rng = np.random.default_rng(4)
        reqs = [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
                for n in (4, 16, 4, 16, 4, 16)]
        cb = ContinuousBatchingEngine(cfg, batch=3, max_seq=40, seed=0)
        cb.serve(reqs, gen_len=5)
        occupancy = cb.stats["occupancy_sum"] / cb.stats["decode_steps"]
        assert occupancy > 0.8
        assert cb.stats["decode_steps"] <= -(-len(reqs) // 3) * 5 + 2

    def test_moe_rejected(self):
        from repro.launch.serve import ContinuousBatchingEngine

        with pytest.raises(AssertionError):
            ContinuousBatchingEngine(
                get_smoke_config("deepseek-moe-16b"), batch=2, max_seq=32
            )


class TestVectorPos:
    @pytest.mark.parametrize("arch", ["codeqwen1.5-7b", "minicpm3-4b"])
    def test_vector_pos_equals_per_row_scalar(self, arch):
        cfg = get_smoke_config(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        B, S = 3, 24
        rng = np.random.default_rng(1)
        cache = jax.tree_util.tree_map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype), model.cache_defs_fn(B, S)
        )
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
        posv = jnp.asarray([2, 7, 11])
        lm, _ = jax.jit(model.decode_fn)(params, cache, tok, posv)
        for b in range(B):
            cb = jax.tree_util.tree_map(
                lambda x: x[:, b:b + 1] if x.ndim >= 2 else x, cache
            )
            lb, _ = jax.jit(model.decode_fn)(
                params, cb, tok[b:b + 1], jnp.asarray(int(posv[b]))
            )
            np.testing.assert_allclose(
                np.asarray(lm[b]), np.asarray(lb[0]), atol=2e-5
            )
