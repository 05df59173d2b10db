"""The ``nbi:`` host spans on the device path, read back from a profiler
trace of the serving engines and of ``train()`` on the CPU."""

import glob
import time
from collections import Counter

import jax
import numpy as np
import pytest

from repro.obs.spans import PREFIX, span


def _trace_spans(trace_dir) -> list:
    """(name, start_ns, end_ns) of every ``nbi:`` event in the trace."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.name[len(PREFIX):], e.start_ns, e.end_ns)
                       for e in line.events if e.name.startswith(PREFIX))
    return out


def _record(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _trace_spans(tmp_path)


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_span_is_a_named_trace_annotation():
    s = span("serve.prefill")
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:  # no profiler session: a no-op
        pass


class TestServeSpans:
    @pytest.fixture(scope="class")
    def engine(self):
        from repro.configs import get_smoke_config
        from repro.launch.serve import ServeEngine

        return ServeEngine(get_smoke_config("codeqwen1.5-7b"), batch=2, max_seq=32, seed=0)

    def test_generate_batch_spans(self, engine, tmp_path):
        gen_len = 5
        prompts = np.random.default_rng(0).integers(0, 100, (3, 8)).astype(np.int32)
        engine.serve_requests(list(prompts), 2)  # compile outside the trace
        spans = _record(tmp_path, lambda: engine.serve_requests(list(prompts), gen_len))
        count = Counter(n for n, _, _ in spans)
        batches = 2  # three requests of one length, engine batch 2
        assert count["serve.prefill"] == batches
        assert count["serve.pad_cache"] == batches
        assert count["serve.decode"] == batches
        assert count["serve.decode_step"] == batches * gen_len
        assert count["serve.token_fetch"] == batches * gen_len
        assert count["serve.sample"] == batches * (gen_len + 1)
        assert count["serve.batch"] == 1 + batches  # bucketing, then each batch's rows
        by = lambda name: [s for s in spans if s[0] == name]  # noqa: E731
        for pad in by("serve.pad_cache"):
            assert any(_inside(pad, p) for p in by("serve.prefill"))
        for step in by("serve.decode_step") + by("serve.token_fetch") + by("serve.sample"):
            assert any(_inside(step, d) for d in by("serve.decode"))

    def test_spans_leave_outputs_and_stats_alone(self, engine):
        """The spanned loop serves what an equal engine serves, and counts
        the same requests and tokens."""
        prompts = np.random.default_rng(1).integers(0, 100, (2, 6)).astype(np.int32)
        before = dict(engine.stats)
        out = engine.generate_batch(prompts, 4)
        assert engine.stats["requests"] - before["requests"] == 2
        assert engine.stats["decode_tokens"] - before["decode_tokens"] == 8
        assert engine.stats["prefill_tokens"] - before["prefill_tokens"] == 12
        np.testing.assert_array_equal(out, engine.generate_batch(prompts, 4))

    def test_continuous_batching_step_spans(self, tmp_path):
        from repro.configs import get_smoke_config
        from repro.launch.serve import ContinuousBatchingEngine

        eng = ContinuousBatchingEngine(get_smoke_config("codeqwen1.5-7b"), batch=2,
                                       max_seq=24, seed=0)
        reqs = [np.arange(1, 6, dtype=np.int32), np.arange(2, 9, dtype=np.int32),
                np.arange(3, 7, dtype=np.int32)]
        eng.serve(reqs, 3)  # compile outside the trace
        steps = eng.stats["decode_steps"]
        spans = _record(tmp_path, lambda: eng.serve(reqs, 3))
        count = Counter(n for n, _, _ in spans)
        steps = eng.stats["decode_steps"] - steps
        assert steps > 0
        for name in ("serve.decode_step", "serve.sample", "serve.token_fetch"):
            assert count[name] == steps, name


class TestTrainSpans:
    def _args(self, monkeypatch, steps: int, log_every: int):
        import repro.configs.nbi100m as mod
        from repro.launch.train import build_argparser

        orig = mod.config
        monkeypatch.setattr(
            mod, "config",
            lambda: orig().replace(
                name="nano", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                head_dim=16, d_ff=128, vocab_size=512,
            ),
        )
        return build_argparser().parse_args([
            "--arch", "nbi-100m", "--steps", str(steps), "--global-batch", "2",
            "--seq", "16", "--log-every", str(log_every),
        ])

    def test_one_step_span_per_step(self, tmp_path, monkeypatch):
        from repro.launch.train import train

        args = self._args(monkeypatch, steps=6, log_every=3)
        spans = _record(tmp_path, lambda: train(args))
        count = Counter(n for n, _, _ in spans)
        assert count["train.step"] == 6
        assert count["train.data"] == 6
        assert count["train.log_sync"] == 2
        assert count["train.checkpoint"] == 0  # no checkpoint directory

    def test_tokens_per_s_counts_since_the_previous_pull(self, monkeypatch):
        """Each logged rate covers the steps since the previous pull and the
        time since it, so only the first holds the compile."""
        import repro.launch.train as train_mod

        clock = iter([0.0, 100.0, 101.0, 103.0])

        class FakeTime:
            monotonic = staticmethod(time.monotonic)
            perf_counter = staticmethod(lambda: next(clock))

        args = self._args(monkeypatch, steps=6, log_every=2)
        monkeypatch.setattr(train_mod, "time", FakeTime)
        rates = [m["tokens_per_s"] for m in train_mod.train(args)["metrics"]]
        tokens = 2 * 2 * 16  # two steps of a 2 x 16 batch between pulls
        assert rates == pytest.approx([tokens / 100.0, tokens / 1.0, tokens / 2.0])
