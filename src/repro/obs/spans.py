"""Host spans on the device path: profiler annotations named ``nbi:*``.

:func:`span` returns a ``jax.profiler.TraceAnnotation``. With no profiler
session active it costs the creation of that one object; inside a
``jax.profiler`` trace (``start_trace``, ``start_server`` + TensorBoard)
each span lands on the host timeline of the same trace, on the same clock,
as the device's operations. There is no switch: no environment variable,
no registry lookup, no clock read and no host sync.

Spans (``docs/observability.md``, "Device path"):

* ``serve.batch`` — ``ServeEngine.serve_requests``: bucketing requests by
  prompt length, and filling each engine batch (filler rows included).
* ``serve.prefill`` — ``ServeEngine.generate_batch``: the prefill dispatch
  through the wait for its logits; holds ``serve.pad_cache``
  (``pad_cache_to``, the prompt-sized cache into the decode layout).
* ``serve.decode`` — the whole token loop of ``generate_batch``.
* ``serve.token_fetch`` — the host's wait for a sampled token
  (``ServeEngine`` and ``ContinuousBatchingEngine``, once per step).
* ``serve.decode_step`` — the dispatch of one decode step (both engines).
* ``serve.sample`` — the dispatch of the sampling of one token (both
  engines).
* ``train.data`` — ``train()``: the next batch from the loader and its
  upload.
* ``train.step`` — the dispatch of the jitted train step.
* ``train.log_sync`` — the pull of a step's metrics to the host.
* ``train.checkpoint`` — ``CheckpointManager.save``.

The model step carries ``jax.named_scope`` names in the op metadata of
the compiled program (``op_name``), forward, recomputed and backward
alike: ``embed``, ``attention``, ``mlp``, ``decode_attention``,
``kv_cache_write``, ``lm_head``, ``loss``, ``grad_cast``, ``clip`` and
``optimizer``.
"""

from __future__ import annotations

PREFIX = "nbi:"


def span(name: str):
    """A context manager marking ``nbi:<name>`` on the profiler's host timeline."""
    import jax

    return jax.profiler.TraceAnnotation(PREFIX + name)
