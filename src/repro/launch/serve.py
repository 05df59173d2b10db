"""serve — batched decode service driver.

    python -m repro.launch.serve --arch nbi-100m --smoke --batch 4 \
        --prompt-len 32 --gen-len 16

Implements the inference side of the framework: a :class:`ServeEngine`
that prefills a batch of prompts, pads the prompt-sized KV cache into the
fixed-capacity decode cache, then runs the jit'd single-token decode step
in a loop (greedy or temperature sampling). A tiny dynamic batcher groups
queued requests into engine-sized batches (left-aligned, right-padded)
so the expensive compiled shapes stay fixed — the vLLM-style idiom of
"compile once per (batch, max_seq), feed many requests".

On a pod this runs under ``nbilaunch serve arch=...`` with the KV cache
sequence dim sharded over the ``model`` mesh axis (flash-decoding split-KV,
see DESIGN.md); on CPU the smoke config serves real tokens.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model
from repro.obs.spans import span
from repro.parallel.sharding import resolve_tree, rules_for
from repro.training.steps import make_prefill_step, make_serve_step


def _init_params(model, mesh, rules, seed: int):
    """Random weights made on the device under their shardings, in one jitted
    program: eager init would hold each leaf's float32 draw beside the
    weights already made, which a 4B-parameter model cannot afford in 16 GB."""
    shardings = resolve_tree(mesh, model.param_logical(), rules)
    with mesh:
        return jax.jit(model.init, out_shardings=shardings)(jax.random.PRNGKey(seed))


def sample_tokens(logits, vocab_size: int, temperature: float = 0.0, rng=None):
    """Greedy (``temperature <= 0``) or temperature sampling over the real
    vocabulary: the padded tail of the embedding is never emitted."""
    logits = logits[..., :vocab_size]
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(rng, logits / temperature, axis=-1)


def pad_cache_to(cache, cache_defs):
    """Zero-pad a prompt-sized prefill cache into the fixed decode layout.

    Leaves match rank; any axis where the prefill extent is smaller (the
    kv-seq axis) is right-padded. Zero padding is safe: decode masks by
    position, and recurrent states (rwkv/rglru) match shape exactly.
    """
    def pad(leaf, want):
        target = want.shape
        if tuple(leaf.shape) == tuple(target):
            return leaf.astype(want.dtype)
        pads = []
        for have, need in zip(leaf.shape, target):
            if have > need:
                raise ValueError(f"cache leaf {leaf.shape} exceeds {target}")
            pads.append((0, need - have))
        return jnp.pad(leaf, pads).astype(want.dtype)

    return jax.tree_util.tree_map(pad, cache, cache_defs)


class ServeEngine:
    """Fixed-shape batched generation over one model."""

    def __init__(self, cfg, *, batch: int, max_seq: int, mesh=None, seed: int = 0):
        self.cfg = cfg
        self.batch = batch
        self.max_seq = max_seq
        self.mesh = mesh or make_host_mesh()
        self.model = build_model(cfg)
        rules = rules_for(
            cfg, self.mesh,
            param_defs=self.model.param_defs,
            batch_size=batch,
            extra_dims={"kv_seq": max_seq, "heads": cfg.n_heads},
        )
        self.rules = rules
        self.params = _init_params(self.model, self.mesh, rules, seed)
        self._prefill = jax.jit(make_prefill_step(self.model, rules, self.mesh))
        # the cache is donated: the step writes its new token in place
        self._decode = jax.jit(make_serve_step(self.model, rules, self.mesh),
                               donate_argnums=(1,))
        self.stats = {"requests": 0, "prefill_tokens": 0, "decode_tokens": 0,
                      "prefill_s": 0.0, "decode_s": 0.0}

    # -- one fixed-shape batch ------------------------------------------------

    def generate_batch(
        self, prompts: np.ndarray, gen_len: int, *,
        temperature: float = 0.0, eos_id: int | None = None, rng=None,
    ) -> np.ndarray:
        """prompts: (batch, prompt_len) int32 → (batch, gen_len) int32."""
        B, P = prompts.shape
        assert B == self.batch, (B, self.batch)
        assert P + gen_len <= self.max_seq, "exceeds engine capacity"
        cache_defs = self.model.cache_defs_fn(B, self.max_seq)
        t0 = time.perf_counter()
        with self.mesh:
            with span("serve.prefill"):
                batch_in = {"tokens": jnp.asarray(prompts, jnp.int32)}
                if self.cfg.family == "encdec":
                    batch_in["frames"] = jnp.zeros(
                        (B, self.cfg.enc_len, self.cfg.d_model), self.cfg.dtype
                    )
                logits, cache = self._prefill(self.params, batch_in)
                with span("serve.pad_cache"):
                    cache = pad_cache_to(cache, cache_defs)
                jax.block_until_ready(logits)
            t1 = time.perf_counter()

            with span("serve.decode"):
                out = np.zeros((B, gen_len), np.int32)
                finished = np.zeros((B,), bool)
                rng = rng or jax.random.PRNGKey(0)
                with span("serve.sample"):
                    tok = sample_tokens(logits[:, -1], self.cfg.vocab_size, temperature, rng)
                for i in range(gen_len):
                    with span("serve.token_fetch"):
                        tok = np.asarray(tok)
                    out[:, i] = np.where(finished, eos_id or 0, tok)
                    if eos_id is not None:
                        finished |= out[:, i] == eos_id
                        if finished.all():
                            out = out[:, : i + 1]
                            break
                    with span("serve.decode_step"):
                        pos = jnp.asarray(P + i, jnp.int32)
                        logits, cache = self._decode(
                            self.params, cache, jnp.asarray(out[:, i : i + 1]), pos
                        )
                    with span("serve.sample"):
                        rng, sub = jax.random.split(rng)
                        tok = sample_tokens(logits[:, -1], self.cfg.vocab_size, temperature, sub)
                jax.block_until_ready(logits)
        t2 = time.perf_counter()
        self.stats["requests"] += B
        self.stats["prefill_tokens"] += B * P
        self.stats["decode_tokens"] += B * out.shape[1]
        self.stats["prefill_s"] += t1 - t0
        self.stats["decode_s"] += t2 - t1
        return out

    # -- dynamic batcher ----------------------------------------------------------

    def serve_requests(
        self, requests: list[np.ndarray], gen_len: int, *,
        temperature: float = 0.0,
    ) -> list[np.ndarray]:
        """Group variable-length requests into fixed engine batches.

        Requests are bucketed by *exact prompt length* (rows in one batch
        never see padding tokens, so a request's output is independent of
        its batch-mates — asserted by the serving tests). Short buckets are
        filled up to the engine batch by repeating the first row; filler
        rows are discarded. Responses return in input order.
        """
        results: list = [None] * len(requests)
        buckets: dict[int, list[int]] = {}
        with span("serve.batch"):
            for i, r in enumerate(requests):
                buckets.setdefault(len(r), []).append(i)
        for length, idxs in sorted(buckets.items()):
            for g in range(0, len(idxs), self.batch):
                group = idxs[g : g + self.batch]
                with span("serve.batch"):
                    block = np.empty((self.batch, length), np.int32)
                    for row in range(self.batch):
                        src = group[row] if row < len(group) else group[0]  # filler
                        block[row] = requests[src]
                out = self.generate_batch(block, gen_len, temperature=temperature)
                for row, i in enumerate(group):
                    results[i] = out[row]
        return results


class ContinuousBatchingEngine:
    """Slot-based continuous batching (the vLLM idiom, shapes held fixed).

    A fixed pool of ``batch`` decode slots advances every step with
    *per-slot positions* (the vector-``pos`` decode path); when a request
    finishes, the next queued request is prefilled (single-row, exact
    length) and written into the free slot's cache rows while the other
    slots keep decoding — no generation stalls on batch-mates, unlike
    static batching where the whole batch waits for its slowest member.

    Restricted to families whose decode is row-independent (dense GQA/MLA;
    MoE routing couples rows through capacity and is excluded).
    """

    def __init__(self, cfg, *, batch: int, max_seq: int, mesh=None, seed: int = 0):
        assert cfg.family in ("dense",), "continuous batching: dense families"
        self.cfg = cfg
        self.batch = batch
        self.max_seq = max_seq
        self.mesh = mesh or make_host_mesh()
        self.model = build_model(cfg)
        rules = rules_for(
            cfg, self.mesh, param_defs=self.model.param_defs, batch_size=batch,
            extra_dims={"kv_seq": max_seq, "heads": cfg.n_heads},
        )
        self.params = _init_params(self.model, self.mesh, rules, seed)
        self._prefill = jax.jit(make_prefill_step(self.model, rules, self.mesh))
        self._decode = jax.jit(make_serve_step(self.model, rules, self.mesh),
                               donate_argnums=(1,))
        self.stats = {"requests": 0, "decode_steps": 0, "slot_tokens": 0,
                      "occupancy_sum": 0.0}

    def _insert(self, cache, slot: int, prompt: np.ndarray):
        """Prefill one request and write its rows into ``slot``. Returns
        (cache, first generated token)."""
        toks = jnp.asarray(prompt[None, :], jnp.int32)
        logits, row_cache = self._prefill(self.params, {"tokens": toks})
        row_cache = pad_cache_to(
            row_cache, self.model.cache_defs_fn(1, self.max_seq)
        )
        cache = jax.tree_util.tree_map(
            lambda full, one: full.at[:, slot].set(one[:, 0]), cache, row_cache
        )
        return cache, int(sample_tokens(logits[0, -1], self.cfg.vocab_size))

    def serve(self, requests: list, gen_len: int) -> list:
        """Greedy-decode every request; returns outputs in input order."""
        B = self.batch
        cache = jax.tree_util.tree_map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype),
            self.model.cache_defs_fn(B, self.max_seq),
        )
        queue = list(range(len(requests)))
        outputs: list = [[] for _ in requests]
        slot_req = [-1] * B  # which request occupies each slot
        pos = np.zeros(B, np.int64)  # next write position per slot
        cur_tok = np.zeros(B, np.int64)

        def fill_free_slots(cache):
            for b in range(B):
                if slot_req[b] == -1 and queue:
                    i = queue.pop(0)
                    prompt = requests[i]
                    assert len(prompt) + gen_len <= self.max_seq
                    cache, tok = self._insert(cache, b, prompt)
                    slot_req[b] = i
                    pos[b] = len(prompt)
                    cur_tok[b] = tok
                    outputs[i].append(tok)
                    self.stats["requests"] += 1
            return cache

        with self.mesh:
            cache = fill_free_slots(cache)
            while any(s != -1 for s in slot_req):
                active = np.array([s != -1 for s in slot_req])
                self.stats["occupancy_sum"] += active.mean()
                self.stats["decode_steps"] += 1
                with span("serve.decode_step"):
                    logits, cache = self._decode(
                        self.params, cache,
                        jnp.asarray(cur_tok[:, None], jnp.int32),
                        jnp.asarray(pos, jnp.int32),
                    )
                with span("serve.sample"):
                    nxt = sample_tokens(logits[:, -1], self.cfg.vocab_size)
                with span("serve.token_fetch"):
                    nxt = np.asarray(nxt)
                for b in range(B):
                    if slot_req[b] == -1:
                        continue
                    i = slot_req[b]
                    self.stats["slot_tokens"] += 1
                    if len(outputs[i]) < gen_len:
                        outputs[i].append(int(nxt[b]))
                        cur_tok[b] = nxt[b]
                        pos[b] += 1
                    if len(outputs[i]) >= gen_len:
                        slot_req[b] = -1  # request done → slot free
                        pos[b] = 0
                        cur_tok[b] = 0
                cache = fill_free_slots(cache)
        return [np.asarray(o, np.int32) for o in outputs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.launch.serve")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    engine = ServeEngine(
        cfg,
        batch=args.batch,
        max_seq=args.prompt_len + args.gen_len,
        seed=args.seed,
    )
    rng = np.random.default_rng(args.seed)
    requests = [
        rng.integers(0, cfg.vocab_size, size=rng.integers(4, args.prompt_len + 1))
        .astype(np.int32)
        for _ in range(args.requests)
    ]
    t0 = time.perf_counter()
    outs = engine.serve_requests(requests, args.gen_len, temperature=args.temperature)
    dt = time.perf_counter() - t0
    for i, o in enumerate(outs[: 4]):
        print(f"[serve] req{i}: prompt_len={len(requests[i])} -> {o[:8].tolist()}...")
    s = engine.stats
    print(
        f"[serve] {len(requests)} requests in {dt:.2f}s | "
        f"prefill {s['prefill_tokens'] / max(s['prefill_s'], 1e-9):.0f} tok/s | "
        f"decode {s['decode_tokens'] / max(s['decode_s'], 1e-9):.0f} tok/s"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
