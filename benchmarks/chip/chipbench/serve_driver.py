"""Drive one serving cell through ``ServeEngine.serve_requests``.

Set-up makes the engine (its jitted init makes the weights on the device
from the seed) and serves one request of every prompt length the traffic
holds, which compiles every program the window will run; the engine's
counters are then cleared. In the window the loop hands ``serve_requests``
every request that is due and not yet served, oldest first, up to
``per_call``, and sleeps until the next one is due when none is. A request
is timed from when it was due to when its call returned. Requests due in
the window that are still waiting when it closes are served after it, up
to ``drain_s``; one not served by then has failed. A traced run notes the
engine's prefill and decode programs during the warm-up
(``programs.Noted``), so that their compiled HLO can be read after the
window.
"""

from __future__ import annotations

import contextlib
import gc
import math
import time
from unittest import mock

import numpy as np

from . import flops
from .programs import Noted
from .reference import load as load_reference
from .spec import arch_config
from .traffic import serve_requests


def _p(values: list, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q)) if values else math.nan


def run(cell, seed: int, seconds: float, devices, tracer=None) -> dict:
    from jax.profiler import TraceAnnotation

    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import ServeEngine

    t0 = time.perf_counter()
    cfg, t = cell.config, cell.traffic
    engine = ServeEngine(arch_config(cfg), batch=t["batch"], max_seq=t["max_seq"],
                         mesh=make_host_mesh(devices=devices), seed=seed)
    programs = Noted()
    noting = contextlib.nullcontext()
    if tracer is not None:
        noting = mock.patch.multiple(engine, _prefill=programs.noting("prefill", engine._prefill),
                                     _decode=programs.noting("decode", engine._decode))
    requests = serve_requests(t, seed, seconds, cfg["vocab_size"])
    gen = int(t["gen_len"])
    with TraceAnnotation("bench:warmup"), noting:  # two new tokens run every program once
        for length in t["prompt_lengths"]:
            engine.serve_requests([np.zeros(length, np.int32)], 2)
    for k in engine.stats:
        engine.stats[k] = type(engine.stats[k])(0)

    due_in_window = [r for r in requests if r["due"] < seconds]
    pending = list(due_in_window)
    done: dict = {}  # id -> (finish time from window start, tokens)
    calls: list = []  # (start, end, ids)
    if tracer is not None:
        tracer.start()
    w0 = time.perf_counter()
    close, deadline = seconds, seconds + float(t["drain_s"])
    while pending:
        now = time.perf_counter() - w0
        if t["arrival"] == "at_start" and now >= close:
            break  # an offline queue is drawn from only while the window is open
        if now >= deadline:
            break
        ready = [r for r in pending if r["due"] <= now][: int(t["per_call"])]
        if not ready:
            with TraceAnnotation("bench:wait_arrival"):
                time.sleep(max(0.0, min(r["due"] for r in pending) - now))
            continue
        with TraceAnnotation("bench:serve_requests"):
            outs = engine.serve_requests([r["prompt"] for r in ready], gen)
        end = time.perf_counter() - w0
        calls.append((now, end, [r["id"] for r in ready]))
        for r, out in zip(ready, outs):
            done[r["id"]] = (end, np.asarray(out))
        served = {r["id"] for r in ready}
        pending = [r for r in pending if r["id"] not in served]
        if tracer is not None:
            tracer.maybe_stop()
    wall = time.perf_counter() - w0
    if tracer is not None:
        tracer.stop()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    stats = dict(engine.stats)
    del engine
    gc.collect()

    if t["arrival"] == "at_start":
        attempted = [r for r in requests if r["id"] in done]
    else:
        attempted = due_in_window
    by_id = {r["id"]: r for r in requests}
    served = [by_id[i] for i in done if done[i][0] <= deadline]
    latency = [done[r["id"]][0] - r["due"] for r in served]
    failed = len(attempted) - len(served)
    tail = sorted(latency) + [math.inf] * failed
    p95 = float(tail[max(0, math.ceil(0.95 * len(tail)) - 1)]) if tail else math.nan
    p95 = min(p95, deadline)  # past the tail: failed requests read as the drain limit
    busy = sum(e - s for s, e, _ in calls)
    new_tokens = sum(len(done[r["id"]][1]) for r in served)
    # per-layer rates of a traced run: over the traced span only
    span = wall if tracer is None else tracer.t_stop - w0
    useful = sum(flops.request_flops(cfg, len(r["prompt"]), gen) for r in served
                 if done[r["id"]][0] <= span)
    batches = stats["requests"] / t["batch"]
    decode_steps = batches * gen
    step_s = stats["decode_s"] / decode_steps if decode_steps else math.nan
    prompt = t["prompt_lengths"][0]
    one_length = len(t["prompt_lengths"]) == 1
    filled = prompt + (gen + 1) / 2  # mean positions a decode step reads, one length only
    e2e = {"serve_tokens_per_s": new_tokens / busy if busy else math.nan,
           "serve_latency_p95_s": p95}
    return {
        "setup_s": w0 - t0,
        "window_s": wall,
        "attempted": len(attempted),
        "failed": failed,
        "memory_peak_bytes": memory_peak,
        "e2e": e2e,
        "values": {
            **e2e,
            "useful_flops_per_s": useful / span if span else math.nan,
            "decode_step_s": step_s,
            "decode_step_flops": t["batch"] * flops.decode_flops(cfg, int(filled) - 1),
            "decode_step_bytes": flops.decode_step_bytes(cfg, t["batch"], filled),
            "useful_row_share": (len(served) * gen / stats["decode_tokens"]
                                 if stats["decode_tokens"] else math.nan),
            "chips": len(devices),
            "one_prompt_length": one_length,
            # a prefill call's work, known where every call has one prompt length
            "prefill_flops_per_call": (t["batch"] * flops.prefill_flops(cfg, prompt)
                                       if one_length else None),
            "kernel_work": flops.causal_kernel_work(cfg, t["batch"], prompt) if one_length else {},
        },
        "log": {
            "calls": len(calls), "busy_s": busy, "new_tokens": new_tokens,
            "latency_median_s": _p(latency, 50), "latency_count": len(latency),
            "oldest_wait_at_call_s": max((s - min(by_id[i]["due"] for i in ids)
                                     for s, _, ids in calls), default=0.0),
            "stats": stats,
        },
        "served": {i: (by_id[i]["prompt"], done[i][1]) for i in done},
        "calls": [(s, e, len(ids)) for s, e, ids in calls],
        "programs": programs,
    }


def sample(served: dict, traffic: dict, seed: int) -> list:
    """Ids of the requests the check compares: the longest, and more drawn
    from the seed, ``check_requests`` in all."""
    ids = sorted(served)
    if not ids:
        return []
    longest = max(ids, key=lambda i: (len(served[i][0]) + len(served[i][1]), -i))
    rest = [i for i in ids if i != longest]
    rng = np.random.default_rng([seed, 3])
    k = min(len(rest), int(traffic["check_requests"]) - 1)
    return [longest] + sorted(rng.choice(rest, size=k, replace=False).tolist())


def gaps(cfg: dict, served: dict, ids: list, seed: int, *, control: bool = False) -> dict:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over the sampled requests. With ``control`` the
    token compared at each position is the one the fp8 reference puts first
    instead of the served one. The reference is the configuration's."""
    import jax.numpy as jnp

    ref = load_reference(cfg)
    params = ref.init_weights(cfg, seed)
    V = cfg["vocab_size"]
    worst, worst_ctl, tokens = 0.0, 0.0, 0
    for i in ids:
        prompt, out = served[i]
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        pos = slice(len(prompt) - 1, len(seq))
        lg = ref.logits(params, seq[None], cfg)[0, pos, :V]
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, jnp.asarray(out)[:, None], -1)[:, 0]
        worst = max(worst, float(jnp.max(best - got)))
        tokens += len(out)
        if control:
            low = ref.logits(params, seq[None], cfg, numerics="fp8")[0, pos, :V]
            pick = jnp.argmax(low, axis=-1)
            got_c = jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
            worst_ctl = max(worst_ctl, float(jnp.max(best - got_c)))
    out = {"logit_gap": worst, "_tokens": tokens, "_requests": len(ids)}
    if control:
        out["control_logit_gap"] = worst_ctl
    return out
