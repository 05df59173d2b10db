"""chip_smoke — train and serve once on a TPU through the normal entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: a 2x2 mesh against one chip

The model code is reached only through ``repro.launch.train.train`` (with
arguments from its ``build_argparser``) and ``repro.launch.serve.ServeEngine``:
the code that ``python -m repro.launch.train|serve`` runs in the job scripts
``nbilaunch`` generates. Weights are random, made from a seed.

One chip:
  * train nbi-100m at its full config (12 layers, d_model 768, vocab 32768,
    float32) for 8 steps at global batch 16 × 512 tokens: every loss finite,
    the last below the first;
  * serve minicpm3-4b at published widths (62 layers, MLA, vocab 73448,
    bf16): 8 greedy requests, four with 128-token and four with 256-token
    prompts, 32 new tokens each; every token in the vocabulary, and the same
    tokens when the 8 requests are served a second time.

``--chips 4`` runs only the four-chip phase: nbi-100m for 5 steps on a 2×2
``(data, model)`` mesh, then the same 5 steps on one chip, with per-step
losses that agree to 1e-3 relative.

A failed check exits non-zero. There is no CPU path: without a TPU the script
exits non-zero before any phase. The last line of a passing run is one JSON
object naming the device.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TRAIN_ARGV = ["--arch", "nbi-100m", "--global-batch", "16", "--seq", "512", "--log-every", "1"]
SERVE_ARCH = "minicpm3-4b"
SERVE_PROMPTS = (128,) * 4 + (256,) * 4
GEN_LEN = 32
LOSS_RTOL = 1e-3


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAILED: {what}")


def devices() -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"[smoke] platform={info['platform']} device_kind={info['kind']} "
          f"count={info['count']}", flush=True)
    return info


def train_phase(kind: str) -> None:
    from repro.launch.train import build_argparser, train

    args = build_argparser().parse_args([*TRAIN_ARGV, "--steps", "8"])
    stamps = []
    t0 = time.perf_counter()
    # loss is pulled to the host every step, so the stamps are step ends
    res = train(args, on_metrics=lambda m: stamps.append(time.perf_counter()))
    losses = [m["loss"] for m in res["metrics"]]
    print(f"[train] losses {' '.join(f'{x:.4f}' for x in losses)}", flush=True)
    check(len(losses) == args.steps, f"{len(losses)} of {args.steps} steps logged")
    check(all(math.isfinite(x) for x in losses), "a loss is not finite")
    check(losses[-1] < losses[0], f"loss did not fall: {losses[0]} -> {losses[-1]}")
    steady = (stamps[-1] - stamps[0]) / (len(stamps) - 1)
    tokens = args.global_batch * args.seq
    print(f"[train] set-up and first step (init, compile): {stamps[0] - t0:.2f} s", flush=True)
    print(f"[train] steady steps 2-{args.steps}: {steady * 1e3:.1f} ms/step, "
          f"{tokens / steady:.0f} tokens/s on {kind}", flush=True)


def serve_phase(cfg, kind: str) -> None:
    import jax
    import numpy as np

    from repro.launch.serve import ServeEngine

    t0 = time.perf_counter()
    engine = ServeEngine(cfg, batch=4, max_seq=max(SERVE_PROMPTS) + GEN_LEN)
    jax.block_until_ready(engine.params)
    weight_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(engine.params))
    print(f"[serve] {cfg.name}: {weight_bytes / 1e9:.2f} GB of weights made in "
          f"{time.perf_counter() - t0:.2f} s", flush=True)

    rng = np.random.default_rng(0)
    requests = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32) for n in SERVE_PROMPTS]
    t1 = time.perf_counter()
    first = engine.serve_requests(requests, GEN_LEN)
    t2 = time.perf_counter()
    second = engine.serve_requests(requests, GEN_LEN)
    t3 = time.perf_counter()

    for i, out in enumerate(first):
        check(out.shape == (GEN_LEN,), f"request {i}: {out.shape} tokens")
        check(0 <= out.min() and out.max() < cfg.vocab_size, f"request {i}: token out of vocab")
    same = all(np.array_equal(a, b) for a, b in zip(first, second))
    print(f"[serve] request 0 -> {first[0][:8].tolist()}...; second pass identical: {same}",
          flush=True)
    check(same, "serving the same requests twice gave different tokens")
    new_tokens = len(requests) * GEN_LEN
    print(f"[serve] first pass (compiles prefill x2, decode): {t2 - t1:.2f} s", flush=True)
    print(f"[serve] second pass: {t3 - t2:.2f} s, {new_tokens / (t3 - t2):.1f} new tokens/s "
          f"on {kind}", flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[serve] peak device memory {stats['peak_bytes_in_use'] / 1e9:.2f} GB", flush=True)


def four_chip_phase(kind: str) -> None:
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_argparser, train

    check(jax.device_count() == 4, f"--chips 4 needs 4 devices, have {jax.device_count()}")
    argv = [*TRAIN_ARGV, "--steps", "5"]
    meshes = {"2x2": make_host_mesh((2, 2)),
              "1x1": make_host_mesh((1, 1), devices=jax.devices()[:1])}
    runs = {}
    for name, mesh in meshes.items():
        t0 = time.perf_counter()
        runs[name] = train(build_argparser().parse_args(argv), mesh=mesh)
        print(f"[4chip] {name} mesh: {time.perf_counter() - t0:.2f} s for "
              f"{runs[name]['completed_steps']} steps (compile included) on {kind}", flush=True)

    wide = [m["loss"] for m in runs["2x2"]["metrics"]]
    one = [m["loss"] for m in runs["1x1"]["metrics"]]
    check(len(wide) == len(one) == 5, "5 steps logged on each mesh")
    for step, (a, b) in enumerate(zip(wide, one), 1):
        rel = abs(a - b) / abs(b)
        print(f"[4chip] step {step}: loss 2x2 {a:.6f}  1x1 {b:.6f}  rel diff {rel:.2e}",
              flush=True)
        check(math.isfinite(a) and rel <= LOSS_RTOL, f"step {step}: losses disagree")

    w = runs["2x2"]["state"]["params"]["blocks"]["mlp"]["wi"]
    shard = w.addressable_shards[0].data.shape
    print(f"[4chip] blocks.mlp.wi {tuple(w.shape)} {w.sharding.spec}: per-device shard "
          f"{tuple(shard)} on {len(w.sharding.device_set)} devices", flush=True)
    check(shard[-1] * 2 == w.shape[-1], "the ff dim is not split over the model axis")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke.py", description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the 2x2-mesh training phase against one chip")
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
    dev = devices()
    if dev["platform"] != "tpu":
        print("[smoke] no TPU: this script runs only on the chip", file=sys.stderr)
        return 2

    if args.chips == 4:
        four_chip_phase(dev["kind"])
    else:
        train_phase(dev["kind"])
        serve_phase(get_config(SERVE_ARCH), dev["kind"])
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
