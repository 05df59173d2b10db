"""train — the end-to-end training driver.

    python -m repro.launch.train --arch nbi-100m --steps 300 \
        --global-batch 16 --seq 512 --ckpt-dir ckpt/nbi100m

Assembles the full stack: config → model → mesh/sharding rules → optimizer →
data pipeline → jit'd train step → checkpoint manager, with:

* **restart safety** — on start, the latest checkpoint (weights, optimizer,
  data cursor, RNG) is restored if present; a SIGTERM/SIGINT triggers a
  final synchronous save, so preemption loses at most the steps since the
  last periodic save;
* **eco-preemption** (beyond-paper, built on the paper's EcoScheduler) —
  with ``--eco-preempt``, the loop checkpoints and exits cleanly at the
  next peak-hours boundary, printing the ``--begin`` directive for the
  next eco window so the wrapper can resubmit the remainder of the run;
* **throughput accounting** — tokens/s and an analytic MFU estimate
  against the local device's peak (the real MFU story lives in the
  dry-run roofline; this is the live-run counterpart).

On the CPU container this is exercised with ``--smoke`` configs and the
``examples/train_100m.py`` driver; on a real pod the same file runs under
``nbilaunch train arch=...`` with the mesh from repro.launch.mesh.
"""

from __future__ import annotations

import argparse
import signal
import time
from datetime import datetime
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.core.eco import EcoScheduler
from repro.data import make_train_loader
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model
from repro.optim import make_optimizer
from repro.optim.schedules import cosine_warmup
from repro.parallel.sharding import resolve_tree, rules_for
from repro.training.steps import (
    init_train_state,
    make_train_step,
    train_state_logical,
)


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="repro.launch.train")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--host-index", type=int, default=0)
    ap.add_argument("--host-count", type=int, default=1)
    ap.add_argument("--eco-preempt", action="store_true",
                    help="checkpoint + exit at the next peak-hours boundary")
    ap.add_argument("--now", default=None, help=argparse.SUPPRESS)  # tests
    return ap


def train(args, *, mesh=None, on_metrics=None) -> dict:
    # multi-host: under a multi-task SLURM job, join the jax.distributed
    # cluster and derive this host's data shard; no-op in single-process runs
    from repro.launch.distributed import maybe_initialize

    proc_index, proc_count = maybe_initialize()
    if proc_count > 1 and args.host_count == 1:
        args.host_index, args.host_count = proc_index, proc_count

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    mesh = mesh or make_host_mesh()

    optimizer = make_optimizer(
        cfg.optimizer, lr=cosine_warmup(args.lr, args.warmup, max(args.steps, 1))
    )
    rules = rules_for(
        cfg, mesh, param_defs=model.param_defs, batch_size=args.global_batch,
        extra_dims={"heads": cfg.n_heads},
    )
    state_sh = resolve_tree(mesh, train_state_logical(model, optimizer), rules)
    step_fn = jax.jit(
        make_train_step(model, optimizer, rules, mesh),
        in_shardings=(state_sh, None),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )

    # ---- state: fresh init or checkpoint restore --------------------------
    manager = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    data_cursor = 0
    # jitted so each weight is made where its sharding puts it
    init = jax.jit(lambda rng: init_train_state(model, optimizer, rng), out_shardings=state_sh)
    with mesh:
        state = init(jax.random.PRNGKey(args.seed))
    if manager and manager.latest_step() is not None:
        state, extra, start_step = manager.restore(state, shardings=state_sh)
        data_cursor = int(extra.get("data_cursor", start_step))
        print(f"[train] resumed from step {start_step}")

    loader = make_train_loader(
        model.cfg.vocab_size,
        args.global_batch,
        args.seq,
        seed=args.seed,
        host_index=args.host_index,
        host_count=args.host_count,
        start=data_cursor,
    )

    # ---- eco-preemption & signal handling ----------------------------------
    # ``--now`` (tests/examples) sets a *virtual clock start*: simulated time
    # advances with real elapsed time from that instant.
    wall_t0 = time.monotonic()
    virtual_start = datetime.fromisoformat(args.now) if args.now else None

    def clock() -> datetime:
        if virtual_start is None:
            return datetime.now()
        from datetime import timedelta

        return virtual_start + timedelta(seconds=time.monotonic() - wall_t0)

    eco_deadline = None
    sched = None
    if args.eco_preempt:
        sched = EcoScheduler()
        eco_deadline = sched.next_peak_start(clock())
        if eco_deadline:
            print(f"[eco] will checkpoint+exit at peak boundary {eco_deadline}")

    stop = {"reason": None}

    def _sig(signum, _frame):
        stop["reason"] = f"signal {signum}"

    old_handlers = {}
    for s in (signal.SIGINT, signal.SIGTERM):
        try:
            old_handlers[s] = signal.signal(s, _sig)
        except ValueError:
            pass  # not the main thread (tests)

    # ---- loop ---------------------------------------------------------------
    metrics_hist = []
    t_start = time.perf_counter()
    tokens_per_step = args.global_batch * args.seq
    step = start_step
    steps_done = start_step  # steps whose update actually applied
    try:
        with mesh:
            for step in range(start_step, args.steps):
                batch_np = next(loader)
                batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
                state, metrics = step_fn(state, batch)
                steps_done = step + 1
                if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
                    m = {k: float(v) for k, v in metrics.items()}
                    dt = time.perf_counter() - t_start
                    done = step + 1 - start_step
                    m.update(step=step + 1, tokens_per_s=tokens_per_step * done / dt)
                    metrics_hist.append(m)
                    if on_metrics:
                        on_metrics(m)
                    print(
                        f"[train] step {step + 1}/{args.steps} "
                        f"loss={m['loss']:.4f} acc={m.get('accuracy', 0):.3f} "
                        f"tok/s={m['tokens_per_s']:.0f}",
                        flush=True,
                    )
                if manager and (step + 1) % args.ckpt_every == 0:
                    manager.save(
                        step + 1, state,
                        extra={"data_cursor": loader.state_dict()["cursor"],
                               "arch": args.arch},
                        blocking=False,
                    )
                if stop["reason"]:
                    break
                if eco_deadline and clock() >= eco_deadline:
                    stop["reason"] = "eco-preempt"
                    break
    finally:
        for s, h in old_handlers.items():
            signal.signal(s, h)
        loader.close()

    completed = steps_done
    result = {
        "completed_steps": completed,
        "stopped": stop["reason"],
        "metrics": metrics_hist,
        "final_loss": metrics_hist[-1]["loss"] if metrics_hist else None,
        "state": state,
    }

    if manager and (stop["reason"] or args.steps > start_step):
        manager.save(
            completed, state,
            extra={"data_cursor": loader.state_dict()["cursor"], "arch": args.arch,
                   "stopped": stop["reason"]},
            blocking=True,
        )
    if stop["reason"] == "eco-preempt" and sched is not None:
        remaining_s = 3600  # conservative: at least an hour of work left
        directive = sched.begin_directive(remaining_s, clock())
        result["resubmit_begin"] = directive
        print(f"[eco] resubmit with --begin={directive}")
    return result


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    enable_compile_cache()
    result = train(args)
    if result["final_loss"] is not None:
        print(f"[train] done: steps={result['completed_steps']} "
              f"final_loss={result['final_loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
