"""Drive one training cell through ``repro.launch.train.train``.

``train()`` is called once, as ``python -m repro.launch.train`` would call
it, with three things of the benchmark's put in its way:

* ``get_config`` returns the cell's configuration (``train`` looks a
  configuration up by name, and the benchmark's is cut to one chip's share);
* ``make_train_loader`` returns the benchmark's feed: rows from the seed,
  a new batch each step;
* ``jax.jit`` wraps the jitted train step it builds, so that the harness
  reads the losses of the first steps, the optimizer's state after step 1
  and the weights after the last checked step, as they come out of the very
  step the window then times. The wrapper adds a host span per call and
  nothing else to the timed path. In a traced run its first call also
  notes the step's abstract arguments (``programs.Noted``), so that its
  compiled HLO can be read after the window.

The window opens at the first loss the loop pulls (every ``log_every``
steps), after the step has compiled, and closes at the first pull at least
``seconds`` later: ``on_metrics`` then raises to end the loop.
"""

from __future__ import annotations

import gc
import math
import time
from unittest import mock

import numpy as np

from . import flops
from .programs import Noted
from .reference import load as load_reference
from .spec import arch_config
from .traffic import train_rows


class WindowClosed(Exception):
    pass


class _Feed:
    """The loader ``train()`` pulls a batch from before every step."""

    def __init__(self, traffic: dict, seed: int, vocab: int, start: int = 0):
        self.traffic, self.seed, self.vocab, self.i = traffic, seed, vocab, start

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench:feed"):
            rows = train_rows(self.traffic, self.seed, self.i, self.vocab)
        self.i += 1
        return rows

    def close(self) -> None:
        pass

    def state_dict(self) -> dict:
        return {"cursor": self.i}


def _leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))(tree)
    flat, _ = jax.tree_util.tree_flatten_with_path(norms)
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


def _change_norms(params, cfg: dict, seed: int) -> dict:
    """Per leaf, the norm of the weights' change from their seeded start."""
    import jax
    import jax.numpy as jnp

    ref = load_reference(cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    leaves = {jax.tree_util.keystr(p): x for p, x in flat}
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    out = {}
    for index, (path, _, _) in enumerate(ref.leaf_paths(cfg)):
        out[path] = float(diff(leaves[path], ref.draw_leaf(cfg, seed, index)))
    return out


class _StepRecorder:
    """Stands in for ``jax`` inside ``repro.launch.train``: ``jit`` of the
    train step (the only call with ``donate_argnums``) returns the step
    wrapped to record the checked readings, and with ``capture`` noted in
    ``programs`` on its first call."""

    def __init__(self, real_jax, cfg: dict, traffic: dict, seed: int, capture: bool = False):
        self._jax = real_jax
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.checked = int(traffic["checked_steps"])
        self.calls = 0
        self.losses: list = []
        self.grad_norms: dict = {}
        self.change_norms: dict = {}
        self.capture, self.programs = capture, Noted()

    def __getattr__(self, name):
        return getattr(self._jax, name)

    def jit(self, fn, **kw):
        jitted = self._jax.jit(fn, **kw)
        if "donate_argnums" not in kw:
            return jitted
        annotate = self._jax.profiler.TraceAnnotation

        def step(state, batch):
            if self.capture and self.calls == 0:
                self.programs.note("train_step", jitted, (state, batch))
            with annotate("bench:train_step"):
                state, metrics = jitted(state, batch)
            self.calls += 1
            if self.calls <= self.checked:
                self._record(state, metrics)
            return state, metrics

        return step

    def _record(self, state, metrics) -> None:
        self.losses.append(float(metrics["loss"]))
        if self.calls == 1:
            b1 = self.traffic["optimizer"]["b1"]
            self.grad_norms = {k: v / (1 - b1) for k, v in _leaf_norms(state["opt"]["m"]).items()}
        if self.calls == self.checked:
            self.change_norms = _change_norms(state["params"], self.cfg, self.seed)


def run(cell, seed: int, seconds: float, devices, tracer=None) -> dict:
    import jax

    import repro.launch.train as train_mod
    from repro.launch.mesh import make_host_mesh

    t0 = time.perf_counter()
    cfg, t = cell.config, cell.traffic
    arch = arch_config(cfg)
    argv = ["--arch", arch.name, "--steps", str(t["steps"]),
            "--global-batch", str(t["global_batch"]), "--seq", str(t["seq"]),
            "--lr", str(t["lr"]), "--warmup", str(t["warmup"]), "--seed", str(seed),
            "--log-every", str(t["log_every"])]
    args = train_mod.build_argparser().parse_args(argv)
    recorder = _StepRecorder(jax, cfg, t, seed, capture=tracer is not None)
    stamps: list = []  # (time, step, loss) at each pull of the loss

    def on_metrics(m: dict) -> None:
        now = time.perf_counter()
        if tracer is not None:
            if not stamps:
                tracer.start()
                now = time.perf_counter()
            else:
                tracer.maybe_stop()
        stamps.append((now, int(m["step"]), float(m["loss"])))
        if len(stamps) > 1 and now - stamps[0][0] >= seconds:
            raise WindowClosed

    def feed(vocab_size, global_batch, seq, **kw):
        return _Feed(t, seed, cfg["vocab_size"], start=kw.get("start", 0))

    mesh = make_host_mesh(devices=devices)
    with mock.patch.object(train_mod, "get_config", lambda name: arch), \
            mock.patch.object(train_mod, "make_train_loader", feed), \
            mock.patch.object(train_mod, "jax", recorder):
        try:
            train_mod.train(args, mesh=mesh, on_metrics=on_metrics)
        except WindowClosed:
            pass
    if tracer is not None:
        tracer.stop()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    gc.collect()

    (w0, s0, _), (w1, s1, _) = stamps[0], stamps[-1]
    tokens = (s1 - s0) * t["global_batch"] * t["seq"]
    window_s = w1 - w0
    losses = [x[2] for x in stamps]
    # per-layer rates of a traced run: over the traced span only
    tw, ts, _ = [x for x in stamps if tracer is None or x[0] <= tracer.t_stop][-1]
    traced_rate = (ts - s0) * t["global_batch"] * t["seq"] / (tw - w0) if tw > w0 else math.nan
    return {
        "setup_s": w0 - t0,
        "window_s": window_s,
        "attempted": s1 - s0,
        "failed": sum(not math.isfinite(x) for x in losses),
        "memory_peak_bytes": memory_peak,
        "e2e": {"train_tokens_per_s": tokens / window_s},
        "values": {
            "train_tokens_per_s": traced_rate,
            "flops_per_token": flops.train_flops_per_token(cfg, t["seq"]),
            "kernel_work": flops.causal_kernel_work(cfg, t["global_batch"] // len(devices),
                                                    t["seq"]),
            "chips": len(devices),
        },
        "log": {"window_steps": [s0, s1], "window_losses": losses,
                "step_s": window_s / max(1, s1 - s0)},
        "program": {"losses": recorder.losses, "grad_norms": recorder.grad_norms,
                    "change_norms": recorder.change_norms},
        "programs": recorder.programs,
    }


def reference_readings(cfg: dict, traffic: dict, seed: int, *, numerics: str = "f32",
                       rows: slice = slice(None), chips: int = 1) -> dict:
    """The same readings from the plain reference: the losses of the checked
    steps, the first step's clipped gradient norms and the weights' change,
    per leaf, from the configuration's reference module. ``rows`` keeps a
    part of each batch (a planted fault). The reference takes each batch in
    ``chips`` blocks of rows, one chip's share of it in the program, so that
    it fits on one chip."""
    import jax
    import jax.numpy as jnp

    ref = load_reference(cfg)
    steps = int(traffic["checked_steps"])
    params = ref.init_weights(cfg, seed)
    zeros = lambda tree: jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)
    m, v = zeros(params), zeros(params)
    losses, grad_norms = [], {}
    for k in range(steps):
        batch = train_rows(traffic, seed, k, cfg["vocab_size"])
        tokens, labels = batch["tokens"][rows], batch["labels"][rows]
        block = max(1, len(tokens) // chips)
        denom = max(1, int(np.sum(labels >= 0)))
        loss, grads = 0.0, None
        for r in range(0, len(tokens), block):
            part, grads = ref.loss_and_grads(params, tokens[r : r + block],
                                             labels[r : r + block], cfg, numerics,
                                             denom=denom, acc=grads)
            loss += float(part)
        losses.append(loss)
        if k == 0:
            raw = ref.leaf_norms(grads, cfg)
        params, m, v, scale = ref.adamw_step(params, m, v, grads, k + 1, traffic)
        if k == 0:
            grad_norms = {p: n * scale for p, n in raw.items()}
        del grads
    del m, v
    change = ref.change_norms(params, cfg, seed)
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


def compare(program: dict, reference: dict) -> dict:
    """The three numbers compared, each the worst case (see ``PERF.md``):
    relative loss gap over the checked steps; and per leaf, the gap between
    the program's and the reference's norm of the first gradient and of the
    weights' change, over the larger of the reference's norm of that leaf and
    of the median leaf. Leaves whose reference gradient is under a
    thousandth of the median leaf's are left out of both."""
    n = min(len(program["losses"]), len(reference["losses"]))
    loss_gap = max((abs(a - b) / abs(b) for a, b in
                    zip(program["losses"][:n], reference["losses"][:n])), default=math.inf)
    if n < len(reference["losses"]):
        loss_gap = math.inf
    g_ref = reference["grad_norms"]
    g_med = float(np.median(list(g_ref.values())))
    kept = [p for p, g in g_ref.items() if g >= 1e-3 * g_med]

    def worst(prog: dict, refn: dict) -> tuple:
        med = float(np.median([refn[p] for p in kept]))
        gaps = {p: abs(prog.get(p, math.inf) - refn[p]) / max(refn[p], med, 1e-30) for p in kept}
        leaf = max(gaps, key=gaps.get)
        return gaps[leaf], leaf

    grad_gap, grad_leaf = worst(program["grad_norms"], g_ref)
    change_gap, change_leaf = worst(program["change_norms"], reference["change_norms"])
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": grad_gap,
        "change_norm_gap": change_gap,
        "_worst_leaves": {"grad_norm_gap": grad_leaf, "change_norm_gap": change_leaf},
        "_left_out": sorted(set(g_ref) - set(kept)),
    }
