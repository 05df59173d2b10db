"""One run of one cell: set-up, the measured window, the check, the result.

    python3 benchmarks/chip/run_cell.py --workload NAME --seed N --seconds S --trace 0|1

The last line on standard output is the result, one JSON object: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, device busy time and a breakdown from the profiler's
trace of the window. Every number the check compares is printed beside its
limit, as the last lines on standard error and under ``checks``, the last
key of the result. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.

A traced run also turns the program's counter registry on
(``repro.obs.metrics``) before set-up and notes the jitted programs the
window runs as set-up calls them (``programs.py``). After the window each
per-layer metric's reader (``metrics/<name>.py``) gets one dict of values:
the driver's, and

- ``trace``: ``trace_reduce.reduce`` over the ``bench:`` spans;
- ``spans``: ``span_reduce.reduce`` over every span and the compiled
  programs: the program's ``nbi:`` spans (``span_stats``, ``idle_inside``),
  device time by scope (``scope_incl_s[name]``, for any name on an op's
  ``op_name`` path), by operation (``op_self_s[name]``, ``op_calls[name]``;
  a Pallas kernel under its kernel's name), and runs and device time by
  program (``program_runs``, ``program_s``);
- ``counters``: every sample of the registry, ``{name: {"label=value,...":
  value}}``;
- ``peaks``: the chip's row of ``peaks.py``.

So a new scope, kernel or counter of the program is read by a new metric
file and entry. An untraced run turns nothing on and notes no program.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

from . import peaks as peaks_mod
from . import serve_driver, span_reduce, train_driver, trace_reduce
from .spec import BENCH_DIR, metric_reader, resolve

EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def chip_devices(chips: int) -> tuple:
    """The first ``chips`` TPU devices and their description; never the CPU."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no accelerator: {e}") from None
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    log(f"[bench] platform={info['platform']} device_kind={info['kind']} count={info['count']}")
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {info['platform']}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return devs[:chips], info


class Tracer:
    """The profiler over the start of the window. The drivers call
    :meth:`maybe_stop` at each boundary they see (a pulled loss, a returned
    ``serve_requests`` call): the trace ends at the first one ``limit_s``
    into the window, or with the window, and the drivers read their
    per-layer rates over the traced span alone. The trace is read back by
    :meth:`reduce` after the run: a trace of a decode loop holds millions of
    events, and reading them inside the window would stall it."""

    def __init__(self, limit_s: float = 10.0):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.limit_s = limit_s
        self._span = None
        self.t_start = self.t_stop = None

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation("bench:window")
        self._span.__enter__()
        self.t_start = time.perf_counter()

    def maybe_stop(self) -> None:
        if self._span is not None and time.perf_counter() - self.t_start >= self.limit_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self._span is None:
            return
        self.t_stop = time.perf_counter()
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()

    def reduce(self, hlo: list = (), keep: str = "") -> tuple:
        """(``trace_reduce.reduce`` over the ``bench:`` spans alone,
        ``span_reduce.reduce`` over every span), the trace loaded once;
        ``hlo`` is [(label, compiled HLO text)] of the programs it ran. With
        ``keep`` the trace is copied into that directory."""
        import glob

        files = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
        try:
            events = span_reduce.load_events(files[0])
            if keep:
                shutil.copy(files[0], Path(keep) / "trace.xplane.pb")
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        bench = {**events, "spans": [x for x in events["spans"]
                                     if x[0].startswith(trace_reduce.SPAN_PREFIX)]}
        scoped = [{**span_reduce.program_scopes(text), "label": label} for label, text in hlo]
        return trace_reduce.reduce(bench), span_reduce.reduce(events, scoped)


@contextlib.contextmanager
def program_counters(on: bool):
    """With ``on``, a fresh registry of the program's counters is active in
    the block, and the dict yielded holds every sample of it once the block
    ends: ``{name: {"label=value,...": value}}``, a histogram as
    ``<name>_count`` and ``<name>_sum``. Without, nothing is turned on and
    the dict stays empty."""
    samples: dict = {}
    if not on:
        yield samples
        return
    from repro.obs import metrics

    registry = metrics.enable(metrics.MetricsRegistry())
    try:
        yield samples
    finally:
        metrics.disable()
        for fam in registry.families():
            for labels, child in fam.samples():
                key = ",".join(f"{k}={v}" for k, v in labels.items())
                if fam.kind == "histogram":
                    samples.setdefault(f"{fam.name}_count", {})[key] = child.count
                    samples.setdefault(f"{fam.name}_sum", {})[key] = child.sum
                else:
                    samples.setdefault(fam.name, {})[key] = child.value


def limits_for(cell_name: str) -> dict:
    path = BENCH_DIR / "workloads" / f"{cell_name}.json"
    return json.loads(path.read_text())["limits"]


def check(cell, seed: int, run: dict) -> dict:
    """The comparison with the plain reference, after the program is freed."""
    if cell.traffic["kind"] == "train":
        readings = train_driver.reference_readings(cell.config, cell.traffic, seed,
                                                   chips=cell.chips)
        return train_driver.compare(run["program"], readings)
    ids = serve_driver.sample(run["served"], cell.traffic, seed)
    return serve_driver.gaps(cell.config, run["served"], ids, seed)


def execute(cell, seed: int, seconds: float, trace: bool, devices, device_info: dict,
            *, peaks: dict | None = None, check_outputs: bool = True, keep: str = "") -> dict:
    """Everything after the look for a chip; returns the result object.
    Without ``check_outputs`` nothing is compared (``correct`` is None);
    with ``keep`` a traced run leaves its trace, the compiled HLO of its
    programs and the values its readers got in that directory."""
    if keep:
        Path(keep).mkdir(parents=True, exist_ok=True)
    tracer = Tracer() if trace else None
    driver = train_driver if cell.traffic["kind"] == "train" else serve_driver
    with program_counters(trace) as counters:
        run = driver.run(cell, seed, seconds, devices, tracer=tracer)
    # lowered again only now: tracing a program counts in the registry
    hlo = run.pop("programs").texts()
    gc.collect()
    numbers = check(cell, seed, run) if check_outputs else {}
    limits = limits_for(cell.name) if check_outputs else {}
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = (all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                   for c in checks.values()) if check_outputs else None)

    for k, v in run["log"].items():
        log(f"[bench] {k}: {v}")
    for k, v in numbers.items():
        if k.startswith("_"):
            log(f"[check] {k[1:]}: {v}")
    device = {**device_info, "memory_peak_bytes": int(run["memory_peak_bytes"])}
    if trace:
        s, spans = tracer.reduce(hlo, keep)
        device.update(busy_s=s["busy_s"], window_s=s["window_s"])
        values = {**run["values"], "trace": s, "spans": spans, "counters": counters,
                  "peaks": peaks}
        if keep:
            for i, (label, text) in enumerate(hlo):
                (Path(keep) / f"{cell.name}.{i}.{label}.hlo.txt").write_text(text)
            (Path(keep) / f"{cell.name}.values.json").write_text(
                json.dumps(values, indent=1, default=str))
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(values)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        extra = {"breakdown": {"device_ops": s["device_ops"], "idle_gaps": s["idle_gaps"]}}
        log(f"[bench] trace: busy {s['busy_s']} s of {s['window_s']} s, "
            f"collective exposed {s['collective_exposed_s']} s on {s['devices']} devices")
    else:
        wanted = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": v, "unit": wanted[k]} for k, v in run["e2e"].items()
                   if k in wanted}
        metrics["setup_s"] = {"value": run["setup_s"], "unit": wanted["setup_s"]}
        extra = {}
    log(f"[bench] setup_s: {run['setup_s']}  window_s: {run['window_s']}")
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'OVER'}")
    return {"correct": correct, "attempted": run["attempted"], "failed": run["failed"],
            "metrics": metrics, "device": device, **extra, "checks": checks}


def arguments(prog: str) -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog=prog)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    return ap


def on_chip(cell) -> tuple | None:
    """(devices, their description, peaks) with the compile cache on, or
    None where the chips are missing or of an unknown kind."""
    try:
        devices, info = chip_devices(cell.chips)
        peaks = peaks_mod.peaks_for(info["kind"])
    except (NoChip, peaks_mod.UnknownDevice) as e:
        log(f"[bench] {e}")
        return None

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    log(f"[bench] compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices, info, peaks


def main(argv=None) -> int:
    ap = arguments("run_cell.py")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = resolve(args.workload)
    chip = on_chip(cell)
    if chip is None:
        return EXIT_NO_CHIP
    devices, info, peaks = chip
    result = execute(cell, args.seed, args.seconds, bool(args.trace), devices, info,
                     peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0
