"""Run one cell traced and read the program's own spans and named scopes.

    python3 benchmarks/chip/trace_cell.py --workload NAME --seed N --seconds S [--out DIR]

The cell runs as ``run_cell.py --trace 1`` runs it: the same driver, the
profiler over the first 10 s of the window, and no comparison with the
reference afterwards. After the window the compiled HLO text of each
program the window ran is read back (lowered again from the arguments of
its first call, which the persistent compile cache serves), and the trace
is reduced by ``trace_reduce`` and ``span_reduce``. The last line on
standard output is one JSON object: both reductions (``span_reduce``'s
``scope_ops`` are the top device ops, each with its program and scope),
the readings of :data:`READERS`, and the rate over the traced part of the
window. With ``--out`` the trace and the HLO texts are copied there.
Without a TPU it exits 3.
"""

import argparse
import glob
import json
import shutil
import sys
from contextlib import ExitStack
from pathlib import Path
from unittest import mock

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import flops, harness, serve_driver, span_reduce, train_driver  # noqa: E402
from chipbench import peaks as peaks_mod  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.spec import resolve  # noqa: E402


def decode_host_gap_ms(v: dict):
    """Device idle time inside ``nbi:serve.decode`` over its decode steps."""
    steps = v["spans"]["span_stats"].get("nbi:serve.decode_step", {}).get("count")
    idle = v["spans"]["idle_inside"].get("nbi:serve.decode")
    return None if not steps or idle is None else 1e3 * idle / steps


def decode_cache_write_ms(v: dict):
    """Device self time under ``kv_cache_write`` over the decode steps."""
    steps = v["spans"]["span_stats"].get("nbi:serve.decode_step", {}).get("count")
    t = v["spans"]["scope_self_s"].get("kv_cache_write")
    return None if not steps or t is None else 1e3 * t / steps


def prefill_mfu(v: dict):
    """Prefill operations of the ``nbi:serve.prefill`` spans over their
    summed time, over the chips' bf16 peak, in %."""
    st = v["spans"]["span_stats"].get("nbi:serve.prefill")
    if not st or not v.get("prefill_flops_per_call"):
        return None
    rate = v["prefill_flops_per_call"] * st["count"] / st["s"]
    return 100.0 * rate / (v["chips"] * v["peaks"]["bf16_flops_per_s"])


def train_attention_share(v: dict):
    """Device self time under ``attention`` over device busy time, in %."""
    t = v["spans"]["scope_self_s"].get("attention")
    return None if t is None or not v["trace"]["busy_s"] else 100.0 * t / v["trace"]["busy_s"]


READERS = {
    "decode_host_gap_ms": decode_host_gap_ms,
    "decode_cache_write_ms": decode_cache_write_ms,
    "prefill_mfu.offline": prefill_mfu,
    "train_attention_share": train_attention_share,
}


class Program:
    """Stands in for a program the driver calls: calls ``call``, and keeps
    the abstract arguments of each new shape of the arguments after the
    first, so that the compiled HLO text of ``jitted`` for them can be read
    after the window."""

    def __init__(self, call, jitted, label: str):
        self.call, self.jitted, self.label, self.calls = call, jitted, label, {}

    def __call__(self, *args):
        import jax

        key = tuple((x.shape, str(x.dtype)) for x in jax.tree_util.tree_leaves(args[1:]))
        if key not in self.calls:
            self.calls[key] = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), args)
        return self.call(*args)


def capture(stack: ExitStack, kind: str) -> list:
    """Wrap the programs the cell's driver will run; returns the list of
    :class:`Program` it fills."""
    programs: list = []
    if kind == "train":
        real = train_driver._StepRecorder.jit

        def jit(recorder, fn, **kw):
            step = real(recorder, fn, **kw)
            if "donate_argnums" not in kw:
                return step
            programs.append(Program(step, recorder._jax.jit(fn, **kw), "train_step"))
            return programs[-1]

        stack.enter_context(mock.patch.object(train_driver._StepRecorder, "jit", jit))
        return programs
    import repro.launch.serve as serve_mod

    class Engine(serve_mod.ServeEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self._prefill = Program(self._prefill, self._prefill, "prefill")
            self._decode = Program(self._decode, self._decode, "decode")
            programs.extend([self._prefill, self._decode])

    stack.enter_context(mock.patch.object(serve_mod, "ServeEngine", Engine))
    return programs


def texts(programs: list) -> list:
    """[(label, compiled HLO text)] of every shape each program ran."""
    return [(f"{p.label}.{i}", p.jitted.lower(*args).compile().as_text())
            for p in programs for i, args in enumerate(p.calls.values())]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="trace_cell.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    cell = resolve(args.workload)
    try:
        devices, info = harness.chip_devices(cell.chips)
        peaks = peaks_mod.peaks_for(info["kind"])
    except (harness.NoChip, peaks_mod.UnknownDevice) as e:
        harness.log(f"[bench] {e}")
        return harness.EXIT_NO_CHIP

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(json.dumps(trace_cell(cell, args.seed, args.seconds, devices, peaks, args.out)),
          flush=True)
    return 0


def trace_cell(cell, seed: int, seconds: float, devices, peaks: dict, out: str = "") -> dict:
    """One traced run of ``cell``; returns the result object."""
    kind = cell.traffic["kind"]
    driver = train_driver if kind == "train" else serve_driver
    tracer = harness.Tracer()
    with ExitStack() as stack:
        programs = capture(stack, kind)
        run = driver.run(cell, seed, seconds, devices, tracer=tracer)
    hlo = texts(programs)
    path = glob.glob(f"{tracer.dir}/**/*.xplane.pb", recursive=True)[0]
    values = {**run["values"], "peaks": peaks}
    if kind == "serve":
        t = cell.traffic
        values["prefill_flops_per_call"] = t["batch"] * flops.prefill_flops(
            cell.config, t["prompt_lengths"][0])
        traced = [(s, e, n) for s, e, n in run["calls"] if e <= tracer.t_stop - tracer.t_start]
        busy = sum(e - s for s, e, _ in traced)
        traced_rate = sum(n for _, _, n in traced) * t["gen_len"] / busy if busy else None
    else:
        traced_rate = run["values"]["train_tokens_per_s"]
    result = {"workload": cell.name, "seed": seed, "setup_s": run["setup_s"],
              "e2e": run["e2e"], "traced_rate": traced_rate, **reduce_trace(path, hlo, values)}
    if out:
        Path(out).mkdir(parents=True, exist_ok=True)
        shutil.copy(path, Path(out) / f"{cell.name}.xplane.pb")
        for label, text in hlo:
            (Path(out) / f"{cell.name}.{label}.hlo.txt").write_text(text)
    shutil.rmtree(tracer.dir, ignore_errors=True)
    return result


def reduce_trace(path: str, hlo: list, values: dict) -> dict:
    """Both reductions of the trace at ``path`` and the readings of
    :data:`READERS`; ``hlo`` is [(label, compiled HLO text)] of the programs
    the trace ran, ``values`` the driver's values and peaks."""
    events = span_reduce.load_events(path)
    bench_only = {**events, "spans": [s for s in events["spans"] if s[0].startswith("bench:")]}
    base = tr.reduce(bench_only)
    scoped = [{**span_reduce.program_scopes(text), "label": label} for label, text in hlo]
    spans = span_reduce.reduce(events, scoped)
    v = {**values, "trace": base, "spans": spans}
    return {"metrics": {name: read(v) for name, read in READERS.items()},
            "trace": base, "spans": spans}


if __name__ == "__main__":
    raise SystemExit(main())
