"""The program's spans and scopes in a profiler trace: one clock for host and
device, idle time by innermost span, device time by named scope, and the
readers of the metrics they feed."""

import random
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import trace_cell  # noqa: E402
from chipbench import span_reduce as sr  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "trace.xplane.pb"
MS = 1e6  # ns


def _pairs(runs):
    launch, done = dict(runs["launch"]), dict(runs["done"])
    return [(launch[k] - s, done[k] - e) for k, s, e in runs["device"]]


def test_offset_on_the_recorded_trace_lies_inside_every_run_bound():
    ev = sr.load_events(str(RECORDED))
    pairs = _pairs(ev["runs"])
    assert len(pairs) == 3
    offset, bound, n = sr.clock_offset(ev["runs"])
    assert n == 3 and bound > 0
    assert all(lo <= offset <= hi for lo, hi in pairs)
    # the device's clock reads 1.19-1.67 ms behind the host's on that chip
    assert 1.19 * MS < offset < 1.67 * MS


def test_trace_reduce_keeps_its_values_on_the_recorded_trace():
    """The keys the accepted metrics read, pinned to what the reduction gave
    before the program had spans; the loader that keeps ``nbi:`` spans reads
    the same devices, modules and ``bench:`` spans."""
    want = {"window_s": 0.065633154, "busy_s": 0.000361783, "idle_share": 0.9944878010890654,
            "collective_exposed_s": 0.0, "devices": 1,
            "device_ops": [["jit__lambda/fusion", 0.000181844],
                           ["jit__lambda/convolution_tanh_fusion", 0.000179906],
                           ["jit__lambda/copy-start", 2.7e-08], ["jit__lambda/copy-done", 6e-09]],
            "idle_gaps": [["wait", 0.065271371]]}
    old = tr.load_events(str(RECORDED))
    assert tr.reduce(old) == pytest.approx(want)
    new = sr.load_events(str(RECORDED))
    assert {k: new[k] for k in old} == old


def test_offset_where_runs_conflict_or_none_is_linked():
    runs = {"device": [("a", 0, 1 * MS), ("b", 10 * MS, 11 * MS)],
            "launch": [("a", 2 * MS), ("b", 11 * MS)],
            "done": [("a", 3 * MS), ("b", 12 * MS)]}
    # a bounds [2, 2] ms, b [1, 1] ms: they conflict by 1 ms
    offset, bound, n = sr.clock_offset(runs)
    assert (offset, bound, n) == (1.5 * MS, -1 * MS, 2)
    assert sr.clock_offset({"device": [("a", 0, 1)], "launch": [], "done": []}) == (0.0, None, 0)


def test_innermost_is_the_brute_force_rule():
    rng = random.Random(7)
    spans = []
    for i in range(300):
        s = rng.uniform(0, 100)
        spans.append((f"s{i}", s, s + rng.expovariate(0.2)))
    points = sorted(rng.uniform(0, 110) for _ in range(500))

    def brute(p):
        cover = [(e - s, n) for n, s, e in spans if s <= p <= e]
        return min(cover)[1] if cover else None

    assert sr.innermost(spans, points) == [brute(p) for p in points]


def _decode_events(device_lag_ms: float):
    """One decode span 0-10 ms holding a step span 2-3 ms and a fetch span
    5-6 ms. On the host's clock the device idles 2.2-2.8 ms (under the step),
    5.2-5.8 ms (under the fetch) and 7-8 ms (under the decode span alone).
    The device's clock reads ``device_lag_ms`` behind, and one linked run
    pins that lag."""
    lag = device_lag_ms * MS
    host_busy = [(0, 2.2 * MS), (2.8 * MS, 5.2 * MS), (5.8 * MS, 7 * MS), (8 * MS, 10 * MS)]
    ops = [(f"%fusion.{i} = f32[] fusion()", s - lag, e - lag) for i, (s, e) in enumerate(host_busy)]
    spans = [("bench:window", 0, 10 * MS), ("nbi:serve.decode", 0, 10 * MS),
             ("nbi:serve.decode_step", 2 * MS, 3 * MS), ("nbi:serve.token_fetch", 5 * MS, 6 * MS)]
    runs = {"device": [((0, 1), 0.5 * MS - lag, 1.0 * MS - lag)],
            "launch": [((0, 1), 0.5 * MS)], "done": [((0, 1), 1.0 * MS)]}
    return {"devices": {0: ops}, "modules": {}, "spans": spans, "runs": runs}


def test_idle_gaps_land_under_the_innermost_program_span():
    r = sr.reduce(_decode_events(1.0))
    assert r["clock_offset_ms"] == pytest.approx(1.0) and r["clock_bound_ms"] == 0
    assert r["idle_under"] == pytest.approx({"nbi:serve.decode": 0.001,
                                             "nbi:serve.decode_step": 0.0006,
                                             "nbi:serve.token_fetch": 0.0006})
    assert r["idle_inside"] == pytest.approx({"nbi:serve.decode": 0.0022,
                                              "nbi:serve.decode_step": 0.0006,
                                              "nbi:serve.token_fetch": 0.0006})
    assert r["span_stats"]["nbi:serve.decode_step"] == {"count": 1, "s": pytest.approx(0.001)}


def test_a_1ms_shift_moves_a_short_gap_to_the_right_span():
    ev = _decode_events(1.0)
    # without the offset the 0.6 ms gap sits at 1.2-1.8 ms, before the step span
    unshifted = dict(ev, runs={"device": [], "launch": [], "done": []})
    assert "nbi:serve.decode_step" not in sr.reduce(unshifted)["idle_under"]
    assert sr.reduce(ev)["idle_under"]["nbi:serve.decode_step"] == pytest.approx(0.0006)


HLO = """HloModule jit_wrapped, entry_computation_layout={()->f32[]}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(wrapped)/while/body/mlp/mul"}
}

%fused_computation.1 (param_0.1: f32[8,16], param_1: f32[8]) -> f32[8,16] {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  %param_1 = f32[8]{0} parameter(1)
  %add.2 = f32[8,16]{1,0} add(%param_0.1, %param_0.1), metadata={op_name="jit(wrapped)/while/body/decode_attention/kv_cache_write/add"}
  ROOT %dynamic-update-slice.3 = f32[8,16]{1,0} dynamic-update-slice(%add.2, %param_1), metadata={op_name="jit(wrapped)/while/body/dynamic_update_slice"}
}

%body (p: (f32[8], f32[8,16])) -> (f32[8], f32[8,16]) {
  %p = (f32[8]{0}, f32[8,16]{1,0}) parameter(0)
  %fusion = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(wrapped)/while/body/mlp/mul"}
  %fusion.3 = f32[8,16]{1,0} fusion(%p, %fusion), kind=kLoop, calls=%fused_computation.1
  %dot.4 = f32[8]{0} dot(%fusion, %fusion), metadata={op_name="jit(wrapped)/transpose(jvp(attention))/dot_general"}
  %fusion.10 = f32[8,16]{1,0} fusion(%p, %fusion), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(wrapped)/while/body/decode_attention/dot_general"}
  ROOT %tuple.5 = (f32[8]{0}, f32[8,16]{1,0}) tuple(%dot.4, %fusion.3)
}

ENTRY %main.6 (a: f32[8]) -> f32[] {
  %a = f32[8]{0} parameter(0)
  %while.7 = (f32[8]{0}, f32[8,16]{1,0}) while(%a), body=%body
  ROOT %copy.8 = f32[] copy(%a)
}
"""


def test_scope_of_each_op_from_the_hlo():
    p = sr.program_scopes(HLO)
    assert p["module"] == "jit_wrapped"
    assert p["scope"]["fusion"] == "mlp"
    # no metadata of its own, a root under no scope, the scoped update fused in
    assert p["scope"]["fusion.3"] == "kv_cache_write"
    # a consumer the update is fused into keeps its own scope
    assert p["scope"]["fusion.10"] == "decode_attention"
    assert p["scope"]["dot.4"] == "attention"
    assert p["scope"]["while.7"] is None and p["scope"]["copy.8"] is None
    assert "multiply.1" not in p["scope"]  # fused instructions run inside their fusion
    assert sr.innermost_scope("jit(step)/checkpoint/attention/mlp/dot") == "mlp"
    assert sr.innermost_scope("jit(step)/while/body/add") is None


def test_device_self_time_by_scope_tells_programs_of_one_name_apart():
    """Two programs named ``jit_wrapped`` that both hold a ``fusion.3``: each
    run takes the scopes of the program whose instructions it ran."""
    other = sr.program_scopes(HLO.replace("calls=%fused_computation.1", "calls=%fused_computation")
                              .replace("%dot.4", "%negate.9").replace("dot(", "negate("))
    programs = [dict(sr.program_scopes(HLO), label="decode"), dict(other, label="prefill")]
    op = lambda n, s, e: (f"%{n} = f32[8] op()", s * MS, e * MS)  # noqa: E731
    ops = [op("while.7", 0, 10), op("fusion", 1, 3), op("fusion.3", 3, 7), op("dot.4", 7, 8),
           op("fusion.3", 12, 14), op("negate.9", 14, 15)]
    modules = [("jit_wrapped(1)", 0, 10 * MS), ("jit_wrapped(2)", 11 * MS, 16 * MS)]
    events = {"devices": {0: ops}, "modules": {0: modules},
              "spans": [("bench:window", 0, 16 * MS)]}
    r = sr.reduce(events, programs)
    assert r["scope_self_s"] == pytest.approx({"kv_cache_write": 0.004, "mlp": 0.004,
                                               "-": 0.003, "attention": 0.002})
    assert r["scope_ops"][:2] == [["decode/fusion.3 [kv_cache_write]", pytest.approx(0.004)],
                                  ["decode/while.7 [-]", pytest.approx(0.003)]]
    placed = sr.scope_ops(ops, modules, programs)
    assert [(p, s) for p, s, n, _, _ in placed if n == "fusion.3"] == [
        ("decode", "kv_cache_write"), ("prefill", "mlp")]


def test_scopes_are_the_programs_catalogue():
    import repro.obs.spans as spans

    assert all(f"``{s}``" in spans.__doc__ for s in sr.SCOPES)


def _values(**spans):
    base = {"span_stats": {"nbi:serve.decode_step": {"count": 512, "s": 1.0},
                           "nbi:serve.prefill": {"count": 2, "s": 5.0}},
            "idle_inside": {"nbi:serve.decode": 1.024},
            "scope_self_s": {"kv_cache_write": 3.072, "attention": 2.5}}
    base.update(spans)
    return {"spans": base, "trace": {"busy_s": 10.0}, "chips": 1,
            "peaks": {"bf16_flops_per_s": 200e12}, "prefill_flops_per_call": 250e12}


def test_readers_on_synthetic_values():
    v = _values()
    assert trace_cell.decode_host_gap_ms(v) == pytest.approx(2.0)
    assert trace_cell.decode_cache_write_ms(v) == pytest.approx(6.0)
    assert trace_cell.prefill_mfu(v) == pytest.approx(50.0)
    assert trace_cell.train_attention_share(v) == pytest.approx(25.0)


def test_readers_find_nothing_in_a_trace_without_program_spans():
    """A program without ``nbi:`` spans or scopes: every reader gives None."""
    v = _values(span_stats={"bench:serve_requests": {"count": 2, "s": 16.0}},
                idle_inside={}, scope_self_s={})
    assert all(read(v) is None for read in trace_cell.READERS.values())


def test_trace_cell_on_the_cpu(tmp_path):
    """The offline cell at smoke size: the engine's programs are captured,
    their HLO read back, and the program's spans counted in the window."""
    import jax

    from chipbench.spec import resolve

    cell = resolve("mistral-7b.serve-offline-4k", smoke=True)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = trace_cell.trace_cell(cell, 2**31 + 11, 1.0, jax.devices()[:1], peaks, str(tmp_path))
    stats = r["spans"]["span_stats"]
    calls = stats["bench:serve_requests"]["count"]
    assert calls >= 1
    assert stats["nbi:serve.prefill"]["count"] == calls
    assert stats["nbi:serve.decode_step"]["count"] == calls * cell.traffic["gen_len"]
    names = sorted(p.name for p in tmp_path.iterdir())
    assert names == sorted([f"{cell.name}.xplane.pb", f"{cell.name}.prefill.0.hlo.txt",
                            f"{cell.name}.decode.0.hlo.txt"])
    decode = sr.program_scopes((tmp_path / f"{cell.name}.decode.0.hlo.txt").read_text())
    assert "kv_cache_write" in decode["scope"].values()
