"""The program's spans and scopes in a profiler trace: one clock for host and
device, idle time by innermost span, device time by named scope and by
operation, and the readers of the metrics they feed."""

import json
import random
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from chipbench import flops, harness, spec  # noqa: E402
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
    placed, _ = sr.scope_ops(ops, modules, programs)
    assert [(p, s) for p, s, n, _, _, _ in placed if n == "fusion.3"] == [
        ("decode", "kv_cache_write"), ("prefill", "mlp")]


def test_scopes_are_the_programs_catalogue():
    import repro.obs.spans as spans

    assert all(f"``{s}``" in spans.__doc__ for s in sr.SCOPES)


def _two_programs(hlo: str = HLO, window=None):
    """The events of the test above: a decode program's run 0-10 ms and a
    prefill program's 11-16 ms, both named ``jit_wrapped``."""
    other = sr.program_scopes(hlo.replace("calls=%fused_computation.1", "calls=%fused_computation")
                              .replace("%dot.4", "%negate.9").replace("dot(", "negate("))
    programs = [dict(sr.program_scopes(hlo), label="decode"), dict(other, label="prefill")]
    op = lambda n, s, e: (f"%{n} = f32[8] op()", s * MS, e * MS)  # noqa: E731
    ops = [op("while.7", 0, 10), op("fusion", 1, 3), op("fusion.3", 3, 7), op("dot.4", 7, 8),
           op("fusion.3", 12, 14), op("negate.9", 14, 15)]
    modules = [("jit_wrapped(1)", 0, 10 * MS), ("jit_wrapped(2)", 11 * MS, 16 * MS)]
    events = {"devices": {0: ops}, "modules": {0: modules},
              "spans": [("bench:window", 0, 16 * MS)]}
    return sr.reduce(events, programs, window=window)


def test_scope_path_names_every_scope_through_transforms():
    assert sr.scope_path("jit(step)/transpose(jvp(mlp))/experts/dot_general") == (
        "step", "mlp", "experts")
    assert sr.scope_path("jit(f)/attention/causal_flash_fwd/pallas_call") == (
        "f", "attention", "causal_flash_fwd")
    assert sr.scope_path(None) == () and sr.scope_path("add") == ()
    p = sr.program_scopes(HLO)
    assert p["path"]["fusion"] == ("wrapped", "while", "body", "mlp")
    # read by the fused instruction under kv_cache_write, as its scope is
    assert p["path"]["fusion.3"] == ("wrapped", "while", "body", "decode_attention",
                                     "kv_cache_write")
    assert p["path"]["while.7"] == ()


def test_inclusive_scope_time_reads_a_scope_no_list_names():
    """A scope the catalogue does not hold (``experts``, inside ``mlp``) is
    read by its name, and leaves the ``mlp`` reading as it was."""
    plain = _two_programs()
    nested = _two_programs(HLO.replace("/mlp/mul", "/mlp/experts/mul"))
    assert "experts" not in sr.SCOPES and "experts" not in plain["scope_incl_s"]
    assert nested["scope_incl_s"]["experts"] == pytest.approx(0.004)
    for r in (plain, nested):
        assert r["scope_incl_s"]["mlp"] == pytest.approx(0.004)
        assert r["scope_incl_s"]["kv_cache_write"] == pytest.approx(0.004)
        # the cache write lies inside decode_attention, nested scopes included
        assert r["scope_incl_s"]["decode_attention"] == pytest.approx(0.004)
        assert r["scope_incl_s"]["attention"] == pytest.approx(0.002)
        assert r["scope_incl_s"]["wrapped"] == pytest.approx(0.010)
    assert nested["scope_self_s"] == plain["scope_self_s"]


@pytest.mark.parametrize("window,calls,runs", [
    (None, {"fusion.3": 2.0, "negate.9": 1.0}, {"decode": 1.0, "prefill": 1.0}),
    # a window to 14.5 ms cuts the prefill run (11-16 ms) and its negate.9 (14-15 ms)
    ((0, 14.5 * MS), {"fusion.3": 2.0, "negate.9": 0.5}, {"decode": 1.0, "prefill": 0.7}),
])
def test_every_operation_and_program_run_is_counted(window, calls, runs):
    r = _two_programs(window=window)
    assert r["op_calls"] == pytest.approx({"while.7": 1.0, "fusion": 1.0, "dot.4": 1.0, **calls})
    assert r["program_runs"] == pytest.approx(runs)
    # device seconds of each program's runs: the prefill run is 5 ms, cut to 3.5
    assert r["program_s"] == pytest.approx({"decode": 0.010,
                                            "prefill": 0.005 * runs["prefill"]})
    assert r["op_self_s"]["fusion.3"] == pytest.approx(0.006)
    assert r["op_self_s"]["negate.9"] == pytest.approx(calls["negate.9"] * 0.001)
    assert r["op_self_s"]["while.7"] == pytest.approx(0.003)


def _nested(rng, s, e, depth, out):
    """Intervals nested inside (s, e) at random, each with the length of
    the intervals directly inside it, by the plain rule."""
    cuts = sorted(rng.uniform(s, e) for _ in range(2 * rng.randint(0, 3 if depth < 3 else 0)))
    i = len(out)
    out.append([s, e, e - s])
    for a, b in zip(cuts[::2], cuts[1::2]):
        out[i][2] -= b - a
        _nested(rng, a, b, depth + 1, out)


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_self_each_is_the_plain_rule_on_nested_intervals(seed):
    rng = random.Random(seed)
    want = []
    for k in range(20):
        _nested(rng, 100.0 * k, 100.0 * k + rng.uniform(1, 90), 0, want)
    order = list(range(len(want)))
    rng.shuffle(order)
    got = tr.self_each([(want[i][0], want[i][1]) for i in order])
    assert got == pytest.approx([want[i][2] for i in order])
    named = [(f"%op{i % 7}", want[i][0], want[i][1]) for i in order]
    sums = {}
    for (n, _, _), t in zip(named, got):
        sums[n[1:]] = sums.get(n[1:], 0.0) + t
    assert dict(tr.self_times(named)) == pytest.approx(sums)


# the driver's values that the accepted readers take besides the trace
DRIVER = {"flops_per_token": 4.7e9, "train_tokens_per_s": 17900.0, "chips": 1,
          "useful_flops_per_s": 3.4e13, "decode_step_s": 0.023, "decode_step_flops": 6.7e10,
          "decode_step_bytes": 9.5e9, "one_prompt_length": True}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ACCEPTED = ["train_mfu", "device_idle_share.train", "serve_mfu.offline", "decode_roofline",
            "decode_step_ms", "device_idle_share.offline"]


@pytest.mark.parametrize("name", ACCEPTED)
def test_readers_keep_their_values_under_the_new_loading(name):
    """The harness loads the trace once with ``span_reduce``'s loader and
    gives ``trace_reduce`` the ``bench:`` spans alone: on the recorded trace
    its values, and what each accepted reader reads from them, are those of
    the loader the harness used before."""
    old = tr.reduce(tr.load_events(str(RECORDED)))
    tracer = harness.Tracer()
    shutil.copy(RECORDED, Path(tracer.dir) / "trace.xplane.pb")
    new, spans = tracer.reduce()
    assert not Path(tracer.dir).exists()
    assert new == old
    assert spans["span_stats"]["bench:step"]["count"] == 3
    read = spec.metric_reader(name)
    before = read({**DRIVER, "trace": old, "peaks": PEAKS})
    assert before is not None
    assert read({**DRIVER, "trace": new, "spans": spans, "counters": {}, "peaks": PEAKS}) == before


def _values(**spans):
    base = {"span_stats": {"nbi:serve.decode_step": {"count": 512, "s": 1.0},
                           "nbi:serve.prefill": {"count": 2, "s": 5.0}},
            "idle_inside": {"nbi:serve.decode": 1.024},
            "scope_incl_s": {"kv_cache_write": 3.072, "attention": 2.5},
            "program_runs": {"train_step": 25.0, "prefill": 2.0},
            "program_s": {"train_step": 11.4, "prefill": 4.0},
            "op_self_s": {"causal_flash_fwd.17": 0.5, "causal_flash_fwd.18": 0.5,
                          "transpose_jvp_causal_flash_dq__.3": 1.0, "causal_flash_dkv.11": 1.0,
                          "fusion.3": 9.0},
            "op_calls": {"causal_flash_fwd.17": 100.0, "causal_flash_fwd.18": 100.0,
                         "transpose_jvp_causal_flash_dq__.3": 100.0,
                         "causal_flash_dkv.11": 100.0, "fusion.3": 100.0}}
    base.update(spans)
    # a call of fwd needs 1 ms at the peak, dq 4 ms of bytes at HBM bandwidth, dkv 2 ms
    work = {"causal_flash_fwd": [197e9, 1.0], "causal_flash_dq": [1.0, 3.276e9],
            "causal_flash_dkv": [394e9, 1.0]}
    return {"spans": base, "trace": {"busy_s": 10.0}, "chips": 1, "peaks": PEAKS,
            "prefill_flops_per_call": 250e12, "kernel_work": work,
            "counters": {"nbi_attention_path_total": {"path=fused": 3.0, "path=chunked": 1.0}}}


NEW = {"decode_host_gap_ms": 2.0, "decode_cache_write_ms": 6.0,
       # the prefill program's device time, not its host spans' 5 s
       "prefill_mfu.offline": 100.0 * (250e12 * 2 / 4.0) / 197e12,
       "train_attention_ms": 100.0, "attention_fused_share.train": 75.0,
       # 200 fwd calls of 1 ms, 100 dq of 4 ms and 100 dkv of 2 ms in 3 s
       "causal_flash_roofline": 100.0 * (0.2 + 0.4 + 0.2) / 3.0,
       "causal_flash_roofline.offline": 100.0 * (0.2 + 0.4 + 0.2) / 3.0}


def test_readers_on_synthetic_values():
    v = _values()
    assert {name: spec.metric_reader(name)(v) for name in NEW} == pytest.approx(NEW)
    only_fwd = _values(op_self_s={"causal_flash_fwd.5": 0.25},
                       op_calls={"causal_flash_fwd.5": 100.0})
    assert spec.metric_reader("causal_flash_roofline.offline")(only_fwd) == pytest.approx(40.0)


def test_readers_find_nothing_in_a_trace_without_program_spans():
    """A program without ``nbi:`` spans, scopes, kernels or counters: every
    reader gives None."""
    v = _values(span_stats={"bench:serve_requests": {"count": 2, "s": 16.0}},
                idle_inside={}, scope_incl_s={}, program_runs={"jit_wrapped": 2.0},
                program_s={"jit_wrapped": 4.0},
                op_self_s={"fusion.3": 9.0}, op_calls={"fusion.3": 100.0})
    v["counters"] = {}
    assert {name: spec.metric_reader(name)(v) for name in NEW} == dict.fromkeys(NEW)


def test_trace_cell_on_the_cpu(tmp_path, monkeypatch, capsys):
    """The offline cell at smoke size through ``trace_cell.py`` on the CPU:
    one traced run of the harness with no check, the engine's programs
    noted, their HLO read back, and the program's spans counted in the
    window."""
    import jax

    import trace_cell

    devices = jax.devices()[:1]
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": 1}
    monkeypatch.setattr(trace_cell, "resolve", lambda name: spec.resolve(name, smoke=True))
    monkeypatch.setattr(harness, "on_chip", lambda cell: (devices, info, PEAKS))
    cell = spec.resolve("mistral-7b.serve-offline-4k", smoke=True)
    assert trace_cell.main(["--workload", cell.name, "--seed", str(2**31 + 11), "--seconds", "1",
                            "--out", str(tmp_path)]) == 0
    r = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert r["correct"] is None and r["checks"] == {}
    values = json.loads((tmp_path / f"{cell.name}.values.json").read_text())
    stats = values["spans"]["span_stats"]
    calls = stats["bench:serve_requests"]["count"]
    assert calls >= 1
    assert stats["nbi:serve.prefill"]["count"] == calls
    assert stats["nbi:serve.decode_step"]["count"] == calls * cell.traffic["gen_len"]
    names = sorted(p.name for p in tmp_path.iterdir())
    assert names == sorted(["trace.xplane.pb", f"{cell.name}.values.json",
                            f"{cell.name}.0.prefill.hlo.txt", f"{cell.name}.1.decode.hlo.txt"])
    decode = sr.program_scopes((tmp_path / f"{cell.name}.1.decode.hlo.txt").read_text())
    assert "kv_cache_write" in decode["scope"].values()
    assert values["prefill_flops_per_call"] == cell.traffic["batch"] * flops.prefill_flops(
        cell.config, cell.traffic["prompt_lengths"][0])
