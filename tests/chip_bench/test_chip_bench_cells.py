"""Each cell end to end at smoke size on the CPU, through the harness's own
functions past its look for a chip; and the control (the plain reference at
fp8 in the program's place), which has to fail the cell's limits.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from chipbench import harness, serve_driver, spec, train_driver  # noqa: E402
from chipbench.spec import Cell, resolve  # noqa: E402

TRAIN, OFFLINE = "minicpm3-4b.train-4k", "mistral-7b.serve-offline-4k"
SEED = 2**31 + 101


def _execute(name: str, seconds: float = 0.5, trace: bool = False, **kw):
    import jax

    cell = resolve(name, smoke=True)
    devices = jax.devices()[: cell.chips]
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    return harness.execute(cell, SEED, seconds, trace, devices, info, **kw)


def _assert_result_shape(result: dict, metrics: set):
    assert list(result)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert set(result["metrics"]) == metrics
    assert all(v["value"] > 0 for v in result["metrics"].values())
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("name,metric", [(TRAIN, "train_tokens_per_s"),
                                         (OFFLINE, "serve_tokens_per_s")])
def test_cell_runs_correct_at_smoke_size(name, metric):
    result = _execute(name)
    _assert_result_shape(result, {metric, "setup_s"})
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_a_traced_run_reads_the_program_counters(tmp_path):
    """The registry is on for a traced run alone: the attention path the
    program counts while it traces its programs reaches the readers'
    values, and the registry is off again afterwards."""
    from repro.obs import metrics

    result = _execute(OFFLINE, trace=True, keep=str(tmp_path),
                      peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert result["correct"], result["checks"]
    values = json.loads((tmp_path / f"{OFFLINE}.values.json").read_text())
    paths = values["counters"]["nbi_attention_path_total"]
    assert paths.get("path=chunked", 0) > 0  # the CPU takes no fused kernel
    assert metrics.get_registry() is metrics.NULL_REGISTRY


@pytest.mark.parametrize("name,labels", [(TRAIN, ["train_step"]),
                                         (OFFLINE, ["prefill", "decode"])])
def test_a_traced_run_notes_its_programs_in_set_up_alone(monkeypatch, name, labels):
    """The programs whose HLO is read after the window are noted before it
    opens; the window calls them as an untraced run does."""
    from chipbench import programs

    seen, note, start = [], programs.Noted.note, harness.Tracer.start

    def noting(self, label, jitted, args):
        seen.append((label, bool(opened)))
        note(self, label, jitted, args)

    def starting(self):
        opened.append(True)
        start(self)

    opened: list = []
    monkeypatch.setattr(programs.Noted, "note", noting)
    monkeypatch.setattr(harness.Tracer, "start", starting)
    assert _execute(name, trace=True,
                    peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})["correct"]
    assert opened and sorted(dict.fromkeys(x for x, _ in seen)) == sorted(labels)
    assert all(not was_open for _, was_open in seen)


def test_an_untraced_run_turns_nothing_on(monkeypatch):
    """No registry enabled and no program noted for its HLO."""
    from chipbench import programs
    from repro.obs import metrics

    seen = []
    monkeypatch.setattr(metrics, "enable", lambda *a, **kw: seen.append("enable"))
    monkeypatch.setattr(programs.Noted, "note", lambda *a, **kw: seen.append("program"))
    for name in (TRAIN, OFFLINE):
        assert _execute(name)["correct"]
    assert seen == []


def test_open_loop_traffic_is_served_at_smoke_size():
    """Gamma arrivals of several prompt lengths, on the whole MLA model's
    configuration: every request due in the window is served, and the served
    tokens are the reference's."""
    import jax

    cfg = spec._with_smoke(json.loads((BENCH / "configs" / "minicpm3-4b.json").read_text()), True)
    t = spec._with_smoke(spec.traffic_file("chat-burst"), True)
    cell = Cell(name="chat", chips=1, config=cfg, traffic=t, end_to_end=[], per_layer=[])
    run = serve_driver.run(cell, SEED, 0.5, jax.devices()[:1])
    assert run["attempted"] > 1 and run["failed"] == 0
    assert len({len(p) for p, _ in run["served"].values()}) > 1
    ids = serve_driver.sample(run["served"], t, SEED)
    assert serve_driver.gaps(cfg, run["served"], ids, SEED)["logit_gap"] < 1e-3


def test_train_control_fails_the_limits():
    cell = resolve(TRAIN, smoke=True)
    limits = harness.limits_for(TRAIN)
    ref = train_driver.reference_readings(cell.config, cell.traffic, SEED)
    low = train_driver.reference_readings(cell.config, cell.traffic, SEED, numerics="fp8")
    numbers = train_driver.compare(low, ref)
    assert any(numbers[k] > limits[k] for k in limits), numbers


def test_reference_in_row_blocks_reads_as_one_pass():
    """A global batch taken one chip's share at a time gives the readings of
    one pass, so a cell whose batch several chips share can check on one."""
    cell = resolve(TRAIN, smoke=True)
    whole = train_driver.reference_readings(cell.config, cell.traffic, SEED)
    rows = train_driver.reference_readings(cell.config, cell.traffic, SEED, chips=2)
    np.testing.assert_allclose(rows["losses"], whole["losses"], rtol=1e-6)
    for key in ("grad_norms", "change_norms"):
        assert rows[key].keys() == whole[key].keys()
        np.testing.assert_allclose([rows[key][k] for k in whole[key]],
                                   list(whole[key].values()), rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("name", [OFFLINE])
def test_serve_control_fails_the_limit(name):
    """fp8 rounding errors grow with depth and width, and the smoke model's
    two layers of 64 hide them (gaps under 0.5): the control is read on 16
    layers of 256, where the cell's limit separates it on both attention
    kinds and a CPU run still takes seconds."""
    cell = resolve(name, smoke=True)
    cfg = {**cell.config, "num_hidden_layers": 16, "hidden_size": 256}
    rng = np.random.default_rng(3)
    t = cell.traffic
    served = {}
    for i in range(4):
        prompt = rng.integers(0, cfg["vocab_size"], t["prompt_lengths"][-1], np.int32)
        served[i] = (prompt, np.zeros(t["gen_len"], np.int32))
    numbers = serve_driver.gaps(cfg, served, list(served), SEED, control=True)
    assert numbers["control_logit_gap"] > harness.limits_for(name)["logit_gap"], numbers
