"""Each configuration names its plain reference, and the harness uses that
module and no other: ``spec.resolve`` refuses a configuration whose reference
is missing, and the drivers' reference readings go through the named module.
"""

import json
import math
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from chipbench import reference, serve_driver, spec, train_driver  # noqa: E402
from chipbench.reference import decoder  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
TRAIN, OFFLINE = "minicpm3-4b.train-4k", "mistral-7b.serve-offline-4k"
SEED = 2**31 + 23
INTERFACE = ("init_weights", "leaf_paths", "draw_leaf", "leaf_norms", "change_norms",
             "loss_and_grads", "adamw_step", "logits")


@pytest.mark.parametrize("smoke", [False, True])
@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_each_configuration_names_a_reference_with_its_interface(cell, smoke):
    """The module a configuration names loads, and gives every function the
    drivers call for the cell's kind of traffic."""
    c = spec.resolve(cell, smoke=smoke)
    module = reference.load(c.config)
    assert module.__name__ == f"chipbench.reference.{c.config['reference']}"
    missing = [f for f in reference.INTERFACE[c.traffic["kind"]]
               if not callable(getattr(module, f, None))]
    assert not missing, f"{module.__name__} lacks {missing}"


def test_a_new_architecture_is_added_as_new_files(tmp_path):
    """A copy of the benchmark that adds a configuration naming a reference
    module of its own (a re-export of ``decoder``), its cell, its limits and
    its names in the metrics' ``workloads``: new files and appended entries
    alone. The copy's own tests of the contract, of each cell's files and of
    each reference's interface pass, the new cell's cases among them."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__", ".jax_cache"))
    bench = tmp_path / "benchmarks" / "chip"
    (bench / "chipbench" / "reference" / "stubref.py").write_text(
        '"""A stand-in architecture\'s reference."""\n\n'
        "from .decoder import *  # noqa: F401,F403\n")
    train = next(w for w in SPEC["workloads"] if w["name"] == TRAIN)
    base = next(c for c in SPEC["configs"] if c["name"] == train["config"])
    config = json.loads((ROOT / base["file"]).read_text())
    (bench / "configs" / "stub.json").write_text(
        json.dumps({**config, "name": "stub", "reference": "stubref"}))
    shutil.copy(bench / "workloads" / f"{TRAIN}.json", bench / "workloads" / "stub.train-4k.json")
    new = json.loads(json.dumps(SPEC))
    new["configs"].append({**base, "name": "stub", "file": "benchmarks/chip/configs/stub.json"})
    new["workloads"].append({**train, "name": "stub.train-4k", "config": "stub"})
    for m in new["end_to_end"] + new["per_layer"]:
        if TRAIN in m.get("workloads", ()):
            m["workloads"].append("stub.train-4k")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new, indent=1))
    tests = ["tests/chip_bench/test_chip_bench_spec.py::test_contract_shape",
             "tests/chip_bench/test_chip_bench_spec.py::test_every_cell_resolves_to_its_files",
             "tests/chip_bench/test_chip_bench_reference_choice.py::"
             "test_each_configuration_names_a_reference_with_its_interface"]
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider", *tests],
        cwd=tmp_path, capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src")})
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    cases = len(SPEC["workloads"]) + 1
    assert f"{1 + cases + 2 * cases} passed" in out.stdout, out.stdout[-4000:]
    passed = [line for line in out.stdout.splitlines() if line.startswith("PASSED ")]
    for case in ("test_every_cell_resolves_to_its_files[stub.train-4k]",
                 "test_each_configuration_names_a_reference_with_its_interface[stub.train-4k-False]",
                 "test_each_configuration_names_a_reference_with_its_interface[stub.train-4k-True]"):
        assert any(line.endswith(f"::{case}") for line in passed), out.stdout[-4000:]


def _config_file(tmp_path, name: str, **changes) -> dict:
    """A spec whose one configuration is the MLA one with ``changes``
    (a value of None takes the key out), written under ``tmp_path``."""
    config = json.loads((BENCH / "configs" / "minicpm3-4b.8l.json").read_text())
    config["name"] = name
    for k, v in changes.items():
        if v is None:
            config.pop(k)
        else:
            config[k] = v
    (tmp_path / f"{name}.json").write_text(json.dumps(config))
    return {**SPEC, "configs": [{**SPEC["configs"][0], "name": name, "file": f"{name}.json"}],
            "workloads": [{**SPEC["workloads"][0], "config": name}]}


@pytest.mark.parametrize("changes,words", [
    ({"reference": "no_such_model"}, ["'no_such_model'", "chipbench.reference.no_such_model"]),
    ({"reference": None}, ["names no reference module"]),
    ({"reference": "../decoder"}, ["names no reference module"]),
])
def test_a_missing_reference_fails_in_resolve(tmp_path, monkeypatch, changes, words):
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    new = _config_file(tmp_path, "odd-model", **changes)
    with pytest.raises(reference.MissingReference) as err:
        spec.resolve(TRAIN, spec=new)
    assert "'odd-model'" in str(err.value)
    for w in words:
        assert w in str(err.value)


def test_a_missing_reference_stops_the_command_before_any_device_work(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    path = tmp_path / SPEC["configs"][0]["file"]
    path.write_text(json.dumps({**json.loads(path.read_text()), "reference": "no_such_model"}))
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run_cell.py", "--workload", TRAIN, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=240)
    assert out.returncode != 0 and not out.stdout.strip()
    assert "chipbench.reference.no_such_model" in out.stderr
    assert "[bench] platform=" not in out.stderr  # the look for a chip never ran


@pytest.fixture
def stand_in(monkeypatch):
    """A reference module of another name that wraps ``decoder`` and records
    which of its functions are called."""
    calls = []
    module = types.ModuleType("chipbench.reference.stand_in")
    for name in INTERFACE:
        def wrapped(*args, _real=getattr(decoder, name), _name=name, **kw):
            calls.append(_name)
            return _real(*args, **kw)

        setattr(module, name, wrapped)
    monkeypatch.setitem(sys.modules, module.__name__, module)
    return calls


def _named(cell: str, ref: str):
    c = spec.resolve(cell, smoke=True)
    return {**c.config, "reference": ref}, c.traffic


def test_train_readings_go_through_the_named_reference(stand_in):
    cfg, traffic = _named(TRAIN, "stand_in")
    readings = train_driver.reference_readings(cfg, traffic, SEED)
    assert {"init_weights", "loss_and_grads", "leaf_norms", "adamw_step",
            "change_norms"} <= set(stand_in)
    assert len(readings["losses"]) == traffic["checked_steps"]
    assert all(math.isfinite(x) for x in readings["losses"])

    del stand_in[:]
    program = {}
    for index, (path, _, _) in enumerate(decoder.leaf_paths(cfg)):
        decoder.set_path(program, decoder.path_keys(path), decoder.draw_leaf(cfg, SEED, index))
    change = train_driver._change_norms(program, cfg, SEED)
    assert {"leaf_paths", "draw_leaf"} <= set(stand_in)
    assert set(change) == {p for p, _, _ in decoder.leaf_paths(cfg)}
    assert all(v == 0.0 for v in change.values())


def test_serve_gaps_go_through_the_named_reference(stand_in):
    cfg, _ = _named(OFFLINE, "stand_in")
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg["vocab_size"], 12).astype(np.int32)
    out = rng.integers(0, cfg["vocab_size"], 4).astype(np.int32)
    numbers = serve_driver.gaps(cfg, {0: (prompt, out)}, [0], SEED, control=True)
    assert {"init_weights", "logits"} <= set(stand_in)
    assert numbers["_tokens"] == 4 and math.isfinite(numbers["control_logit_gap"])


def _inline_change_norms(params, cfg, seed):
    """The walk ``reference_readings`` made inline before
    ``decoder.change_norms`` took it over: the oracle."""
    import jax.numpy as jnp

    ref = decoder
    change = {}
    for index, (path, _, _) in enumerate(ref.leaf_paths(cfg)):
        start = ref.draw_leaf(cfg, seed, index)
        keys = ref.path_keys(path)
        if keys[0] == "blocks":
            sq = sum(float(jnp.sum(jnp.square(ref.get_path(lay, keys[1:]).astype(jnp.float32)
                                              - start[i].astype(jnp.float32))))
                     for i, lay in enumerate(params["layers"]))
        else:
            sq = float(jnp.sum(jnp.square(params[keys[0]].astype(jnp.float32)
                                          - start.astype(jnp.float32))))
        change[path] = math.sqrt(sq)
    return change


@pytest.mark.parametrize("cell", [TRAIN, OFFLINE])
def test_decoder_change_norms_match_the_inline_walk(cell):
    import jax
    import jax.numpy as jnp

    cfg = spec.resolve(cell, smoke=True).config
    start = decoder.init_weights(cfg, SEED)
    moved = jax.tree_util.tree_map(
        lambda a: a + 1e-3 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)).reshape(a.shape)
        .astype(a.dtype), start)
    got = decoder.change_norms(moved, cfg, SEED)
    assert got == _inline_change_norms(moved, cfg, SEED)
    assert all(v > 0 for v in got.values())
    assert all(v == 0.0 for v in decoder.change_norms(start, cfg, SEED).values())
