"""What ``BENCHMARK.json`` names, found on disk by name.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
The configuration's file is the one its ``configs`` entry gives; the traffic
mix is ``traffic/<traffic>.json``; each per-layer metric is read by
``metrics/<metric>.py``; the plain reference is the module under
``reference/`` that the configuration file's ``reference`` key names. Adding a
configuration, a traffic mix, a metric or a reference is adding its file and
its entry: nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

from . import reference

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
SPEC_FILE = ROOT / "BENCHMARK.json"

# Published (Hugging Face) key -> ArchConfig field.
_HF_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "tie_word_embeddings": "tie_embeddings",
    "attn_chunk": "attn_chunk",
    "n_routed_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "moe_d_ff",
    "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "n_dense_layers",
}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file, smoke overrides applied if asked
    traffic: dict  # the traffic file, smoke overrides applied if asked
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def load_spec(path: Path = SPEC_FILE) -> dict:
    return json.loads(Path(path).read_text())


def _with_smoke(data: dict, smoke: bool) -> dict:
    data = json.loads(json.dumps(data))
    over = data.pop("smoke", {})
    if smoke:
        program = over.pop("program", {})
        data.update(over)
        data.setdefault("program", {}).update(program)
    return data


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, *, spec: dict | None = None, smoke: bool = False) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics. Raises
    ``reference.MissingReference`` where the configuration's reference
    module is missing."""
    spec = spec or load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_file = ROOT / configs[w["config"]]["file"]
    config = _with_smoke(json.loads(cfg_file.read_text()), smoke)
    reference.load(config)
    traffic = _with_smoke(traffic_file(w["traffic"]), smoke)
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
    )


def traffic_file(name: str) -> dict:
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def metric_reader(name: str):
    """``read(run) -> float | None`` from ``metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file. A key whose
    value is null (``head_dim``, a ``q_lora_rank`` with no query compression)
    is left out, so the field's default holds."""
    from repro.models.config import ArchConfig

    fields = {field: config[key] for key, field in _HF_KEYS.items()
              if config.get(key) is not None}
    fields.update(config.get("program", {}))
    return ArchConfig(name=config["name"], **fields)
