"""BENCHMARK.json against the files it names, the generator of inputs, the
operation counts, the peaks table, and the command without a chip."""

import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from chipbench import flops, peaks, spec, traffic  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_contract_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    names = ([c["name"] for c in SPEC["configs"]] + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
    cells = {w["name"] for w in SPEC["workloads"]}
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        reported = [m for m in SPEC["end_to_end"] if w["name"] in m.get("workloads", cells)]
        assert {"setup_s"} < {m["name"] for m in reported}
        assert any(w["name"] in m.get("workloads", cells) for m in SPEC["per_layer"])
    for m in SPEC["per_layer"]:
        moved = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = spec.resolve(cell)
    assert c.config["name"] == next(w for w in SPEC["workloads"] if w["name"] == cell)["config"]
    assert c.traffic["kind"] in ("train", "serve")
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    limits = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())["limits"]
    assert limits and all(v > 0 for v in limits.values())
    spec.arch_config(c.config)
    spec.arch_config(spec.resolve(cell, smoke=True).config)


def test_added_entries_are_found_by_name_alone(tmp_path):
    """A new configuration entry, traffic mix, cell, end-to-end metric and
    per-layer metric: files and entries, no code edit."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip")
    (root / "benchmarks/chip/traffic/chat-long.json").write_text(json.dumps(
        {**spec.traffic_file("chat-burst"), "prompt_lengths": [2048], "length_weights": [1]}))
    (root / "benchmarks/chip/metrics/queue_depth.py").write_text(
        "def read(run):\n    return run.get('queue')\n")
    new = json.loads(json.dumps(SPEC))
    new["configs"].append({"name": "minicpm3-4b", "source": SPEC["configs"][0]["source"],
                           "file": "benchmarks/chip/configs/minicpm3-4b.json",
                           "reduced": [], "why": "the whole model"})
    new["workloads"].append({"name": "minicpm3-4b.chat-long", "config": "minicpm3-4b",
                             "traffic": "chat-long", "chips": 1, "why": "long prompts"})
    new["end_to_end"].append({"name": "serve_latency_p95_s", "unit": "s", "better": "lower",
                              "bound": 0.25, "source": "host_clock",
                              "workloads": ["minicpm3-4b.chat-long"]})
    new["per_layer"].append({"name": "queue_depth", "unit": "requests", "better": "lower",
                             "source": "program_counter", "layer": "batcher",
                             "moves": "serve_latency_p95_s",
                             "workloads": ["minicpm3-4b.chat-long"]})
    old_bench, old_root = spec.BENCH_DIR, spec.ROOT
    spec.BENCH_DIR, spec.ROOT = root / "benchmarks" / "chip", root
    try:
        shutil.copy(ROOT / "BENCHMARK.json", root)
        cell = spec.resolve("minicpm3-4b.chat-long", spec=new)
        reader = spec.metric_reader("queue_depth")
    finally:
        spec.BENCH_DIR, spec.ROOT = old_bench, old_root
    assert cell.config["num_hidden_layers"] == 62
    assert cell.traffic["prompt_lengths"] == [2048]
    assert [m["name"] for m in cell.per_layer] == ["queue_depth"]
    assert {m["name"] for m in cell.end_to_end} == {"serve_latency_p95_s", "setup_s"}
    assert reader({"queue": 3}) == 3


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def _run_cell(cwd: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run_cell.py", "--workload", "minicpm3-4b.train-4k",
         "--seed", "2147483700", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_command_fails_without_a_tpu():
    out = _run_cell(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_command_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    out = _run_cell(tmp_path)
    assert out.returncode != 0 and not out.stdout.strip()


# --------------------------------------------------------------------------
# the generator of inputs
# --------------------------------------------------------------------------

SEEDS = (2**31 + 7, 12345)


@pytest.mark.parametrize("mix", ["chat-burst", "offline-4k"])
def test_requests_are_deterministic_in_the_seed(mix):
    t = spec.traffic_file(mix)
    a = traffic.serve_requests(t, SEEDS[0], 30, 1000)
    b = traffic.serve_requests(t, SEEDS[0], 30, 1000)
    c = traffic.serve_requests(t, SEEDS[1], 30, 1000)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    assert not all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, c))
    # every seed holds the same work: the same lengths and gaps, reordered
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in c)
    if mix == "chat-burst":
        assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in c]
        assert len(a) == round(t["rate"] * 30)
        d = np.diff([r["due"] for r in a])
        assert d.std() / d.mean() == pytest.approx(t["cv"], rel=0.25)


def test_block_shuffle_reorders_within_blocks_only():
    values = np.arange(40.0)
    a = traffic._block_shuffle(values, 0, np.random.default_rng(1), 8)
    b = traffic._block_shuffle(values, 0, np.random.default_rng(2), 8)
    assert sorted(a) == sorted(b) == sorted(values)
    assert not np.array_equal(a, b)
    for start in range(0, 40, 8):
        assert sorted(a[start : start + 8]) == sorted(b[start : start + 8])


def test_gamma_gaps_have_the_stated_rate_and_spread():
    g = traffic.gamma_gaps(4000, 5.0, 2.0)
    assert g.mean() == pytest.approx(0.2)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.1)
    assert (g > 0).all()


def test_length_shares_are_exact():
    counts = traffic._shares([0.4, 0.3, 0.2, 0.1], 120)
    assert counts.tolist() == [48, 36, 24, 12]
    assert traffic._shares([0.4, 0.3, 0.2, 0.1], 7).sum() == 7


def test_train_rows_are_deterministic_and_differ_by_step():
    t = spec.traffic_file("train-4k")
    a = traffic.train_rows(t, SEEDS[0], 0, 73448)
    b = traffic.train_rows(t, SEEDS[0], 0, 73448)
    c = traffic.train_rows(t, SEEDS[0], 1, 73448)
    assert a["tokens"].shape == (t["global_batch"], t["seq"])
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"][0], a["tokens"][1])


# --------------------------------------------------------------------------
# operations and bytes, against hand counts
# --------------------------------------------------------------------------

TINY_GQA = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 2, "intermediate_size": 16,
            "vocab_size": 10, "program": {"attention": "gqa", "dtype": "bfloat16",
                                          "param_dtype": "bfloat16"}}
TINY_MLA = {"hidden_size": 8, "num_hidden_layers": 1, "num_attention_heads": 2,
            "num_key_value_heads": 2, "intermediate_size": 4, "vocab_size": 10,
            "q_lora_rank": 3, "kv_lora_rank": 2, "qk_nope_head_dim": 2,
            "qk_rope_head_dim": 2, "v_head_dim": 1, "program": {"attention": "mla"}}


def test_gqa_counts_by_hand():
    # q 8*4*2=64, k and v 8*2*2=32 each, o 4*2*8=64; mlp 3*8*16=384
    assert flops.layer_matmul_params(TINY_GQA) == 64 + 32 + 32 + 64 + 384
    per_layer = 576
    head = 80
    # train, seq 4: 6 per weight, attention 3 * L * H * (2 + 2) * (4 + 1)
    assert flops.train_flops_per_token(TINY_GQA, 4) == 6 * (2 * per_layer + head) + 3 * 2 * 4 * 4 * 5
    # prefill of 3: 2*L*W*3 + L*H*(dqk+dv)*3*4 + 2*head
    assert flops.prefill_flops(TINY_GQA, 3) == 2 * 2 * per_layer * 3 + 2 * 4 * 4 * 12 + 2 * head
    # decode at position 3 attends to 4 positions
    assert flops.decode_flops(TINY_GQA, 3) == 2 * (2 * per_layer + head) + 2 * 2 * 4 * 4 * 4
    assert flops.request_flops(TINY_GQA, 3, 3) == (
        flops.prefill_flops(TINY_GQA, 3) + flops.decode_flops(TINY_GQA, 3)
        + flops.decode_flops(TINY_GQA, 4))
    # bytes: bf16 weights (layers with their two norms, head, final norm)
    # plus k and v of 2 heads x 2 wide, 2 bytes each, per layer and position
    assert flops.weight_bytes(TINY_GQA) == 2 * (2 * (576 + 16) + 80 + 8)
    assert flops.decode_step_bytes(TINY_GQA, 3, 5.0) == flops.weight_bytes(TINY_GQA) + 3 * 2 * 5 * 16


def test_mla_counts_by_hand():
    # wdq 8*3, wuq 3*2*4, wdkv 8*2, wukv 2*2*3, wkr 8*2, wo 2*1*8, mlp 3*8*4
    assert flops.layer_matmul_params(TINY_MLA) == 24 + 24 + 16 + 12 + 16 + 16 + 96
    assert flops.attn_dims(TINY_MLA) == (2, 4, 1)
    assert flops.kv_bytes_per_position(TINY_MLA) == (2 + 2) * 2
    assert flops.layer_norm_params(TINY_MLA) == 2 * 8 + 3 + 2
    assert math.isclose(flops.train_flops_per_token(TINY_MLA, 1),
                        6 * (204 + 80) + 3 * 1 * 2 * 5 * 2)


# A tiny DeepSeek-V2-style block: MLA with no query compression, one leading
# dense layer, then expert layers with 2 shared experts and 4 of 8 routed
# experts held here, 2 a token.
TINY_MOE = {"hidden_size": 8, "num_hidden_layers": 3, "num_attention_heads": 2,
            "num_key_value_heads": 2, "intermediate_size": 16, "vocab_size": 10,
            "q_lora_rank": None, "kv_lora_rank": 2, "qk_nope_head_dim": 2,
            "qk_rope_head_dim": 2, "v_head_dim": 1, "first_k_dense_replace": 1,
            "n_routed_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 3,
            "n_shared_experts": 2, "published": {"n_routed_experts": 8},
            "program": {"family": "moe", "attention": "mla", "dtype": "bfloat16",
                        "param_dtype": "bfloat16"}}


def test_causal_kernel_work_by_hand():
    # GQA, 4 query heads of 2 over 2 KV heads, bf16; 3 rows of 4 positions:
    # 3 * 4 * (4 * 5 / 2) = 120 scores
    work = flops.causal_kernel_work(TINY_GQA, 3, 4)
    assert work["causal_flash_fwd"][0] == 2 * 120 * (2 + 2)
    assert work["causal_flash_dq"][0] == 2 * 120 * (2 * 2 + 2)
    assert work["causal_flash_dkv"][0] == 2 * 120 * (2 * 2 + 2 * 2)
    # per position of 12: q and o 4*2*2 = 16 bytes, k and v 2*2*2 = 8, a
    # float32 statistic 4*4 = 16
    q, kv, stat = 12 * 16, 12 * 8, 12 * 16
    assert work["causal_flash_fwd"][1] == q + 2 * kv + q + stat
    assert work["causal_flash_dq"][1] == 3 * q + 2 * kv + 2 * stat
    assert work["causal_flash_dkv"][1] == 2 * q + 4 * kv + 2 * stat
    # MLA: a key and a value head per query head, of widths 4 and 1
    mla = flops.causal_kernel_work(TINY_MLA, 1, 2)
    assert mla["causal_flash_dkv"][0] == 2 * (2 * 3) * (2 * 4 + 2 * 1)


def test_mla_without_query_compression_counts_by_hand():
    # wq 8*2*(2+2)=64 straight from the hidden state, wdkv 8*2, wukv 2*2*3,
    # wkr 8*2, wo 2*1*8; no wdq, no query norm
    assert flops.attn_matmul_params(TINY_MOE) == 64 + 16 + 12 + 16 + 16
    assert flops.layer_matmul_params(TINY_MOE) == 124 + 3 * 8 * 16
    assert flops.layer_norm_params(TINY_MOE) == 2 * 8 + 2


def test_moe_operation_counts_by_hand():
    # dense layer 124 + 384 = 508; an expert layer per token: attention 124,
    # router 8*8 = 64 over all 8 experts, shared 2*(3*8*3) = 144, routed
    # 2 * 4/8 = 1 expert of 72 on average: 404
    per_token = 508 + 2 * 404
    assert flops.token_matmul_params(TINY_MOE) == per_token
    head = 80
    # attention as for dense: H 2, qk 4, v 1 in each of the 3 layers
    assert flops.train_flops_per_token(TINY_MOE, 4) == 6 * (per_token + head) + 3 * 3 * 2 * 5 * 5
    assert flops.prefill_flops(TINY_MOE, 3) == 2 * per_token * 3 + 3 * 2 * 5 * 12 + 2 * head
    assert flops.decode_flops(TINY_MOE, 3) == 2 * (per_token + head) + 2 * 3 * 2 * 5 * 4


def test_moe_weight_bytes_by_hand():
    # bf16. Dense layer 508 + norms 18; an expert layer 124 + 64 + 144 + 18
    # = 350 and the held experts reached: one row reaches 4*(1 - 6/8) = 1 of
    # 72, two rows 4*(1 - (6/8)^2) = 1.75, every held one 4; head 80, final norm 8
    assert flops.weight_bytes(TINY_MOE, 1) == 2 * (526 + 2 * (350 + 72) + 80 + 8)
    assert flops.weight_bytes(TINY_MOE, 2) == 2 * (526 + 2 * (350 + 1.75 * 72) + 88)
    assert flops.weight_bytes(TINY_MOE) == 2 * (526 + 2 * (350 + 4 * 72) + 88)
    # cache: kv_lora 2 + rope 2, bf16, in 3 layers, 5 positions of 2 rows
    assert flops.decode_step_bytes(TINY_MOE, 2, 5.0) == 2 * (526 + 2 * (350 + 126) + 88) + 2 * 3 * 5 * 8


def test_moe_without_a_published_count_holds_the_whole_layer():
    whole = {k: v for k, v in TINY_MOE.items() if k != "published"}
    # router 8*4 = 32; routed 2 * 4/4 = 2 experts of 72 a token
    assert flops.token_matmul_params(whole) == 508 + 2 * (124 + 32 + 144 + 144)
    # every row reaches 4 * (1 - (1 - 2/4)^1) = 2 held experts
    assert flops.weight_bytes(whole, 1) == 2 * (526 + 2 * (124 + 32 + 144 + 18 + 144) + 88)


def test_arch_config_maps_the_expert_keys_and_drops_nulls():
    arch = spec.arch_config({**TINY_MOE, "name": "tiny-moe", "head_dim": None,
                             "rope_theta": 10000.0})
    assert (arch.family, arch.attention) == ("moe", "mla")
    assert (arch.n_experts, arch.top_k, arch.moe_d_ff) == (4, 2, 3)
    assert (arch.n_shared_experts, arch.n_dense_layers) == (2, 1)
    assert arch.q_lora_rank == 0 and arch.head_dim == 0
    assert (arch.kv_lora_rank, arch.qk_nope_dim, arch.qk_rope_dim, arch.v_head_dim) == (2, 2, 2, 1)
