"""Operations and bytes a model needs, from its configuration's shapes.

Counts are of the model, not of the program: a matrix product of an (m, k)
by a (k, n) operand is 2·m·k·n operations; causal attention counts only the
positions at or before each query; recomputation (remat) and padding (the
padded vocabulary, filler rows) are not counted. The head counts the real
vocabulary. Keys are the configuration file's (Hugging Face) names.

Mixture of experts (``n_routed_experts`` set): the first
``first_k_dense_replace`` layers are dense, with ``intermediate_size``; every
later layer has its attention, a router over all ``E`` experts (``E`` is
``published.n_routed_experts`` where the chip holds a share, else
``n_routed_experts``), ``n_shared_experts`` shared experts and routed
experts of ``moe_intermediate_size``. The chip holds ``n_routed_experts`` of
them, and a token brings it ``num_experts_per_tok`` x held / ``E`` expert
MLPs on average. A decode step of ``rows`` tokens reads the held experts
they are expected to reach under uniform routing. MLA with a null
``q_lora_rank`` projects its query straight from the hidden state and has
no query norm.

A kernel's call is counted apart (:func:`causal_kernel_work`): the work of
one call at its shapes, whatever blocks it computes in.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _mla(cfg: dict) -> bool:
    return cfg.get("program", {}).get("attention") == "mla"


def attn_matmul_params(cfg: dict) -> int:
    """Weights of one layer's attention that enter a matrix product."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    if _mla(cfg):
        rq, rkv = cfg.get("q_lora_rank"), cfg["kv_lora_rank"]
        nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
        query = D * rq + rq * H * (nope + rope) if rq else D * H * (nope + rope)
        return query + D * rkv + rkv * H * (nope + dv) + D * rope + H * dv * D
    K = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // H
    return D * H * hd + 2 * D * K * hd + H * hd * D


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one dense layer that enter a matrix product."""
    return attn_matmul_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_norm_params(cfg: dict) -> int:
    D = cfg["hidden_size"]
    return 2 * D + ((cfg.get("q_lora_rank") or 0) + cfg["kv_lora_rank"] if _mla(cfg) else 0)


def _experts(cfg: dict) -> tuple:
    """(experts held, experts routed over, experts per token) of an expert layer."""
    held = cfg["n_routed_experts"]
    return held, cfg.get("published", {}).get("n_routed_experts", held), cfg["num_experts_per_tok"]


def _layer_counts(cfg: dict) -> tuple:
    """(dense layers, expert layers) of the configuration."""
    L = cfg["num_hidden_layers"]
    if not cfg.get("n_routed_experts"):
        return L, 0
    dense = min(L, cfg.get("first_k_dense_replace") or 0)
    return dense, L - dense


def _expert_layer_params(cfg: dict, routed: float) -> float:
    """Matrix weights of one expert layer with ``routed`` routed experts:
    attention, the router and the shared experts, plus those experts."""
    D = cfg["hidden_size"]
    _, E, _ = _experts(cfg)
    expert = 3 * D * cfg["moe_intermediate_size"]
    shared = (cfg.get("n_shared_experts") or 0) * expert
    return attn_matmul_params(cfg) + D * E + shared + routed * expert


def token_matmul_params(cfg: dict) -> float:
    """Matrix weights one token passes through in all the layers (not the
    head): every dense layer's, and of each expert layer the routed experts'
    expected share on this chip."""
    dense, moe = _layer_counts(cfg)
    total = dense * layer_matmul_params(cfg)
    if moe:
        held, E, k = _experts(cfg)
        total += moe * _expert_layer_params(cfg, k * held / E)
    return total


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def attn_dims(cfg: dict) -> tuple:
    """(heads, query-key width, value width) of the attention core."""
    H = cfg["num_attention_heads"]
    if _mla(cfg):
        return H, cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    return H, hd, hd


def kv_bytes_per_position(cfg: dict) -> int:
    """Cache bytes one position of one row holds in one layer."""
    b = _BYTES[cfg.get("program", {}).get("dtype", "bfloat16")]
    if _mla(cfg):
        return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * b
    H = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    return 2 * cfg["num_key_value_heads"] * hd * b


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward: 6 per matmul weight, plus causal attention."""
    L = cfg["num_hidden_layers"]
    H, dqk, dv = attn_dims(cfg)
    matmul = 6 * (token_matmul_params(cfg) + head_params(cfg))
    attention = 3 * L * H * (dqk + dv) * (seq + 1)  # fwd S(S+1)/2 scores, x3 for bwd
    return float(matmul + attention)


def prefill_flops(cfg: dict, prompt: int) -> float:
    """One prompt through every layer, and the head at its last position."""
    L = cfg["num_hidden_layers"]
    H, dqk, dv = attn_dims(cfg)
    return float(2 * token_matmul_params(cfg) * prompt
                 + L * H * (dqk + dv) * prompt * (prompt + 1)
                 + 2 * head_params(cfg))


def decode_flops(cfg: dict, position: int) -> float:
    """One new token at ``position`` (0-based), attending to position + 1."""
    L = cfg["num_hidden_layers"]
    H, dqk, dv = attn_dims(cfg)
    return float(2 * (token_matmul_params(cfg) + head_params(cfg))
                 + 2 * L * H * (dqk + dv) * (position + 1))


def request_flops(cfg: dict, prompt: int, new_tokens: int) -> float:
    """Prefill gives the first new token; each later one is a decode step."""
    return prefill_flops(cfg, prompt) + sum(
        decode_flops(cfg, prompt + i) for i in range(new_tokens - 1))


def weight_bytes(cfg: dict, rows: int | None = None):
    """Weights a decode step of ``rows`` tokens reads: every layer and the
    head (real vocab); of each expert layer's held experts, those the rows
    are expected to reach under uniform routing, all of them where ``rows``
    is None."""
    b = _BYTES[cfg.get("program", {}).get("param_dtype", "bfloat16")]
    dense, moe = _layer_counts(cfg)
    total = dense * (layer_matmul_params(cfg) + layer_norm_params(cfg))
    if moe:
        held, E, k = _experts(cfg)
        reached = held if rows is None else held * (1 - (1 - k / E) ** rows)
        total += moe * (_expert_layer_params(cfg, reached) + layer_norm_params(cfg))
    return b * (total + head_params(cfg) + cfg["hidden_size"])


def decode_step_bytes(cfg: dict, rows: int, filled: float) -> float:
    """Weights plus ``filled`` cache positions of each of ``rows`` rows."""
    L = cfg["num_hidden_layers"]
    return float(weight_bytes(cfg, rows) + rows * L * filled * kv_bytes_per_position(cfg))


# Multiply-adds per causal score of one call of each fused causal attention
# kernel, as (times the query-key width, times the value width): the forward
# pass forms q.k and p.v; dq recomputes q.k, forms do.v and ds.k; dkv
# recomputes q.k, forms do.v, p.do and ds.q.
CAUSAL_KERNELS = {"causal_flash_fwd": (1, 1), "causal_flash_dq": (2, 1),
                  "causal_flash_dkv": (2, 2)}


def causal_kernel_work(cfg: dict, rows: int, seq: int) -> dict:
    """{kernel: [operations, bytes]} of one call of each kernel of
    :data:`CAUSAL_KERNELS` over one layer's attention of ``rows`` rows of
    ``seq`` positions. Operations: 2 per multiply-add, over the S(S+1)/2
    scores at or before each query of every head and row; the kernels
    compute whole blocks on the diagonal, so this is never more than a call
    does. Bytes: the least a call moves, each operand read and each result
    written once (MLA's key and value have a head per query head; the
    softmax statistics are float32)."""
    H, dqk, dv = attn_dims(cfg)
    K = H if _mla(cfg) else cfg["num_key_value_heads"]
    b = _BYTES[cfg.get("program", {}).get("dtype", "bfloat16")]
    scores = rows * H * seq * (seq + 1) // 2
    pos = rows * seq
    q, o, k, v, stat = (pos * H * dqk * b, pos * H * dv * b, pos * K * dqk * b,
                        pos * K * dv * b, pos * H * 4)
    moved = {"causal_flash_fwd": q + k + v + o + stat,  # o and the log-sum-exp out
             "causal_flash_dq": q + k + v + o + 2 * stat + q,  # do, lse, di in; dq out
             "causal_flash_dkv": q + k + v + o + 2 * stat + k + v}  # dk, dv out
    return {name: [float(2 * scores * (a * dqk + c * dv)), float(moved[name])]
            for name, (a, c) in CAUSAL_KERNELS.items()}
