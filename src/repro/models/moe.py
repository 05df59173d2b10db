"""Mixture-of-Experts family (DeepSeek-MoE fine-grained, Kimi-K2 scale).

GShard/MaxText-style capacity-based dispatch: tokens are grouped
(``moe_group_tokens`` per group), routed top-k with a per-expert capacity
``C = ceil(k·N/E · capacity_factor)``, dispatched to experts with one-hot
dispatch/combine einsums, and the expert dim is sharded over the ``model``
mesh axis (expert parallelism — GSPMD materialises the all-to-all).

Shared experts (DeepSeek's "2 shared + 64 routed") run densely for every
token. Leading ``n_dense_layers`` use an ordinary dense MLP (DeepSeek/Kimi
put a dense layer first for routing stability).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import ParamDef, cross_entropy, rms_norm, shard, swiglu
from .config import ArchConfig
from .transformer import (
    _stack,
    attn_defs,
    block_defs,
    dense_block,
    embed_tokens,
    gqa_decode_attn,
    mlp_defs,
    remat_wrap,
    unembed,
    write_cache,
)

# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def moe_ffn_defs(cfg: ArchConfig, pdt) -> dict:
    D, E, Fm = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    defs = {
        "router": ParamDef((D, E), ("embed", None), pdt, scale=0.1),
        "wg": ParamDef((E, D, Fm), ("experts", "embed", None), pdt),
        "wi": ParamDef((E, D, Fm), ("experts", "embed", None), pdt),
        "wo": ParamDef((E, Fm, D), ("experts", None, "embed"), pdt),
    }
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * cfg.moe_d_ff
        defs["shared"] = {
            "wg": ParamDef((D, Fs), ("embed", "ff"), pdt),
            "wi": ParamDef((D, Fs), ("embed", "ff"), pdt),
            "wo": ParamDef((Fs, D), ("ff", "embed"), pdt),
        }
    return defs


def moe_block_defs(cfg: ArchConfig, pdt) -> dict:
    D = cfg.d_model
    return {
        "ln1": ParamDef((D,), (None,), pdt, "ones"),
        "attn": attn_defs(cfg, pdt),
        "ln2": ParamDef((D,), (None,), pdt, "ones"),
        "moe": moe_ffn_defs(cfg, pdt),
    }


def moe_param_defs(cfg: ArchConfig) -> dict:
    pdt = jnp.dtype(cfg.param_dtype)
    V, D = cfg.vocab_size, cfg.d_model
    n_moe = cfg.n_layers - cfg.n_dense_layers
    is_def = lambda x: isinstance(x, ParamDef)
    defs = {
        "embed": ParamDef((V, D), ("vocab", "embed"), pdt),
        "moe_blocks": jax.tree_util.tree_map(
            lambda d: _stack(n_moe, d), moe_block_defs(cfg, pdt), is_leaf=is_def
        ),
        "final_ln": ParamDef((D,), (None,), pdt, "ones"),
        "unembed": ParamDef((D, V), ("embed", "vocab"), pdt),
    }
    if cfg.n_dense_layers:
        defs["dense_blocks"] = jax.tree_util.tree_map(
            lambda d: _stack(cfg.n_dense_layers, d), block_defs(cfg, pdt), is_leaf=is_def
        )
    return defs


# ---------------------------------------------------------------------------
# Routing + dispatch
# ---------------------------------------------------------------------------


def capacity(cfg: ArchConfig, tokens_per_group: int) -> int:
    c = math.ceil(cfg.top_k * tokens_per_group / cfg.n_experts * cfg.capacity_factor)
    return max(4, int(c))


def top_k_routing(logits, cfg: ArchConfig, cap: int):
    """GShard top-k with per-slot positions. logits: (G, N, E) f32.

    Returns dispatch (G,N,E,C) bool-as-dtype, combine (G,N,E,C) f32,
    aux load-balance loss (scalar).
    """
    G, N, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.top_k)  # (G,N,k)
    # DeepSeek normalises the selected gates to sum to 1.
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    counts = jnp.zeros((G, E), jnp.int32)
    dispatch = jnp.zeros((G, N, E, cap), jnp.bool_)
    combine = jnp.zeros((G, N, E, cap), jnp.float32)
    for j in range(cfg.top_k):  # k is small and static — unrolled
        mask_j = jax.nn.one_hot(gate_idx[..., j], E, dtype=jnp.int32)  # (G,N,E)
        pos_j = counts[:, None, :] + jnp.cumsum(mask_j, axis=1) - mask_j
        keep = (pos_j < cap) & (mask_j > 0)  # (G,N,E)
        pos_oh = jax.nn.one_hot(pos_j, cap, dtype=jnp.bool_) & keep[..., None]
        dispatch = dispatch | pos_oh
        combine = combine + pos_oh * gate_vals[..., j, None, None]
        counts = counts + mask_j.sum(axis=1)

    # load-balance auxiliary loss (Switch/GShard): E * Σ_e f_e · p_e
    f = dispatch.any(-1).astype(jnp.float32).mean(axis=1)  # (G,E) fraction routed
    p = probs.mean(axis=1)  # (G,E) mean router prob
    aux = E * jnp.mean(jnp.sum(f * p, axis=-1))
    return dispatch, combine, aux


def moe_ffn(p, x, cfg: ArchConfig):
    """x: (B, S, D) → (B, S, D), plus aux loss."""
    dt = jnp.dtype(cfg.dtype)
    B, S, D = x.shape
    N = min(cfg.moe_group_tokens, B * S)
    G = (B * S) // N
    assert (B * S) % N == 0, (B, S, N)
    cap = capacity(cfg, N)

    xg = x.reshape(G, N, D)
    xg = shard(xg, "batch", None, None)
    logits = jnp.einsum(
        "gnd,de->gne", xg, p["router"].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    dispatch, combine, aux = top_k_routing(logits, cfg, cap)

    # dispatch → (E, G, C, D): expert dim sharded over `model` (EP all-to-all)
    expert_in = jnp.einsum("gnec,gnd->egcd", dispatch.astype(dt), xg)
    expert_in = shard(expert_in, "experts", "batch", None, None)
    g = jnp.einsum("egcd,edf->egcf", expert_in, p["wg"].astype(dt))
    h = jnp.einsum("egcd,edf->egcf", expert_in, p["wi"].astype(dt))
    h = jax.nn.silu(g) * h
    expert_out = jnp.einsum("egcf,efd->egcd", h, p["wo"].astype(dt))
    expert_out = shard(expert_out, "experts", "batch", None, None)
    y = jnp.einsum("gnec,egcd->gnd", combine.astype(dt), expert_out)
    y = y.reshape(B, S, D)

    if cfg.n_shared_experts:
        sh = p["shared"]
        y = y + swiglu(x, sh["wg"], sh["wi"], sh["wo"], dt)
    return shard(y, "batch", None, None), aux


def moe_block(p, carry, cfg: ArchConfig, positions):
    x, aux_acc = carry
    from .transformer import gqa_attention, mla_attention

    attn_fn = mla_attention if cfg.attention == "mla" else gqa_attention
    x = x + attn_fn(p["attn"], rms_norm(x, p["ln1"]), cfg, positions)
    y, aux = moe_ffn(p["moe"], rms_norm(x, p["ln2"]), cfg)
    return x + y, aux_acc + aux


# ---------------------------------------------------------------------------
# Forward / loss / decode
# ---------------------------------------------------------------------------


def moe_forward(params, cfg: ArchConfig, tokens):
    h = embed_tokens(params, cfg, tokens)
    S = h.shape[1]
    positions = jnp.arange(S)
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.n_dense_layers:
        from .transformer import run_stack

        h = run_stack(
            params["dense_blocks"], h, cfg,
            lambda p, y: dense_block(p, y, cfg, positions),
        )

    def body(carry, layer_params):
        return moe_block(layer_params, carry, cfg, positions), None

    body = remat_wrap(body, cfg)
    (h, aux_total), _ = jax.lax.scan(body, (h, aux_total), params["moe_blocks"])
    h = rms_norm(h, params["final_ln"])
    return unembed(params, cfg, h), aux_total


def moe_loss(params, cfg: ArchConfig, batch):
    logits, aux = moe_forward(params, cfg, batch["tokens"])
    loss, metrics = cross_entropy(logits, batch["labels"], z_loss=cfg.z_loss)
    n_moe = cfg.n_layers - cfg.n_dense_layers
    aux_mean = aux / max(1, n_moe)
    metrics["aux_loss"] = aux_mean
    return loss + cfg.router_aux_weight * aux_mean, metrics


def moe_prefill(params, cfg: ArchConfig, tokens):
    """Prefill with KV-cache collection (attention KV only; MoE is stateless)."""
    from .transformer import gqa_attention, run_stack

    h = embed_tokens(params, cfg, tokens)
    S = h.shape[1]
    positions = jnp.arange(S)
    dt = jnp.dtype(cfg.dtype)
    cache = {}

    if cfg.n_dense_layers:

        def dense_body(h, p):
            y, kv = gqa_attention(p["attn"], rms_norm(h, p["ln1"]), cfg, positions, collect=True)
            h = h + y
            m = p["mlp"]
            h = h + swiglu(rms_norm(h, p["ln2"]), m["wg"], m["wi"], m["wo"], dt)
            return h, kv

        h, cache["dense"] = jax.lax.scan(
            remat_wrap(dense_body, cfg), h, params["dense_blocks"]
        )

    def moe_body(h, p):
        y, kv = gqa_attention(p["attn"], rms_norm(h, p["ln1"]), cfg, positions, collect=True)
        h = h + y
        y2, _aux = moe_ffn(p["moe"], rms_norm(h, p["ln2"]), cfg)
        return h + y2, kv

    h, cache["moe"] = jax.lax.scan(remat_wrap(moe_body, cfg), h, params["moe_blocks"])
    h = rms_norm(h[:, -1:], params["final_ln"])
    return unembed(params, cfg, h), cache


def moe_decode_ffn(p, x, cfg: ArchConfig):
    """Decode-time MoE: one group over the (tiny) token batch.

    Reuses the training dispatch math with G=1, N=B·S — per-expert capacity
    is then ``ceil(k·B/E·cf)`` so expert compute stays O(B·k·D·F), not
    O(B·E·D·F). The group dim (size 1) is left unsharded; the token dim is
    sharded over the batch axes instead.
    """
    dt = jnp.dtype(cfg.dtype)
    B, S, D = x.shape
    N = B * S
    cap = capacity(cfg, N)
    xg = x.reshape(1, N, D)
    xg = shard(xg, None, "batch", None)
    logits = jnp.einsum(
        "gnd,de->gne", xg, p["router"].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    dispatch, combine, _aux = top_k_routing(logits, cfg, cap)
    expert_in = jnp.einsum("gnec,gnd->egcd", dispatch.astype(dt), xg)
    expert_in = shard(expert_in, "experts", None, None, None)
    g = jnp.einsum("egcd,edf->egcf", expert_in, p["wg"].astype(dt))
    h = jnp.einsum("egcd,edf->egcf", expert_in, p["wi"].astype(dt))
    h = jax.nn.silu(g) * h
    expert_out = jnp.einsum("egcf,efd->egcd", h, p["wo"].astype(dt))
    expert_out = shard(expert_out, "experts", None, None, None)
    y = jnp.einsum("gnec,egcd->gnd", combine.astype(dt), expert_out).reshape(B, S, D)
    if cfg.n_shared_experts:
        sh = p["shared"]
        y = y + swiglu(x, sh["wg"], sh["wi"], sh["wo"], dt)
    return y


def moe_cache_defs(cfg: ArchConfig, batch: int, max_seq: int) -> dict:
    from .transformer import dense_cache_defs

    L, K = cfg.n_layers, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    out = {
        "moe": {
            "k": jax.ShapeDtypeStruct(
                (cfg.n_layers - cfg.n_dense_layers, batch, K, max_seq, hd), dt
            ),
            "v": jax.ShapeDtypeStruct(
                (cfg.n_layers - cfg.n_dense_layers, batch, K, max_seq, hd), dt
            ),
        }
    }
    if cfg.n_dense_layers:
        out["dense"] = {
            "k": jax.ShapeDtypeStruct((cfg.n_dense_layers, batch, K, max_seq, hd), dt),
            "v": jax.ShapeDtypeStruct((cfg.n_dense_layers, batch, K, max_seq, hd), dt),
        }
    return out


def moe_cache_logical(cfg: ArchConfig) -> dict:
    leaf = {"k": ("layers", "batch", None, "kv_seq", None),
            "v": ("layers", "batch", None, "kv_seq", None)}
    out = {"moe": dict(leaf)}
    if cfg.n_dense_layers:
        out["dense"] = dict(leaf)
    return out


def moe_decode_step(params, cfg: ArchConfig, cache, tokens, pos):
    """One decode step; each layer scan yields its layers' new tokens, and
    one write after each scan puts them into the cache (:func:`write_cache`)."""
    h = embed_tokens(params, cfg, tokens)
    dt = jnp.dtype(cfg.dtype)

    new_cache = {}
    if cfg.n_dense_layers:

        def dense_body(h, inp):
            p, c = inp
            y, new = gqa_decode_attn(p["attn"], c, rms_norm(h, p["ln1"]), cfg, pos)
            h = h + y
            m = p["mlp"]
            h = h + swiglu(rms_norm(h, p["ln2"]), m["wg"], m["wi"], m["wo"], dt)
            return h, new

        h, new = jax.lax.scan(dense_body, h, (params["dense_blocks"], cache["dense"]))
        new_cache["dense"] = write_cache(cache["dense"], new, pos)

    def moe_body(h, inp):
        p, c = inp
        y, new = gqa_decode_attn(p["attn"], c, rms_norm(h, p["ln1"]), cfg, pos)
        h = h + y
        h = h + moe_decode_ffn(p["moe"], rms_norm(h, p["ln2"]), cfg)
        return h, new

    h, new = jax.lax.scan(moe_body, h, (params["moe_blocks"], cache["moe"]))
    new_cache["moe"] = write_cache(cache["moe"], new, pos)
    h = rms_norm(h, params["final_ln"])
    return unembed(params, cfg, h), new_cache
