"""Dense decoder-only transformer family.

Covers codeqwen1.5-7b, starcoder2-7b, mistral-large-123b (GQA), minicpm3-4b
(MLA — multi-head latent attention with a compressed KV cache and the
absorbed-matmul decode path) and llava-next-mistral-7b (visual-prefix stub).

Layout conventions
------------------
* Per-layer weights are stacked on a leading ``layers`` axis and executed via
  ``lax.scan`` (+ optional ``jax.checkpoint``) — HLO size is depth-independent.
* Projection weights are shaped (D, H, hd) so tensor parallelism is a logical
  axis annotation on the ``heads`` dim.
* KV caches are laid out (L, B, Hkv, S, hd) with the *sequence* dim sharded
  over the ``model`` axis at decode time (flash-decoding split-KV; see
  DESIGN.md) — mandatory for 32k×128 caches on 16 GB chips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import (
    ParamDef,
    apply_rope,
    attention_chunked,
    attention_path,
    attention_single_shot,
    axis_devices,
    causal_mask,
    cross_entropy,
    fused_causal_attention,
    rms_norm,
    scoped,
    shard,
    swiglu,
)
from .config import ArchConfig

# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def _stack(n, d: ParamDef) -> ParamDef:
    return ParamDef(
        shape=(n, *d.shape),
        logical=("layers", *d.logical),
        dtype=d.dtype,
        init=d.init,
        scale=d.scale,
    )


def attn_defs(cfg: ArchConfig, pdt) -> dict:
    D, H, K = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    if cfg.attention == "mla":
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        return {
            "wdq": ParamDef((D, cfg.q_lora_rank), ("embed", None), pdt),
            "q_ln": ParamDef((cfg.q_lora_rank,), (None,), pdt, "ones"),
            "wuq": ParamDef((cfg.q_lora_rank, H, qk), (None, "heads", None), pdt),
            "wdkv": ParamDef((D, cfg.kv_lora_rank), ("embed", None), pdt),
            "kv_ln": ParamDef((cfg.kv_lora_rank,), (None,), pdt, "ones"),
            "wukv": ParamDef(
                (cfg.kv_lora_rank, H, cfg.qk_nope_dim + cfg.v_head_dim),
                (None, "heads", None), pdt,
            ),
            "wkr": ParamDef((D, cfg.qk_rope_dim), ("embed", None), pdt),
            "wo": ParamDef((H, cfg.v_head_dim, D), ("heads", None, "embed"), pdt),
        }
    return {
        "wq": ParamDef((D, H, hd), ("embed", "heads", None), pdt),
        "wk": ParamDef((D, K, hd), ("embed", "kv_heads", None), pdt),
        "wv": ParamDef((D, K, hd), ("embed", "kv_heads", None), pdt),
        "wo": ParamDef((H, hd, D), ("heads", None, "embed"), pdt),
    }


def mlp_defs(cfg: ArchConfig, pdt, d_ff=None) -> dict:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wg": ParamDef((D, F), ("embed", "ff"), pdt),
        "wi": ParamDef((D, F), ("embed", "ff"), pdt),
        "wo": ParamDef((F, D), ("ff", "embed"), pdt),
    }


def block_defs(cfg: ArchConfig, pdt) -> dict:
    D = cfg.d_model
    return {
        "ln1": ParamDef((D,), (None,), pdt, "ones"),
        "attn": attn_defs(cfg, pdt),
        "ln2": ParamDef((D,), (None,), pdt, "ones"),
        "mlp": mlp_defs(cfg, pdt),
    }


def dense_param_defs(cfg: ArchConfig) -> dict:
    pdt = jnp.dtype(cfg.param_dtype)
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab_size
    defs = {
        "embed": ParamDef((V, D), ("vocab", "embed"), pdt),
        "blocks": jax.tree_util.tree_map(
            lambda d: _stack(L, d), block_defs(cfg, pdt), is_leaf=lambda x: isinstance(x, ParamDef)
        ),
        "final_ln": ParamDef((D,), (None,), pdt, "ones"),
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((D, V), ("embed", "vocab"), pdt)
    return defs


# ---------------------------------------------------------------------------
# Attention (full-sequence / training path)
# ---------------------------------------------------------------------------


@scoped("attention")
def gqa_attention(p, x, cfg: ArchConfig, positions, collect: bool = False):
    dt = jnp.dtype(cfg.dtype)
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"].astype(dt))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "heads", None, None)
    k = shard(k, "batch", "kv_heads", None, None)
    v = shard(v, "batch", "kv_heads", None, None)
    k_compact, v_compact = k, v  # cache keeps the Hkv layout
    window = cfg.window if cfg.attention == "local" else 0
    path = attention_path(q, k, window=window, logit_cap=cfg.logit_cap,
                          fallback="pallas" if cfg.use_pallas else "chunked")
    if path == "fused":
        # the fused kernel maps query heads to KV heads in its index maps
        out = fused_causal_attention(q, k, v)
    elif path == "pallas":
        # Pallas flash kernel handles GQA in its index map (no KV expansion).
        from repro.kernels import ops as kops

        out = kops.attention(
            q, k, v,
            causal=True,
            window=window,
            logit_cap=cfg.logit_cap,
            kv_chunk=cfg.attn_chunk,
            use_pallas=True,
        )
    else:
        # Expand KV heads to Hq for the full-sequence path: with few KV heads
        # (e.g. 8 on a 16-way model axis) the grouped (Hkv, G) reshape would
        # not shard — the expanded Hq dim does. The decode path keeps the
        # grouped form and shards the KV *sequence* dim instead.
        G = cfg.n_heads // cfg.n_kv_heads
        if G > 1:
            k = jnp.repeat(k, G, axis=1)
            v = jnp.repeat(v, G, axis=1)
            k = shard(k, "batch", "heads", None, None)
            v = shard(v, "batch", "heads", None, None)
        out = attention_chunked(
            q, k, v,
            causal=True,
            window=window,
            kv_chunk=cfg.attn_chunk,
            logit_cap=cfg.logit_cap,
        )
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"].astype(dt))
    if collect:
        return y, {"k": k_compact, "v": v_compact}
    return y


@scoped("attention")
def mla_attention(p, x, cfg: ArchConfig, positions, collect: bool = False):
    """Training-path MLA: expand latent projections to per-head q/k/v."""
    dt = jnp.dtype(cfg.dtype)
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wdq"].astype(dt)), p["q_ln"])
    q = jnp.einsum("bsr,rhk->bhsk", cq, p["wuq"].astype(dt))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    ckv = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wdkv"].astype(dt)), p["kv_ln"])
    kv = jnp.einsum("bsr,rhk->bhsk", ckv, p["wukv"].astype(dt))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_rope = jnp.einsum("bsd,dk->bsk", x, p["wkr"].astype(dt))[:, None]  # shared head
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:-1], rope))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    q = shard(q, "batch", "heads", None, None)
    k = shard(k, "batch", "heads", None, None)
    if attention_path(q, k) == "fused":
        out = fused_causal_attention(q, k, v, kv_heads="heads")
    else:
        out = attention_chunked(q, k, v, causal=True, kv_chunk=cfg.attn_chunk)
    y = jnp.einsum("bhsv,hvd->bsd", out, p["wo"].astype(dt))
    if collect:
        # compressed MLA cache: the latent ckv + shared roped k_rope
        return y, {"ckv": ckv, "krope": k_rope[:, 0]}
    return y


def dense_block(p, x, cfg: ArchConfig, positions):
    # Residual-stream constraint: ("batch", "seq", None). With seq_shard ON
    # (sequence parallelism) the "seq" rule maps to the model axis — norms
    # and residual elementwise run 1/TP-sized, and GSPMD turns each TP
    # region's all-reduce into reduce-scatter + all-gather (Megatron-SP).
    attn_fn = mla_attention if cfg.attention == "mla" else gqa_attention
    x = x + attn_fn(p["attn"], rms_norm(x, p["ln1"]), cfg, positions)
    x = shard(x, "batch", "seq", None)
    dt = jnp.dtype(cfg.dtype)
    m = p["mlp"]
    x = x + swiglu(rms_norm(x, p["ln2"]), m["wg"], m["wi"], m["wo"], dt)
    return shard(x, "batch", "seq", None)


# ---------------------------------------------------------------------------
# Layer-stack execution (shared across families)
# ---------------------------------------------------------------------------


def remat_wrap(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "selective":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def run_stack(blocks, x, cfg: ArchConfig, apply_block):
    """scan the layer stack (or unroll when cfg.use_scan=False)."""

    def body(h, layer_params):
        return apply_block(layer_params, h), None

    body = remat_wrap(body, cfg)
    if cfg.use_scan:
        x, _ = jax.lax.scan(body, x, blocks)
        return x
    n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    for i in range(n):
        layer = jax.tree_util.tree_map(lambda a: a[i], blocks)
        x, _ = body(x, layer)
    return x


# ---------------------------------------------------------------------------
# Training forward
# ---------------------------------------------------------------------------


@scoped("embed")
def embed_tokens(params, cfg: ArchConfig, tokens):
    dt = jnp.dtype(cfg.dtype)
    h = jnp.take(params["embed"].astype(dt), tokens, axis=0)
    return shard(h, "batch", "seq", None)


@scoped("lm_head")
def unembed(params, cfg: ArchConfig, h):
    dt = jnp.dtype(cfg.dtype)
    table = (
        params["embed"].astype(dt).T
        if cfg.tie_embeddings
        else params["unembed"].astype(dt)
    )
    logits = jnp.einsum("bsd,dv->bsv", h, table)
    return shard(logits, "batch", None, "vocab")


def dense_forward(params, cfg: ArchConfig, tokens, patches=None):
    """tokens: (B, S_text) int32; patches: (B, P, D) visual-prefix stub."""
    h = embed_tokens(params, cfg, tokens)
    if patches is not None:
        h = jnp.concatenate([patches.astype(h.dtype), h], axis=1)
        h = shard(h, "batch", None, None)
    S = h.shape[1]
    positions = jnp.arange(S)
    h = run_stack(
        params["blocks"], h, cfg, lambda p, y: dense_block(p, y, cfg, positions)
    )
    h = rms_norm(h, params["final_ln"])
    return unembed(params, cfg, h)


def dense_loss(params, cfg: ArchConfig, batch):
    logits = dense_forward(
        params, cfg, batch["tokens"], patches=batch.get("patches")
    )
    loss, metrics = cross_entropy(logits, batch["labels"], z_loss=cfg.z_loss)
    return loss, metrics


def dense_prefill(params, cfg: ArchConfig, tokens, patches=None):
    """Inference prefill: full-sequence forward that also materialises the
    per-layer KV cache (compressed latent cache for MLA). Returns
    (last-position logits, cache)."""
    h = embed_tokens(params, cfg, tokens)
    if patches is not None:
        h = jnp.concatenate([patches.astype(h.dtype), h], axis=1)
        h = shard(h, "batch", None, None)
    S = h.shape[1]
    positions = jnp.arange(S)
    attn_fn = mla_attention if cfg.attention == "mla" else gqa_attention

    def body(h, p):
        y, kv = attn_fn(p["attn"], rms_norm(h, p["ln1"]), cfg, positions, collect=True)
        h = h + y
        m = p["mlp"]
        dt = jnp.dtype(cfg.dtype)
        h = h + swiglu(rms_norm(h, p["ln2"]), m["wg"], m["wi"], m["wo"], dt)
        return h, kv

    h, cache = jax.lax.scan(remat_wrap(body, cfg), h, params["blocks"])
    h = rms_norm(h[:, -1:], params["final_ln"])
    return unembed(params, cfg, h), cache


# ---------------------------------------------------------------------------
# Decoding (KV cache; GQA standard path + MLA compressed-latent path)
# ---------------------------------------------------------------------------


def dense_cache_defs(cfg: ArchConfig, batch: int, max_seq: int) -> dict:
    """Abstract cache layout for (de)serialisation and the dry-run."""
    L, K = cfg.n_layers, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    if cfg.attention == "mla":
        return {
            "ckv": jax.ShapeDtypeStruct((L, batch, max_seq, cfg.kv_lora_rank), dt),
            "krope": jax.ShapeDtypeStruct((L, batch, max_seq, cfg.qk_rope_dim), dt),
        }
    return {
        "k": jax.ShapeDtypeStruct((L, batch, K, max_seq, hd), dt),
        "v": jax.ShapeDtypeStruct((L, batch, K, max_seq, hd), dt),
    }


def cache_logical(cfg: ArchConfig) -> dict:
    """Logical axes for each cache leaf (sequence sharded over `model`)."""
    if cfg.attention == "mla":
        return {
            "ckv": ("layers", "batch", "kv_seq", None),
            "krope": ("layers", "batch", "kv_seq", None),
        }
    return {
        "k": ("layers", "batch", None, "kv_seq", None),
        "v": ("layers", "batch", None, "kv_seq", None),
    }


@scoped("kv_cache_write")
def scatter_seq(buf, update, pos):
    """Write `update` (..., 1, d) into `buf` (..., S, d) at index `pos`.

    One-hot multiply-add instead of dynamic_update_slice: elementwise →
    GSPMD-shardable when S is sharded over the `model` axis. It reads and
    writes all of ``buf``, so :func:`write_cache` takes it only there.

    ``pos`` may be a scalar (whole batch at one position) or a (B,) vector
    (continuous batching: every slot at its own depth); vector positions
    assume ``buf``'s leading dim is the batch.
    """
    S = buf.shape[-2]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        onehot = (jnp.arange(S) == pos).astype(buf.dtype)[..., None]  # (S,1)
    else:
        B = buf.shape[0]
        oh = (jnp.arange(S)[None, :] == pos[:, None]).astype(buf.dtype)  # (B,S)
        onehot = oh.reshape((B,) + (1,) * (buf.ndim - 3) + (S, 1))
    return buf * (1 - onehot) + update.astype(buf.dtype) * onehot


@scoped("kv_cache_write")
def _write_seq(buf, update, pos):
    """``update`` (L, B, ..., 1, d) into ``buf`` (L, B, ..., S, d) at sequence
    position ``pos``, in place where ``buf`` is donated: one
    dynamic_update_slice for a scalar ``pos``, one a row for a (B,) ``pos``."""
    update = update.astype(buf.dtype)
    pos = jnp.asarray(pos)
    seq = buf.ndim - 2
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(buf, update, pos, axis=seq)
    # one slice update a row: XLA on the TPU gives a vmapped update or a
    # scatter its own layout of the cache, and copies the whole cache twice
    for b in range(pos.shape[0]):
        start = [0] * buf.ndim
        start[1], start[seq] = b, pos[b]
        buf = jax.lax.dynamic_update_slice(buf, update[:, b:b + 1], start)
    return buf


def write_cache(cache, new, pos):
    """The decode step's one cache write, after the layer scan: each leaf of
    ``new`` holds every layer's new token (L, B, ..., 1, d) and goes into the
    stacked ``cache`` (L, B, ..., S, d) at ``pos`` (scalar or (B,)).

    Where the rules split ``kv_seq`` over more than one device, the one-hot
    blend (:func:`scatter_seq`, layer by layer), which GSPMD partitions
    elementwise."""
    if axis_devices("kv_seq") > 1:
        write = jax.vmap(lambda b, u: scatter_seq(b, u, pos))
    else:
        write = functools.partial(_write_seq, pos=pos)
    return jax.tree_util.tree_map(write, cache, new)


def _pos_rope(pos, batch: int):
    """Positions for RoPE at decode: scalar → (1,); vector → (B,1,1) so the
    angle tensor broadcasts against (B, H, 1, dh/2)."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return jnp.full((1,), pos)
    return jnp.broadcast_to(pos, (batch,))[:, None, None]


def _seen_mask(pos, batch: int, skv: int, window: int = 0):
    """(B,1,1,1,S) mask of the cache positions a decode query at ``pos``
    (scalar or (B,)) attends besides its own token: those before ``pos``
    and, with a local ``window``, after ``pos - window``."""
    pos = jnp.broadcast_to(jnp.asarray(pos), (batch,))[:, None, None, None, None]
    k_pos = jnp.arange(skv)[None, None, None, None, :]
    mask = k_pos < pos
    return mask & (k_pos > pos - window) if window > 0 else mask


@scoped("decode_attention")
def gqa_decode_attn(p, layer_cache, x, cfg: ArchConfig, pos):
    """One-token attention against the cache and the new token; ``pos``
    scalar or (B,). The cache is read, not written: returns (y, the new
    token's {"k", "v"} (B, Hkv, 1, hd) in the cache dtype)."""
    dt = jnp.dtype(cfg.dtype)
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(dt))
    k_new = jnp.einsum("bsd,dhk->bhsk", x, p["wk"].astype(dt))
    v_new = jnp.einsum("bsd,dhk->bhsk", x, p["wv"].astype(dt))
    positions = _pos_rope(pos, B)
    q = apply_rope(q, positions, cfg.rope_theta)
    k_new = apply_rope(k_new, positions, cfg.rope_theta).astype(layer_cache["k"].dtype)
    v_new = v_new.astype(layer_cache["v"].dtype)
    k = shard(layer_cache["k"], "batch", None, "kv_seq", None)
    v = shard(layer_cache["v"], "batch", None, "kv_seq", None)
    window = cfg.window if cfg.attention == "local" else 0
    mask = _seen_mask(pos, B, k.shape[-2], window)
    out = attention_single_shot(q, k, v, mask=mask, logit_cap=cfg.logit_cap,
                                new_kv=(k_new, v_new))
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"].astype(dt))
    return y, {"k": k_new, "v": v_new}


@scoped("decode_attention")
def mla_decode_attn(p, layer_cache, x, cfg: ArchConfig, pos):
    """Absorbed-matmul MLA decode over the compressed (ckv, k_rope) cache and
    the new token, which joins the softmax in score space.

    ``pos`` scalar or (B,) (continuous batching). Returns (y, the new token's
    {"ckv": (B, 1, r), "krope": (B, 1, rope)} in the cache dtype)."""
    dt = jnp.dtype(cfg.dtype)
    B = x.shape[0]
    nope, rope_d = cfg.qk_nope_dim, cfg.qk_rope_dim
    positions = _pos_rope(pos, B)
    cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wdq"].astype(dt)), p["q_ln"])
    q = jnp.einsum("bsr,rhk->bhsk", cq, p["wuq"].astype(dt))
    q_nope, q_rope = q[..., :nope], apply_rope(q[..., nope:], positions, cfg.rope_theta)
    ckv_new = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wdkv"].astype(dt)), p["kv_ln"])
    krope_new = apply_rope(
        jnp.einsum("bsd,dk->bsk", x, p["wkr"].astype(dt))[:, None], positions,
        cfg.rope_theta,
    )[:, 0]
    ckv_new = ckv_new.astype(layer_cache["ckv"].dtype)
    krope_new = krope_new.astype(layer_cache["krope"].dtype)
    ckv = shard(layer_cache["ckv"], "batch", "kv_seq", None)
    krope = shard(layer_cache["krope"], "batch", "kv_seq", None)
    wuk = p["wukv"][..., :nope].astype(dt)  # (r, H, nope)
    wuv = p["wukv"][..., nope:].astype(dt)  # (r, H, v)
    q_abs = jnp.einsum("bhsk,rhk->bhsr", q_nope, wuk)

    def scores(c, kr):
        s = jnp.einsum("bhsr,btr->bhst", q_abs, c) + jnp.einsum("bhsk,btk->bhst", q_rope, kr)
        return s.astype(jnp.float32) * ((nope + rope_d) ** -0.5)

    s = jnp.where(_seen_mask(pos, B, ckv.shape[1])[:, :, 0], scores(ckv, krope), -1e30)
    s_new = scores(ckv_new, krope_new)  # (B,H,1,1)
    m = jnp.maximum(s.max(axis=-1, keepdims=True), s_new)
    w, w_new = jnp.exp(s - m), jnp.exp(s_new - m)
    l = w.sum(axis=-1, keepdims=True) + w_new
    ctx = (jnp.einsum("bhst,btr->bhsr", (w / l).astype(dt), ckv,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bhst,btr->bhsr", (w_new / l).astype(dt), ckv_new,
                        preferred_element_type=jnp.float32)).astype(dt)
    out_h = jnp.einsum("bhsr,rhv->bhsv", ctx, wuv)
    y = jnp.einsum("bhsv,hvd->bsd", out_h, p["wo"].astype(dt))
    return y, {"ckv": ckv_new, "krope": krope_new}


def dense_decode_step(params, cfg: ArchConfig, cache, tokens, pos):
    """One decode step. tokens: (B, 1) int32; pos: scalar or (B,) int32.

    The layer scan reads the cache and yields each layer's new token; one
    write (:func:`write_cache`) then puts them all at ``pos``. Returns
    (logits, cache)."""
    h = embed_tokens(params, cfg, tokens)
    decode_attn = mla_decode_attn if cfg.attention == "mla" else gqa_decode_attn

    def body(h, inp):
        layer_p, layer_c = inp
        y, new = decode_attn(layer_p["attn"], layer_c, rms_norm(h, layer_p["ln1"]), cfg, pos)
        h = h + y
        m = layer_p["mlp"]
        h = h + swiglu(rms_norm(h, layer_p["ln2"]), m["wg"], m["wi"], m["wo"], jnp.dtype(cfg.dtype))
        return h, new

    h, new = jax.lax.scan(body, h, (params["blocks"], cache))
    h = rms_norm(h, params["final_ln"])
    return unembed(params, cfg, h), write_cache(cache, new, pos)
