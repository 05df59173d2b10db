"""Plain decoder-only transformer: MLA (MiniCPM3) or GQA (Mistral) attention.

Written from the published descriptions, with the departures that each
configuration file lists (no MiniCPM embedding, residual or logit scales; plain
rotate-half RoPE; the vocabulary padded to ``padded_vocab_size`` rows, which
take part in the loss's softmax). It imports nothing of the program.

Every matrix product goes through ``common.mm``. At ``numerics="f32"`` the
operands are float32 at ``Precision.HIGHEST``; at ``numerics="fp8"`` each
operand is first rounded to float8 e4m3 with one scale per tensor, the
control that a lower-precision path would give.

The weights are stored in the configuration's ``param_dtype`` and cast to
float32 where they are used. Layers are run one at a time, and the loss and
its gradient over blocks of rows, so that a model that fills a chip's memory
in the program fits beside its reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# The model-independent pieces; ``round_e4m3``, ``lr_at`` and ``adamw_step``
# are re-exported, part of this module's interface (``reference/__init__.py``).
from .common import (_draw, adamw_step, get_path, leaf_keys, lr_at, mm,  # noqa: F401
                     path_keys, round_e4m3, set_path, spec_paths)


# ---------------------------------------------------------------------------
# Shapes and the seeded weights
# ---------------------------------------------------------------------------


def dims(cfg: dict) -> dict:
    prog = cfg.get("program", {})
    d = {
        "L": cfg["num_hidden_layers"],
        "D": cfg["hidden_size"],
        "H": cfg["num_attention_heads"],
        "K": cfg["num_key_value_heads"],
        "F": cfg["intermediate_size"],
        "V": cfg["vocab_size"],
        "Vp": cfg["padded_vocab_size"],
        "eps": cfg["rms_norm_eps"],
        "theta": cfg["rope_theta"],
        "mla": prog.get("attention") == "mla",
        "z_loss": prog.get("z_loss", 0.0),
        "param_dtype": jnp.dtype(prog.get("param_dtype", "float32")),
    }
    if d["mla"]:
        d.update(rq=cfg["q_lora_rank"], rkv=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
                 rope=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"])
    else:
        d["hd"] = cfg.get("head_dim") or d["D"] // d["H"]
    return d


def weight_specs(cfg: dict) -> dict:
    """Nested dict of (shape, fan_in or None for ones). Stacked per layer."""
    d = dims(cfg)
    L, D, H, K, F, Vp = d["L"], d["D"], d["H"], d["K"], d["F"], d["Vp"]
    if d["mla"]:
        qk = d["nope"] + d["rope"]
        attn = {
            "wdq": ((L, D, d["rq"]), D),
            "q_ln": ((L, d["rq"]), None),
            "wuq": ((L, d["rq"], H, qk), d["rq"] * H),
            "wdkv": ((L, D, d["rkv"]), D),
            "kv_ln": ((L, d["rkv"]), None),
            "wukv": ((L, d["rkv"], H, d["nope"] + d["dv"]), d["rkv"] * H),
            "wkr": ((L, D, d["rope"]), D),
            "wo": ((L, H, d["dv"], D), H * d["dv"]),
        }
    else:
        hd = d["hd"]
        attn = {
            "wq": ((L, D, H, hd), D * H),
            "wk": ((L, D, K, hd), D * K),
            "wv": ((L, D, K, hd), D * K),
            "wo": ((L, H, hd, D), H * hd),
        }
    return {
        "blocks": {
            "attn": attn,
            "ln1": ((L, D), None),
            "ln2": ((L, D), None),
            "mlp": {"wg": ((L, D, F), D), "wi": ((L, D, F), D), "wo": ((L, F, D), F)},
        },
        "embed": ((Vp, D), Vp),
        "final_ln": ((D,), None),
        "unembed": ((D, Vp), D),
    }


def leaf_paths(cfg: dict) -> list:
    """(path, shape, fan_in) in the order the seeded keys are handed out."""
    return spec_paths(weight_specs(cfg))


def draw_leaf(cfg: dict, seed: int, index: int):
    """Leaf ``index`` of :func:`leaf_paths`, stacked over layers as drawn."""
    _, shape, fan_in = leaf_paths(cfg)[index]
    keys = leaf_keys(seed, len(leaf_paths(cfg)))
    return _draw(keys[index], shape, fan_in, dims(cfg)["param_dtype"])


def init_weights(cfg: dict, seed: int) -> dict:
    """Random weights from the seed: one normal draw per leaf, scaled by
    1/sqrt(fan_in), in the configuration's parameter dtype; norms are ones.
    Stored one dict per layer: ``{"layers": [...], "embed", "final_ln",
    "unembed"}``."""
    L = dims(cfg)["L"]
    out = {"layers": [{} for _ in range(L)]}
    for index, (path, _, _) in enumerate(leaf_paths(cfg)):
        w = draw_leaf(cfg, seed, index)
        keys = path_keys(path)
        if keys[0] == "blocks":
            for i in range(L):
                set_path(out["layers"][i], keys[1:], w[i])
        else:
            out[keys[0]] = w
        del w
    return out


def leaf_norms(tree: dict, cfg: dict) -> dict:
    """Per program leaf (stacked over layers) L2 norm of a per-layer tree."""
    out = {}
    for path, _, _ in leaf_paths(cfg):
        keys = path_keys(path)
        if keys[0] == "blocks":
            sq = sum(float(jnp.sum(jnp.square(get_path(lay, keys[1:]).astype(jnp.float32))))
                     for lay in tree["layers"])
        else:
            sq = float(jnp.sum(jnp.square(tree[keys[0]].astype(jnp.float32))))
        out[path] = math.sqrt(sq)
    return out


def change_norms(params: dict, cfg: dict, seed: int) -> dict:
    """Per program leaf (stacked over layers), the L2 norm of a per-layer
    tree's change from the leaf's seeded start."""
    out = {}
    for index, (path, _, _) in enumerate(leaf_paths(cfg)):
        start = draw_leaf(cfg, seed, index)
        keys = path_keys(path)
        if keys[0] == "blocks":
            sq = sum(float(jnp.sum(jnp.square(get_path(lay, keys[1:]).astype(jnp.float32)
                                              - start[i].astype(jnp.float32))))
                     for i, lay in enumerate(params["layers"]))
        else:
            sq = float(jnp.sum(jnp.square(params[keys[0]].astype(jnp.float32)
                                          - start.astype(jnp.float32))))
        out[path] = math.sqrt(sq)
    return out


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotate-half RoPE on the last dim. x: (B, S, ..., d); positions (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(0, 2 * half, 2, dtype=np.float32) / (2 * half)))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freqs)[None, :]  # (S, half)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _causal_softmax_av(q, k, v, scale, q_block, numerics):
    """softmax(q k^T * scale, causal) v over blocks of query rows.

    q: (B, S, H, dq); k: (B, S, H, dq); v: (B, S, H, dv) -> (B, S, H, dv)."""
    B, S, H, _ = q.shape
    qb = min(q_block, S)
    assert S % qb == 0, (S, qb)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = mm("bqhd,bkhd->bhqk", qi, k, numerics) * scale
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v, numerics)

    out = jax.lax.map(block, jnp.arange(S // qb))  # (n, B, qb, H, dv)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, v.shape[-1])


def attention(p, x, d, numerics, q_block=512):
    B, S, _ = x.shape
    pos = jnp.arange(S)
    if d["mla"]:
        cq = rms_norm(mm("bsd,dr->bsr", x, p["wdq"], numerics), p["q_ln"], d["eps"])
        q = mm("bsr,rhk->bshk", cq, p["wuq"], numerics)
        q_nope, q_pe = q[..., : d["nope"]], rope(q[..., d["nope"]:], pos, d["theta"])
        ckv = rms_norm(mm("bsd,dr->bsr", x, p["wdkv"], numerics), p["kv_ln"], d["eps"])
        kv = mm("bsr,rhk->bshk", ckv, p["wukv"], numerics)
        k_nope, v = kv[..., : d["nope"]], kv[..., d["nope"]:]
        k_pe = rope(mm("bsd,dk->bsk", x, p["wkr"], numerics)[:, :, None], pos, d["theta"])
        k_pe = jnp.broadcast_to(k_pe, (B, S, d["H"], d["rope"]))
        qf = jnp.concatenate([q_nope, q_pe], -1)
        kf = jnp.concatenate([k_nope, k_pe], -1)
        ctx = _causal_softmax_av(qf, kf, v, (d["nope"] + d["rope"]) ** -0.5, q_block, numerics)
        return mm("bshv,hvd->bsd", ctx, p["wo"], numerics)
    q = rope(mm("bsd,dhk->bshk", x, p["wq"], numerics), pos, d["theta"])
    k = rope(mm("bsd,dhk->bshk", x, p["wk"], numerics), pos, d["theta"])
    v = mm("bsd,dhk->bshk", x, p["wv"], numerics)
    group = d["H"] // d["K"]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    ctx = _causal_softmax_av(q, k, v, d["hd"] ** -0.5, q_block, numerics)
    return mm("bshk,hkd->bsd", ctx, p["wo"], numerics)


def layer(p, h, d, numerics):
    """One pre-norm block: h + attn(norm(h)), then + SwiGLU(norm(h))."""
    h = h + attention(p["attn"], rms_norm(h, p["ln1"], d["eps"]), d, numerics)
    x = rms_norm(h, p["ln2"], d["eps"])
    m = p["mlp"]
    g = mm("bsd,df->bsf", x, m["wg"], numerics)
    u = mm("bsd,df->bsf", x, m["wi"], numerics)
    return h + mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["wo"], numerics)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_fwd(p, h, d_items, numerics):
    return layer(p, h, dict(d_items), numerics)


def _items(d: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in d.items()))


def hidden_states(params, tokens, cfg: dict, numerics: str = "f32", q_block: int = 512):
    """Final-norm hidden states (B, S, D) float32, layer by layer. A long
    sequence is padded at its end to whole query blocks (causal attention
    keeps the padding out of every real position) and cut back."""
    d = dims(cfg)
    tokens = np.asarray(tokens)
    S = tokens.shape[1]
    if S > q_block and S % q_block:
        tokens = np.pad(tokens, ((0, 0), (0, q_block - S % q_block)))
    h = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for i in range(d["L"]):
        h = _layer_fwd(params["layers"][i], h, _items(d), numerics)
    return rms_norm(h[:, :S], params["final_ln"], d["eps"])


@functools.partial(jax.jit, static_argnums=(2,))
def _logits(h, unembed, numerics):
    return mm("bsd,dv->bsv", h, unembed, numerics)


def logits(params, tokens, cfg: dict, numerics: str = "f32", row_block: int = 1024):
    """Logits over the padded vocabulary, (B, S, Vp) float32, on the device."""
    h = hidden_states(params, tokens, cfg, numerics)
    S = h.shape[1]
    parts = [_logits(h[:, s : s + row_block], params["unembed"], numerics)
             for s in range(0, S, row_block)]
    return jnp.concatenate(parts, axis=1)


# ---------------------------------------------------------------------------
# Loss, gradient and AdamW, layer by layer
# ---------------------------------------------------------------------------


def _ce_terms(logits, labels, z_loss):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - gold + z_loss * lse * lse) * mask), jnp.sum(mask)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _head_vjp(h_pre, final_ln, unembed, labels, denom, eps, z_loss, numerics):
    """Loss share of a block of rows and its cotangents."""

    def f(h_pre, final_ln, unembed):
        x = rms_norm(h_pre, final_ln, eps)
        total, _ = _ce_terms(mm("bsd,dv->bsv", x, unembed, numerics), labels, z_loss)
        return total / denom

    val, vjp = jax.vjp(f, h_pre, final_ln.astype(jnp.float32), unembed.astype(jnp.float32))
    return (val,) + vjp(jnp.ones((), jnp.float32))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_vjp(p, h, g_out, d_items, numerics):
    d = dict(d_items)
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    _, vjp = jax.vjp(lambda p_, h_: layer(p_, h_, d, numerics), p32, h)
    return vjp(g_out)


def loss_and_grads(params, tokens, labels, cfg: dict, numerics: str = "f32",
                   row_block: int = 1024, *, denom=None, acc=None):
    """Mean token loss (cross-entropy + z-loss) and float32 gradients.

    A batch too large for one pass is taken in blocks of rows: each call
    gets the whole batch's token count as ``denom``, returns its share of
    the loss, and adds its gradients into ``acc`` (the previous block's),
    layer by layer."""
    d = dims(cfg)
    tokens = jnp.asarray(tokens)
    labels = jnp.asarray(labels)
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    inputs = []
    for i in range(d["L"]):
        inputs.append(h)
        h = _layer_fwd(params["layers"][i], h, _items(d), numerics)
    if denom is None:
        denom = jnp.maximum(jnp.sum(labels >= 0), 1)
    denom = jnp.asarray(denom, jnp.float32)
    loss = jnp.zeros((), jnp.float32)
    g_h, g_ln, g_un = [], jnp.zeros(d["D"], jnp.float32), 0.0
    for s in range(0, h.shape[1], row_block):
        val, gh, gl, gu = _head_vjp(h[:, s : s + row_block], params["final_ln"],
                                    params["unembed"], labels[:, s : s + row_block], denom,
                                    d["eps"], d["z_loss"], numerics)
        loss, g_ln, g_un = loss + val, g_ln + gl, g_un + gu
        g_h.append(gh)
    g = jnp.concatenate(g_h, axis=1)
    del h, g_h
    if acc is None:
        acc = {"layers": [None] * d["L"], "embed": jnp.zeros(params["embed"].shape, jnp.float32),
               "final_ln": 0.0, "unembed": 0.0}
    layer_grads = acc["layers"]
    for i in reversed(range(d["L"])):
        lg, g = _layer_vjp(params["layers"][i], inputs[i], g, _items(d), numerics)
        if layer_grads[i] is not None:
            lg = jax.tree_util.tree_map(jnp.add, layer_grads[i], lg)
        layer_grads[i] = lg
        inputs[i] = None
    g_embed = acc["embed"].at[tokens.reshape(-1)].add(g.reshape(-1, d["D"]))
    grads = {"layers": layer_grads, "embed": g_embed,
             "final_ln": acc["final_ln"] + g_ln, "unembed": acc["unembed"] + g_un}
    return loss, grads
