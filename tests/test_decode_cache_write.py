"""The decode step writes only the new token into the cache.

Each decode-attention layer reads the cache it is given and takes the new
token into its softmax in score space; the layer scan yields the new K and V
(MLA: ``ckv`` and ``krope``) and one write after the scan puts them at
``pos``. The reference below is the path this replaced: each layer blends the
new token into its whole cache with a one-hot (``scatter_seq``), attends over
that cache, and the scan returns every layer's whole cache.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as tx
from repro.models.common import apply_rope, rms_norm, swiglu
from repro.models.moe import moe_decode_ffn
from repro.models.registry import build_model

B, S = 2, 16
CASES = {
    "gqa": ("codeqwen1.5-7b", {}),
    "mla": ("minicpm3-4b", {}),
    "gqa-local-window": ("codeqwen1.5-7b", {"attention": "local", "window": 4}),
    "gqa-logit-cap": ("codeqwen1.5-7b", {"logit_cap": 5.0}),
    # drop-free, as in test_archs: a dropped token would hide the attention
    "moe": ("deepseek-moe-16b", {"capacity_factor": 16.0}),
}
POSITIONS = {"scalar": 9, "per-row": [5, 11]}


# ---------------------------------------------------------------------------
# The replaced path: one-hot blend of each layer's whole cache
# ---------------------------------------------------------------------------


def ref_scatter_seq(buf, update, pos):
    S = buf.shape[-2]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        onehot = (jnp.arange(S) == pos).astype(buf.dtype)[..., None]
    else:
        oh = (jnp.arange(S)[None, :] == pos[:, None]).astype(buf.dtype)
        onehot = oh.reshape((buf.shape[0],) + (1,) * (buf.ndim - 3) + (S, 1))
    return buf * (1 - onehot) + update.astype(buf.dtype) * onehot


def ref_mask(pos, skv, window=0):
    """(B,1,1,1,S): positions up to and with ``pos``, after ``pos - window``."""
    pos = jnp.broadcast_to(jnp.asarray(pos), (B,))[:, None, None, None, None]
    k_pos = jnp.arange(skv)[None, None, None, None, :]
    mask = k_pos <= pos
    return mask & (k_pos > pos - window) if window > 0 else mask


def ref_gqa(p, c, x, cfg, pos):
    dt = jnp.dtype(cfg.dtype)
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(dt))
    k_new = jnp.einsum("bsd,dhk->bhsk", x, p["wk"].astype(dt))
    v_new = jnp.einsum("bsd,dhk->bhsk", x, p["wv"].astype(dt))
    positions = tx._pos_rope(pos, B)
    q = apply_rope(q, positions, cfg.rope_theta)
    k_new = apply_rope(k_new, positions, cfg.rope_theta)
    k = ref_scatter_seq(c["k"], k_new, pos)
    v = ref_scatter_seq(c["v"], v_new, pos)
    Hkv, Skv, hd = k.shape[1:]
    qg = q.reshape(B, Hkv, -1, 1, hd) * hd**-0.5
    s = jnp.einsum("bhgqd,bhsd->bhgqs", qg, k, preferred_element_type=jnp.float32)
    if cfg.logit_cap > 0:
        s = cfg.logit_cap * jnp.tanh(s / cfg.logit_cap)
    window = cfg.window if cfg.attention == "local" else 0
    s = jnp.where(ref_mask(pos, Skv, window), s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqs,bhsd->bhgqd", w, v, preferred_element_type=jnp.float32)
    out = out.reshape(q.shape).astype(q.dtype)
    return jnp.einsum("bhsk,hkd->bsd", out, p["wo"].astype(dt)), {"k": k, "v": v}


def ref_mla(p, c, x, cfg, pos):
    dt = jnp.dtype(cfg.dtype)
    nope, rope_d = cfg.qk_nope_dim, cfg.qk_rope_dim
    positions = tx._pos_rope(pos, B)
    cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wdq"].astype(dt)), p["q_ln"])
    q = jnp.einsum("bsr,rhk->bhsk", cq, p["wuq"].astype(dt))
    q_nope, q_rope = q[..., :nope], apply_rope(q[..., nope:], positions, cfg.rope_theta)
    ckv_new = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wdkv"].astype(dt)), p["kv_ln"])
    krope_new = apply_rope(jnp.einsum("bsd,dk->bsk", x, p["wkr"].astype(dt))[:, None],
                           positions, cfg.rope_theta)[:, 0]
    ckv = ref_scatter_seq(c["ckv"], ckv_new, pos)
    krope = ref_scatter_seq(c["krope"], krope_new, pos)
    q_abs = jnp.einsum("bhsk,rhk->bhsr", q_nope, p["wukv"][..., :nope].astype(dt))
    s = (jnp.einsum("bhsr,btr->bhst", q_abs, ckv)
         + jnp.einsum("bhsk,btk->bhst", q_rope, krope))
    s = s.astype(jnp.float32) * ((nope + rope_d) ** -0.5)
    s = jnp.where(ref_mask(pos, ckv.shape[1])[:, :, 0], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(dt)
    ctx = jnp.einsum("bhst,btr->bhsr", w, ckv)
    out_h = jnp.einsum("bhsr,rhv->bhsv", ctx, p["wukv"][..., nope:].astype(dt))
    y = jnp.einsum("bhsv,hvd->bsd", out_h, p["wo"].astype(dt))
    return y, {"ckv": ckv, "krope": krope}


def ref_decode(params, cfg, cache, tokens, pos):
    dt = jnp.dtype(cfg.dtype)
    h = tx.embed_tokens(params, cfg, tokens)
    attn = ref_mla if cfg.attention == "mla" else ref_gqa

    def scan(blocks, layer_cache, ffn):
        def body(h, inp):
            p, c = inp
            y, new_c = attn(p["attn"], c, rms_norm(h, p["ln1"]), cfg, pos)
            h = h + y
            return h + ffn(p, rms_norm(h, p["ln2"])), new_c

        return jax.lax.scan(body, h, (blocks, layer_cache))

    dense = lambda p, x: swiglu(x, p["mlp"]["wg"], p["mlp"]["wi"], p["mlp"]["wo"], dt)  # noqa: E731
    if cfg.family == "moe":
        new = {}
        if cfg.n_dense_layers:
            h, new["dense"] = scan(params["dense_blocks"], cache["dense"], dense)
        h, new["moe"] = scan(params["moe_blocks"], cache["moe"],
                             lambda p, x: moe_decode_ffn(p["moe"], x, cfg))
    else:
        h, new = scan(params["blocks"], cache, dense)
    return tx.unembed(params, cfg, rms_norm(h, params["final_ln"])), new


# ---------------------------------------------------------------------------


def first_layer(tree):
    """Layer 0 of the cache's first layer stack: its input is the embedding,
    so both paths compute its new token from the same numbers."""
    leaves = tree["dense"] if "dense" in tree else tree
    return {k: v[0] for k, v in leaves.items()}


@pytest.mark.parametrize("where", sorted(POSITIONS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_writes_only_the_new_token(case, where):
    arch, over = CASES[case]
    model = build_model(get_smoke_config(arch).replace(**over))
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(0))
    defs = model.cache_defs_fn(B, S)
    keys = jax.random.split(jax.random.PRNGKey(1), len(jax.tree_util.tree_leaves(defs)))
    cache = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(defs),
        [jax.random.normal(k, d.shape, d.dtype)
         for k, d in zip(keys, jax.tree_util.tree_leaves(defs))])
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    pos = jnp.asarray(POSITIONS[where], jnp.int32)

    logits, new = jax.jit(model.decode_fn)(params, cache, tokens, pos)
    want_logits, want = jax.jit(lambda p, c, t, i: ref_decode(p, cfg, c, t, i))(
        params, cache, tokens, pos)

    at_pos = np.zeros((B, S), bool)
    at_pos[np.arange(B), np.broadcast_to(np.asarray(pos), (B,))] = True
    for path, got in jax.tree_util.tree_leaves_with_path(new):
        before = dict(jax.tree_util.tree_leaves_with_path(cache))[path]
        ref = dict(jax.tree_util.tree_leaves_with_path(want))[path]
        assert got.shape == before.shape and got.dtype == before.dtype
        # sequence axis to the front of the row: (L, B, ..., S, d) -> (B, S, rest)
        rows = lambda a: np.moveaxis(np.asarray(a), (1, a.ndim - 2), (0, 1))  # noqa: E731
        g, b, r = rows(got), rows(before), rows(ref)
        np.testing.assert_array_equal(g[~at_pos], b[~at_pos])
        # later layers' inputs carry the attention's sums in another order
        np.testing.assert_allclose(g[at_pos], r[at_pos], rtol=1e-5, atol=1e-6)
    for key, got in first_layer(new).items():
        ref = first_layer(want)[key]
        rows = lambda a: np.moveaxis(np.asarray(a), (0, a.ndim - 2), (0, 1))  # noqa: E731
        np.testing.assert_array_equal(rows(got)[at_pos], rows(ref)[at_pos])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("seq_devices", [1, 2], ids=["in-place", "one-hot"])
@pytest.mark.parametrize("where", sorted(POSITIONS))
@pytest.mark.parametrize("arch", ["codeqwen1.5-7b", "minicpm3-4b"])
def test_write_cache_matches_the_one_hot_blend(arch, where, seq_devices, monkeypatch):
    """``write_cache`` puts the stacked new tokens where the replaced
    per-layer one-hot blend put them, bit for bit, on its in-place path and
    on the one-hot path it keeps for a sharded ``kv_seq`` (chosen here by
    faking the number of devices the rules split ``kv_seq`` over)."""
    monkeypatch.setattr(tx, "axis_devices", lambda name: seq_devices)
    model = build_model(get_smoke_config(arch))
    defs = model.cache_defs_fn(B, S)
    rng = np.random.default_rng(3)
    cache = {k: jnp.asarray(rng.standard_normal(d.shape), d.dtype) for k, d in defs.items()}
    new = {k: jnp.asarray(rng.standard_normal((*d.shape[:-2], 1, d.shape[-1])), d.dtype)
           for k, d in defs.items()}
    pos = jnp.asarray(POSITIONS[where], jnp.int32)
    got = jax.jit(tx.write_cache)(cache, new, pos)
    for k in defs:
        want = jax.vmap(lambda b, u: ref_scatter_seq(b, u, pos))(cache[k], new[k])
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want))
