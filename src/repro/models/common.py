"""Shared model machinery: parameter definitions with logical axes, logical
sharding rules, norms, RoPE, and memory-efficient attention.

Design notes
------------
* **No flax.** A model is described by a pytree of :class:`ParamDef`
  (shape + logical axis names + dtype). ``init_params`` materialises real
  arrays; the multi-pod dry-run only ever calls ``jax.eval_shape`` over it,
  so trillion-parameter configs never allocate.
* **Logical axes** ("embed", "heads", "ff", "experts", "vocab", ...) are
  resolved to mesh axes through a rules table (see :mod:`repro.parallel.sharding`),
  the MaxText idiom — one model definition serves every mesh.
* **Attention** ships two XLA paths: a chunked flash-style scan (online
  softmax over KV blocks; bounded memory for 32k prefill) and a single-shot
  path for tiny query lengths (decode). On a TPU, causal full-sequence
  self-attention takes the fused Pallas kernel of
  :mod:`repro.kernels.causal_attention` instead (:func:`attention_path`);
  ``ArchConfig.use_pallas`` routes the other cases to ``repro.kernels.ops``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import get_registry

# ---------------------------------------------------------------------------
# Named scopes
# ---------------------------------------------------------------------------


def scoped(name: str):
    """Run the decorated function under ``jax.named_scope(name)``: the name
    goes into the ``op_name`` metadata of every op it traces, forward,
    recomputed and backward alike, and changes no computation. The scope
    catalogue is in :mod:`repro.obs.spans`."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Declarative parameter: shape + logical axes (+ init scale)."""

    shape: tuple
    logical: tuple  # logical axis name per dim (None = replicated dim)
    dtype: Any = jnp.float32
    init: str = "normal"  # normal | zeros | ones | constant
    scale: float = 1.0  # stddev for normal / value for constant

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_defs(tree):
    return jax.tree_util.tree_leaves(tree, is_leaf=is_def)


def init_params(defs, rng):
    """Materialise a ParamDef tree into real arrays (small configs only)."""
    leaves, treedef = jax.tree_util.tree_flatten(defs, is_leaf=is_def)
    keys = jax.random.split(rng, len(leaves))
    out = []
    for k, d in zip(keys, leaves):
        if d.init == "zeros":
            out.append(jnp.zeros(d.shape, d.dtype))
        elif d.init == "ones":
            out.append(jnp.ones(d.shape, d.dtype))
        elif d.init == "constant":
            out.append(jnp.full(d.shape, d.scale, d.dtype))
        else:
            std = d.scale / math.sqrt(max(1, _fan_in(d)))
            out.append((jax.random.normal(k, d.shape) * std).astype(d.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(defs):
    """ShapeDtypeStruct tree — what the dry-run lowers against."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs, is_leaf=is_def
    )


def _fan_in(d: ParamDef) -> int:
    if len(d.shape) == 0:
        return 1
    if len(d.shape) == 1:
        return d.shape[0]
    # stacked-layer leading dim ("layers") is not a fan-in dim
    dims = d.shape[1:] if d.logical and d.logical[0] == "layers" else d.shape
    return int(np.prod(dims[:-1])) if len(dims) > 1 else dims[0]


# ---------------------------------------------------------------------------
# Logical sharding constraints
# ---------------------------------------------------------------------------

# Active logical→mesh rules, installed by repro.parallel.sharding.use_rules().
_ACTIVE_RULES: dict | None = None
_ACTIVE_MESH = None


def set_logical_rules(rules: dict | None, mesh=None) -> None:
    global _ACTIVE_RULES, _ACTIVE_MESH
    _ACTIVE_RULES = rules
    _ACTIVE_MESH = mesh


def logical_to_spec(logical: tuple) -> "jax.sharding.PartitionSpec":
    from jax.sharding import PartitionSpec as P

    if _ACTIVE_RULES is None:
        return P()
    axes = []
    for name in logical:
        axes.append(_ACTIVE_RULES.get(name) if name is not None else None)
    return P(*axes)


def axis_devices(name: str) -> int:
    """How many devices the active rules and mesh split the logical axis
    ``name`` over: 1 without rules, or where the rule maps it to no axis."""
    if _ACTIVE_RULES is None or _ACTIVE_MESH is None:
        return 1
    axes = _ACTIVE_RULES.get(name)
    if axes is None:
        return 1
    return math.prod(_ACTIVE_MESH.shape[a] for a in ((axes,) if isinstance(axes, str) else axes))


def shard(x, *logical):
    """with_sharding_constraint by logical axis names (no-op without rules)."""
    if _ACTIVE_RULES is None or _ACTIVE_MESH is None:
        return x
    spec = logical_to_spec(tuple(logical))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(_ACTIVE_MESH, spec)
    )


# ---------------------------------------------------------------------------
# Norms & embeddings
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def sinusoidal_positions(length: int, dim: int, dtype=jnp.float32):
    pos = np.arange(length)[:, None]
    div = np.exp(np.arange(0, dim, 2) / dim * -math.log(10000.0))
    emb = np.zeros((length, dim), np.float32)
    emb[:, 0::2] = np.sin(pos * div)
    emb[:, 1::2] = np.cos(pos * div)
    return jnp.asarray(emb, dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 1e4):
    """x: (..., S, Dh); positions: (S,) or (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (XLA paths)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def attention_chunked(
    q, k, v, *, causal: bool = True, window: int = 0,
    q_offset=0, kv_chunk: int = 1024, logit_cap: float = 0.0,
):
    """Flash-style double-blocked attention (query blocks × KV chunks).

    q: (B, Hq, Sq, Dh); k, v: (B, Hkv, Skv, Dh); GQA via Hq = G·Hkv.
    Peak live memory is one (B, H, q_block, chunk) score tile (both the
    forward scan step and its rematerialised backward), so 32k×32k attention
    never materialises O(Sq·Skv).

    Causal self-attention (Sq == Skv, q_offset == 0) uses a *triangular*
    schedule: query blocks are unrolled and each scans only its ≤ diagonal
    KV chunks — no masked-out block is ever computed (2× FLOP saving vs the
    rectangular scan; local attention additionally clips at the window).
    Ragged lengths (whisper's 1500-frame encoder) are padded and masked.
    """
    B, Hq, Sq, Dh = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[-1]  # MLA has v_head_dim != qk head dim
    G = Hq // Hkv
    chunk = min(kv_chunk, max(Skv, 1))
    valid_kv = Skv
    if Skv % chunk:  # pad ragged KV
        pad = chunk - Skv % chunk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        Skv += pad
    n_kv = Skv // chunk
    valid_q = Sq
    qb = min(chunk, Sq)
    if Sq % qb:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, qb - Sq % qb), (0, 0)))
        Sq = q.shape[2]
    n_q = Sq // qb

    qg = q.reshape(B, Hkv, G, n_q, qb, Dh) * (Dh**-0.5)
    kc = k.reshape(B, Hkv, n_kv, chunk, Dh).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, n_kv, chunk, Dv).transpose(2, 0, 1, 3, 4)

    def tile(carry, q_blk, k_blk, v_blk, q_pos, k_pos):
        """One (q_block × kv_chunk) online-softmax update."""
        m, l, acc = carry
        s = jnp.einsum(
            "bhgqd,bhcd->bhgqc", q_blk, k_blk, preferred_element_type=jnp.float32
        )
        if logit_cap > 0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        mask = jnp.broadcast_to(k_pos[None, :] < valid_kv, (qb, chunk))
        mask &= q_pos[:, None] < valid_q + q_offset
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + p.sum(axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum(
            "bhgqc,bhcd->bhgqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    def init_carry():
        return (
            jnp.full((B, Hkv, G, qb), NEG_INF, jnp.float32),
            jnp.zeros((B, Hkv, G, qb), jnp.float32),
            jnp.zeros((B, Hkv, G, qb, Dv), jnp.float32),
        )

    triangular = causal and Sq == Skv and not _is_traced(q_offset) and q_offset == 0

    if triangular:
        outs = []
        for qi in range(n_q):
            lo = 0
            if window > 0:
                lo = max(0, qi - (window + chunk - 1) // chunk)
            q_blk = qg[:, :, :, qi]
            q_pos = qi * qb + jnp.arange(qb)

            @jax.checkpoint
            def q_block_fn(q_blk, ks, vs, lo=lo, qi=qi, q_pos=q_pos):
                def step(carry, inp):
                    ci, k_blk, v_blk = inp
                    k_pos = ci * chunk + jnp.arange(chunk)
                    return tile(carry, q_blk, k_blk, v_blk, q_pos, k_pos), None

                carry, _ = jax.lax.scan(
                    jax.checkpoint(step), init_carry(), (jnp.arange(lo, qi + 1), ks, vs)
                )
                m, l, acc = carry
                return acc / jnp.maximum(l, 1e-30)[..., None]

            outs.append(q_block_fn(q_blk, kc[lo : qi + 1], vc[lo : qi + 1]))
        out = jnp.stack(outs, axis=3)  # (B,Hkv,G,n_q,qb,Dv)
    else:
        # rectangular: outer scan over q blocks, inner scan over all KV chunks
        @jax.checkpoint
        def q_block_fn(q_blk, q_pos):
            def step(carry, inp):
                ci, k_blk, v_blk = inp
                k_pos = ci * chunk + jnp.arange(chunk)
                return tile(carry, q_blk, k_blk, v_blk, q_pos, k_pos), None

            carry, _ = jax.lax.scan(
                jax.checkpoint(step), init_carry(), (jnp.arange(n_kv), kc, vc)
            )
            m, l, acc = carry
            return acc / jnp.maximum(l, 1e-30)[..., None]

        def outer(_, inp):
            qi, q_blk = inp
            q_pos = q_offset + qi * qb + jnp.arange(qb)
            return None, q_block_fn(q_blk, q_pos)

        _, out = jax.lax.scan(
            outer, None, (jnp.arange(n_q), qg.transpose(3, 0, 1, 2, 4, 5))
        )
        out = out.transpose(1, 2, 3, 0, 4, 5)  # → (B,Hkv,G,n_q,qb,Dv)

    out = out.reshape(B, Hq, Sq, Dv)[:, :, :valid_q]
    return out.astype(q.dtype)


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# Attention (fused Pallas path) and the dispatch between the two
# ---------------------------------------------------------------------------


def attention_platform() -> str:
    """Where the model's arrays live: the active mesh's devices, else JAX's
    default backend."""
    if _ACTIVE_MESH is not None:
        return _ACTIVE_MESH.devices.flat[0].platform
    return jax.default_backend()


def attention_path(
    q, k, *, causal: bool = True, window: int = 0, logit_cap: float = 0.0,
    q_offset=0, fallback: str = "chunked",
) -> str:
    """``"fused"`` where full-sequence attention runs in the fused causal
    kernel (:mod:`repro.kernels.causal_attention`): on a TPU, causal
    self-attention (Sq == Skv, no query offset) with no window and no logit
    cap, S a multiple of 128. Else ``fallback``, the path the caller keeps.

    Counts the decision in ``nbi_attention_path_total{path=}`` (once per
    trace, not per step)."""
    S = q.shape[2]
    fused = (
        attention_platform() == "tpu"
        and causal
        and S == k.shape[2]
        and not _is_traced(q_offset)
        and q_offset == 0
        and window == 0
        and logit_cap == 0
        and S % 128 == 0
    )
    path = "fused" if fused else fallback
    get_registry().counter(
        "nbi_attention_path_total",
        "full-sequence attention calls traced, by the path they take",
        labels=("path",),
    ).labels(path=path).inc()
    return path


def fused_causal_attention(q, k, v, kv_heads: str = "kv_heads"):
    """:func:`repro.kernels.causal_attention.causal_flash_attention` on the
    active mesh: per device under ``shard_map`` (batch over the data axes,
    heads as the rules split them), since a Pallas call is not partitioned
    by the compiler. ``kv_heads`` is the logical axis of k's and v's heads.
    """
    from repro.kernels.causal_attention import causal_flash_attention

    if _ACTIVE_RULES is None or _ACTIVE_MESH is None or _ACTIVE_MESH.size == 1:
        return causal_flash_attention(q, k, v)
    q_spec = logical_to_spec(("batch", "heads", None, None))
    kv_spec = logical_to_spec(("batch", kv_heads, None, None))
    if kv_spec[1] != q_spec[1]:  # KV heads not split as the query heads are
        G = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
        kv_spec = q_spec
    return jax.shard_map(
        causal_flash_attention, mesh=_ACTIVE_MESH,
        in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec, check_vma=False,
    )(q, k, v)


def attention_single_shot(q, k, v, *, mask=None, logit_cap: float = 0.0, new_kv=None):
    """Naive attention for tiny Sq (decode): one (B,H,Sq,Skv) score tensor.

    With the KV sequence dim sharded over the ``model`` mesh axis this is
    exactly flash-decoding's split-KV: GSPMD turns the softmax reductions
    into tiny per-shard partials + an all-reduce.

    ``new_kv`` = (k_new, v_new), each (B, Hkv, T, Dh), are keys and values
    that every query sees besides ``k`` and ``v`` (decode: the new token,
    not yet in the cache). They join the one softmax by a shared max and
    sum, in score space, so the cache is never copied to append them.
    """
    B, Hq, Sq, Dh = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Sq, Dh) * (Dh**-0.5)

    def scores(keys):
        s = jnp.einsum("bhgqd,bhsd->bhgqs", qg, keys, preferred_element_type=jnp.float32)
        return logit_cap * jnp.tanh(s / logit_cap) if logit_cap > 0 else s

    s = scores(k)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    parts = [(s, v)] if new_kv is None else [(s, v), (scores(new_kv[0]), new_kv[1])]
    m = functools.reduce(jnp.maximum, [x.max(axis=-1, keepdims=True) for x, _ in parts])
    parts = [(jnp.exp(x - m), vals) for x, vals in parts]
    l = functools.reduce(jnp.add, [p.sum(axis=-1, keepdims=True) for p, _ in parts])
    l = jnp.maximum(l, 1e-30)
    out = functools.reduce(jnp.add, [
        jnp.einsum("bhgqs,bhsd->bhgqd", (p / l).astype(vals.dtype), vals,
                   preferred_element_type=jnp.float32)
        for p, vals in parts
    ])
    return out.reshape(B, Hq, Sq, Dh).astype(q.dtype)


def causal_mask(sq: int, skv: int, q_offset=0):
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(skv)
    return (k_pos[None, :] <= q_pos[:, None])[None, None, None]


# ---------------------------------------------------------------------------
# Activations / misc
# ---------------------------------------------------------------------------


@scoped("mlp")
def swiglu(x, wg, wi, wo, dtype):
    g = jnp.einsum("bsd,df->bsf", x, wg.astype(dtype))
    h = jnp.einsum("bsd,df->bsf", x, wi.astype(dtype))
    h = jax.nn.silu(g) * h
    h = shard(h, "batch", None, "ff")
    return jnp.einsum("bsf,fd->bsd", h, wo.astype(dtype))


@scoped("mlp")
def geglu(x, wg, wi, wo, dtype):
    g = jnp.einsum("bsd,df->bsf", x, wg.astype(dtype))
    h = jnp.einsum("bsd,df->bsf", x, wi.astype(dtype))
    h = jax.nn.gelu(g) * h
    h = shard(h, "batch", None, "ff")
    return jnp.einsum("bsf,fd->bsd", h, wo.astype(dtype))


@scoped("loss")
def cross_entropy(logits, labels, *, z_loss: float = 1e-4):
    """Token-level CE with optional z-loss; logits may be vocab-sharded.

    Returns (mean loss, metrics). labels == -100 are masked out.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    # gold logit via masked reduce (not take_along_axis): elementwise over the
    # (possibly vocab-sharded) logits + a partial-sum reduce — GSPMD keeps the
    # big tensor sharded instead of all-gathering it for a gather op.
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(
        jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1
    )
    mask = (labels >= 0).astype(jnp.float32)
    ce = (lse - gold) * mask
    zl = z_loss * jnp.square(lse) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (ce + zl).sum() / denom
    acc = ((logits.argmax(-1) == labels) * mask).sum() / denom
    return loss, {"ce": ce.sum() / denom, "accuracy": acc}
