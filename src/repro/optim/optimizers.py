"""Sharded functional optimizers: AdamW, 8-bit AdamW, Lion.

No optax dependency. Each optimizer is a pair of pure functions plus a
*logical-axis mirror* so the dry-run can lower trillion-parameter update
steps without allocating:

  init(params)                 → opt state (tree of arrays)
  update(grads, state, params) → (new_params, new_state)
  state_logical(param_logical) → logical axes for every state leaf

``adamw8bit`` stores m/v block-quantized to int8 with per-row absmax scales
(bitsandbytes-style) — 4 bytes/param of optimizer state instead of 8. This
is what lets the kimi-k2 (≈1.03 T params) train_step fit the dry-run memory
budget (see EXPERIMENTS.md).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    name: str
    init: Callable
    update: Callable  # (grads, state, params) -> (new_params, new_state)
    state_logical: Callable  # (param_logical_tree) -> state logical tree


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


# ---------------------------------------------------------------------------
# AdamW (fp32 moments)
# ---------------------------------------------------------------------------


def adamw(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, clip_norm=1.0):
    sched = lr if callable(lr) else (lambda _: jnp.asarray(lr, jnp.float32))

    def init(params):
        return {
            "m": _tmap(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "v": _tmap(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "count": jnp.zeros((), jnp.int32),
        }

    def update(grads, state, params):
        grads = _tmap(lambda g: g.astype(jnp.float32), grads)
        grads = _clip_by_global_norm(grads, clip_norm)
        count = state["count"] + 1
        lr_t = sched(count)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        m = _tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = _tmap(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)

        def step(p, m_, v_):
            upd = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps) + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr_t * upd).astype(p.dtype)

        new_params = _tmap(step, params, m, v)
        return new_params, {"m": m, "v": v, "count": count}

    def state_logical(param_logical):
        return {"m": param_logical, "v": param_logical, "count": ()}

    return Optimizer("adamw", init, update, state_logical)


# ---------------------------------------------------------------------------
# 8-bit AdamW (block-quantized moments, error kept implicitly via requant)
# ---------------------------------------------------------------------------


def _quant(x):
    """Per-row int8 absmax quantisation. x: f32 (..., N) → (int8, f32 scales)."""
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def _dequant(q, scale):
    return q.astype(jnp.float32) * scale[..., None]


def adamw8bit(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, clip_norm=1.0):
    sched = lr if callable(lr) else (lambda _: jnp.asarray(lr, jnp.float32))

    def init(params):
        def zq(p):
            return {
                "q": jnp.zeros(p.shape, jnp.int8),
                "scale": jnp.zeros(p.shape[:-1], jnp.float32),
            }

        return {
            "m": _tmap(zq, params),
            "v": _tmap(zq, params),
            "count": jnp.zeros((), jnp.int32),
        }

    def update(grads, state, params):
        grads = _tmap(lambda g: g.astype(jnp.float32), grads)
        grads = _clip_by_global_norm(grads, clip_norm)
        count = state["count"] + 1
        lr_t = sched(count)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        leaves_g, treedef = jax.tree_util.tree_flatten(grads)
        leaves_p = treedef.flatten_up_to(params)
        leaves_m = treedef.flatten_up_to(state["m"])
        leaves_v = treedef.flatten_up_to(state["v"])
        new_p, new_m, new_v = [], [], []
        for g, p, mq, vq in zip(leaves_g, leaves_p, leaves_m, leaves_v):
            m = b1 * _dequant(mq["q"], mq["scale"]) + (1 - b1) * g
            v = b2 * _dequant(vq["q"], vq["scale"]) + (1 - b2) * g * g
            upd = (m / c1) / (jnp.sqrt(jnp.maximum(v, 0.0) / c2) + eps)
            upd = upd + weight_decay * p.astype(jnp.float32)
            new_p.append((p.astype(jnp.float32) - lr_t * upd).astype(p.dtype))
            qm, sm = _quant(m)
            qv, sv = _quant(v)
            new_m.append({"q": qm, "scale": sm})
            new_v.append({"q": qv, "scale": sv})
        unf = jax.tree_util.tree_unflatten
        return unf(treedef, new_p), {
            "m": unf(treedef, new_m),
            "v": unf(treedef, new_v),
            "count": count,
        }

    def state_logical(param_logical):
        def mirror(lg):
            return {"q": lg, "scale": lg[:-1]}

        wrap = lambda tree: jax.tree_util.tree_map(
            mirror, tree, is_leaf=lambda x: isinstance(x, tuple)
        )
        return {"m": wrap(param_logical), "v": wrap(param_logical), "count": ()}

    return Optimizer("adamw8bit", init, update, state_logical)


# ---------------------------------------------------------------------------
# Lion (single moment) — lowest-memory fp option
# ---------------------------------------------------------------------------


def lion(lr=1e-4, b1=0.9, b2=0.99, weight_decay=0.1, clip_norm=1.0):
    sched = lr if callable(lr) else (lambda _: jnp.asarray(lr, jnp.float32))

    def init(params):
        return {
            "m": _tmap(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "count": jnp.zeros((), jnp.int32),
        }

    def update(grads, state, params):
        grads = _tmap(lambda g: g.astype(jnp.float32), grads)
        grads = _clip_by_global_norm(grads, clip_norm)
        count = state["count"] + 1
        lr_t = sched(count)

        def step(p, m, g):
            upd = jnp.sign(b1 * m + (1 - b1) * g) + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr_t * upd).astype(p.dtype)

        new_params = _tmap(step, params, state["m"], grads)
        m = _tmap(lambda m, g: b2 * m + (1 - b2) * g, state["m"], grads)
        return new_params, {"m": m, "count": count}

    def state_logical(param_logical):
        return {"m": param_logical, "count": ()}

    return Optimizer("lion", init, update, state_logical)


def make_optimizer(name: str, **kw) -> Optimizer:
    return {"adamw": adamw, "adamw8bit": adamw8bit, "lion": lion}[name](**kw)


# ---------------------------------------------------------------------------


def _clip_by_global_norm(grads, max_norm: float):
    if not max_norm or max_norm <= 0:
        return grads
    with jax.named_scope("clip"):
        sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads))
        norm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
        return _tmap(lambda g: g * scale, grads)
