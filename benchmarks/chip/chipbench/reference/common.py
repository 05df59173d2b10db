"""What every plain reference shares, whatever the model.

Matrix products in float32 at ``Precision.HIGHEST`` or, for the controls, on
operands rounded to float8 e4m3 (:func:`mm`); the seeded draw of a leaf, as
the program's jitted init makes it; paths into a nested dict of weights; and
AdamW with global-norm clipping, leaf by leaf. It imports nothing of the
program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def round_e4m3(x):
    """Round float32 to the nearest float8 e4m3 value (3 mantissa bits,
    smallest normal 2^-6, subnormal step 2^-9, largest 448), in float32
    arithmetic so that it runs where the fp8 type does not."""
    _, e = jnp.frexp(x)  # x = m * 2^e with 0.5 <= |m| < 1
    step = jnp.exp2(jnp.maximum(e - 1, -6) - 3).astype(jnp.float32)
    return jnp.clip(jnp.round(x / step) * step, -F8_MAX, F8_MAX)


def _to_fp8(x):
    """x rounded to fp8 with one scale per tensor. The gradient passes
    straight through the rounding, so the backward matmuls see the rounded
    operands; rounding's own derivative, zero almost everywhere, would cut
    the gradient instead of coarsening it."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return x + jax.lax.stop_gradient(round_e4m3(x / scale) * scale - x)


def mm(eq: str, a, b, numerics: str = "f32"):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if numerics == "fp8":
        a, b = _to_fp8(a), _to_fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


# ---------------------------------------------------------------------------
# Seeded leaves and paths
# ---------------------------------------------------------------------------


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def spec_paths(specs: dict) -> list:
    """(path, shape, fan_in) of a nested dict of (shape, fan_in) leaves, in
    the order ``jax.tree_util`` flattens it, which is the program's."""
    flat, _ = jax.tree_util.tree_flatten_with_path(specs, is_leaf=_is_spec)
    return [(jax.tree_util.keystr(p), spec[0], spec[1]) for p, spec in flat]


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, fan_in, dtype):
    if fan_in is None:
        return jnp.ones(shape, dtype)
    std = 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape) * std).astype(dtype)


def leaf_keys(seed: int, n: int):
    return jax.random.split(jax.random.PRNGKey(seed), n)


def path_keys(path: str) -> list:
    return [k.strip("'\"") for k in path.strip("[]").split("][")]


def set_path(tree: dict, keys: list, value) -> None:
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


def get_path(tree: dict, keys: list):
    for k in keys:
        tree = tree[k]
    return tree


# ---------------------------------------------------------------------------
# AdamW, leaf by leaf
# ---------------------------------------------------------------------------


def lr_at(count: int, traffic: dict) -> float:
    """Linear warm-up then cosine decay to 0, at optimizer step ``count``."""
    peak, warm, total = traffic["lr"], traffic["warmup"], traffic["steps"]
    if count < warm:
        return peak * min(1.0, count / max(1, warm))
    frac = min(max((count - warm) / max(1, total - warm), 0.0), 1.0)
    return peak * 0.5 * (1 + math.cos(math.pi * frac))


@functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(0, 1, 2))
def _adamw_leaf(p, m, v, g, lr, count, hyper):
    b1, b2, eps, wd = hyper
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count
    p32 = p.astype(jnp.float32)
    upd = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p32
    return (p32 - lr * upd).astype(p.dtype), m, v


def adamw_step(params, m, v, grads, count: int, traffic: dict):
    """AdamW with global-norm clipping, leaf by leaf. Returns (params, m, v,
    clip scale): the optimizer got ``grads`` times that scale."""
    o = traffic["optimizer"]
    gl = jax.tree_util.tree_leaves(grads)
    norm = math.sqrt(sum(float(jnp.sum(g * g)) for g in gl))
    scale = min(1.0, o["clip_norm"] / max(norm, 1e-12)) if o["clip_norm"] else 1.0
    lr = lr_at(count, traffic)
    hyper = (o["b1"], o["b2"], o["eps"], o["weight_decay"])
    pl, treedef = jax.tree_util.tree_flatten(params)
    ml, vl = treedef.flatten_up_to(m), treedef.flatten_up_to(v)
    new = [_adamw_leaf(p, m_, v_, g * scale, lr, float(count), hyper)
           for p, m_, v_, g in zip(pl, ml, vl, gl)]
    unf = functools.partial(jax.tree_util.tree_unflatten, treedef)
    return (unf([n[0] for n in new]), unf([n[1] for n in new]), unf([n[2] for n in new]),
            scale)
