"""Pallas TPU kernel: fused MoE gating (softmax → top-k → capacity slots).

One kernel invocation routes one dispatch group: from router logits (N, E)
it produces, entirely in VMEM,

    idx  (N, k) int32  — expert chosen per slot (iterated-argmax order,
                          matching jax.lax.top_k's stable tie-breaking)
    gate (N, k) f32    — softmax gate weights renormalised over the k picks
    pos  (N, k) int32  — capacity slot within the expert's buffer, or -1
                          when the expert is over capacity (token dropped)

The XLA path materialises probs → top_k → k one-hot (N, E) masks → k
cumsums at HBM-visible boundaries; fused, the (N, E) intermediates stay in
VMEM (N=1024, E=384 f32 ≈ 1.6 MB/tile). Grid = (G,), fully parallel —
capacity state is per-group by construction (GShard semantics).

Dispatch/combine stay as the einsum path: per §Perf cell 3 the AR-combined
one-hot dispatch is wire-optimal at EP=16/top-8, so the *gating decision* is
the part worth fusing, not the data movement.

Validated in interpret mode against :func:`repro.kernels.ref.moe_gating_ref`
and cross-checked against :func:`repro.models.moe.top_k_routing` (the
dispatch/combine tensors rebuilt from (idx, gate, pos) must match exactly).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import default_interpret


def _gating_kernel(logits_ref, idx_ref, gate_ref, pos_ref,
                   *, top_k: int, capacity: int, renormalise: bool):
    f32 = jnp.float32
    x = logits_ref[0].astype(f32)  # (N, E)
    N, E = x.shape
    # softmax over experts
    m = x.max(axis=-1, keepdims=True)
    p = jnp.exp(x - m)
    probs = p / p.sum(axis=-1, keepdims=True)

    # integer bookkeeping in f32 (exact far beyond any N, E) — Mosaic reduces
    # floats across lanes, and has no cumsum: per-expert prefix counts over
    # tokens are a lower-triangular matmul, exact with 0/1 bf16 operands and
    # f32 accumulation
    expert = jax.lax.broadcasted_iota(jnp.int32, (N, E), 1).astype(f32)
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)
        <= jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
    ).astype(jnp.bfloat16)
    pick = jax.lax.broadcasted_iota(jnp.int32, (N, top_k), 1)
    idx = jnp.zeros((N, top_k), f32)
    gate = jnp.zeros((N, top_k), f32)
    pos = jnp.zeros((N, top_k), f32)
    counts = jnp.zeros((1, E), f32)
    remaining = probs
    for j in range(top_k):  # static k → unrolled
        g_j = remaining.max(axis=-1, keepdims=True)  # (N, 1)
        # first max wins (jax.lax.top_k's tie-breaking)
        e_j = jnp.min(jnp.where(remaining == g_j, expert, float(E)),
                      axis=-1, keepdims=True)
        onehot = (expert == e_j).astype(f32)  # (N, E)
        # capacity slot: tokens earlier in the group claim lower slots
        earlier = jax.lax.dot_general(
            tri, onehot.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=f32,
        ) - onehot
        slot = jnp.sum((counts + earlier) * onehot, axis=-1, keepdims=True)  # (N, 1)
        here = pick == j
        idx = jnp.where(here, e_j, idx)
        gate = jnp.where(here, g_j, gate)
        pos = jnp.where(here, jnp.where(slot < capacity, slot, -1.0), pos)
        counts = counts + onehot.sum(axis=0, keepdims=True)
        remaining = jnp.where(onehot > 0, -jnp.inf, remaining)
    if renormalise:
        gate = gate / jnp.maximum(gate.sum(axis=-1, keepdims=True), 1e-9)
    idx_ref[0] = idx.astype(jnp.int32)
    gate_ref[0] = gate
    pos_ref[0] = pos.astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("top_k", "capacity", "renormalise", "interpret")
)
def moe_gating_pallas(logits, *, top_k: int, capacity: int,
                      renormalise: bool = True, interpret: bool | None = None):
    """logits: (G, N, E) → (idx (G,N,k) i32, gate (G,N,k) f32, pos (G,N,k) i32).

    ``interpret=None`` interprets off the TPU only (:func:`default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    G, N, E = logits.shape
    kernel = functools.partial(
        _gating_kernel, top_k=top_k, capacity=capacity, renormalise=renormalise
    )
    return pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[pl.BlockSpec((1, N, E), lambda g: (g, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, N, top_k), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, N, top_k), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, N, top_k), lambda g: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, N, top_k), jnp.int32),
            jax.ShapeDtypeStruct((G, N, top_k), jnp.float32),
            jax.ShapeDtypeStruct((G, N, top_k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(logits)
