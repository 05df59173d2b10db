"""Pallas TPU flash-attention (forward) kernel.

TPU-native adaptation of FlashAttention: the (Sq × Skv) score matrix never
exists in HBM — each grid step loads one (block_q × d) query tile and one
(block_k × d) KV tile into VMEM, runs the online-softmax update on the MXU,
and carries running (m, l, acc) in VMEM scratch across the sequential
KV-block dimension.

Grid = (B, Hq, nQ, nK), with nK innermost — TPU grid semantics execute the
last dimension sequentially per core, so scratch written at step ki is
visible at ki+1 (this replaces the CUDA kernel's shared-memory loop).
Causal/local masking is positional; fully-masked KV tiles are skipped with
``pl.when`` (the compute simply does not issue — the TPU equivalent of
FlashAttention's block skipping).

Block shapes default to (128, 128) — MXU-aligned (the systolic array is
128×128) and small enough that q/k/v/o tiles + f32 scratch stay well under
the ~16 MB/core VMEM budget for every head_dim in the assigned archs
(d ≤ 256 → ~0.6 MB live).

GQA is handled in the index map (query head h reads KV head h // G): no
repeated K/V materialisation in HBM.

Validated in ``interpret=True`` mode against :func:`repro.kernels.ref.attention_ref`
(this container is CPU-only; on real v5e hardware the same call lowers to
Mosaic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import default_interpret

NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref,  # VMEM tiles
    m_ref, l_ref, acc_ref,  # scratch (persist across the kv grid dim)
    *, scale: float, block_q: int, block_k: int, n_k: int,
    causal: bool, window: int, logit_cap: float, kv_valid: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # tile is live unless causal/local masking kills all of it
    live = True
    if causal:  # lowest q row sees k ≤ q_start + block_q - 1
        live = k_start <= q_start + block_q - 1
    if window > 0:  # highest q row q_start+block_q-1 sees k > q - window
        live = jnp.logical_and(
            live, k_start + block_k - 1 > q_start - window
        ) if causal else live

    @pl.when(live)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        if logit_cap > 0:
            s = logit_cap * jnp.tanh(s / logit_cap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_valid  # padded KV columns never attended
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)  # (bq, bk); masked lanes exp(-inf)=0
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "logit_cap", "block_q", "block_k", "interpret",
    ),
)
def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: int = 0,
    logit_cap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    """q: (B,Hq,Sq,d); k,v: (B,Hkv,Skv,d) → (B,Hq,Sq,d). GQA via Hq=G·Hkv.

    ``interpret=None`` interprets off the TPU only (:func:`default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    B, Hq, Sq, d = q.shape
    _, Hkv, Skv, dv = v.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = d**-0.5

    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    # pad ragged sequence lengths (masking keeps semantics exact: padded KV
    # columns have k_pos > every valid q_pos under causal; for non-causal we
    # mask explicitly below via window=0 ∧ causal=False edge case)
    pad_q = (-Sq) % bq
    pad_k = (-Skv) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_k
    n_q = Sq_p // bq
    n_k = Skv_p // bk
    grid = (B, Hq, n_q, n_k)

    kernel = functools.partial(
        _attn_kernel,
        scale=scale, block_q=bq, block_k=bk, n_k=n_k,
        causal=causal, window=window, logit_cap=logit_cap, kv_valid=Skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
