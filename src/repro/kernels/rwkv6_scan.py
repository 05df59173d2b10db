"""Pallas TPU kernel for the RWKV-6 WKV recurrence (chunked parallel form).

The recurrence (per head, dk×dv state S):

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    y_t = r_t · (S_{t-1} + diag(u) k_t v_t^T)

CUDA RWKV ships a hand-written sequential kernel (one thread per channel).
The TPU-native adaptation instead processes the sequence in chunks of C
tokens: within a chunk, all pairwise decay ratios
``exp(lc_excl[t] - lc[s]) (s < t)`` form a (C, C, dk) tensor — every term is
≤ 1 because decays are in (0,1), so the exponentials are numerically safe —
and the in-chunk output is two MXU contractions instead of C sequential
vector ops. The cross-chunk state is carried in VMEM scratch across the
sequential chunk grid dimension (grid = (B, H, T/C), last dim sequential on
TPU).

VMEM budget per step (C=64, dk=dv=64, f32): tiles ~192 KB, the pairwise
ratio tensor 1 MB, state 16 KB — comfortably inside a v5e core's ~16 MB.

Validated in interpret mode against the token-by-token oracle
:func:`repro.kernels.ref.wkv6_ref` (forward); the training path uses the
identical-math XLA form in :mod:`repro.models.rwkv6` (jax.checkpoint-ed),
so kernel and model cross-check each other.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import default_interpret


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                y_ref, sout_ref, s_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)  # (C, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)  # (C, dv)
    w = w_ref[0, 0].astype(jnp.float32)  # (C, dk), in (0,1)
    u = u_ref[0].astype(jnp.float32)  # (1, dk)
    s = s_ref[...]  # (dk, dv)

    C = chunk
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    tri = col < row  # s < t

    logw = jnp.log(jnp.maximum(w, 1e-38))
    # inclusive prefix sum over the chunk as a lower-triangular matmul
    # (Mosaic has no cumsum)
    lc = jax.lax.dot_general(
        (col <= row).astype(jnp.float32), logw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # (C, dk)
    lc_excl = lc - logw

    # in-chunk pairwise term: A[t,s] = Σ_i r[t,i] k[s,i] e^{lc_excl[t,i]-lc[s,i]}
    ratio = jnp.exp(lc_excl[:, None, :] - lc[None, :, :])  # (C, C, dk), ≤1 under tri
    A = jnp.sum(r[:, None, :] * ratio * k[None, :, :], axis=-1)  # (C, C)
    A = jnp.where(tri, A, 0.0)
    diag = jnp.sum(r * u * k, axis=-1)  # (C,)
    y = (
        jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + diag[:, None] * v
        + jax.lax.dot_general(r * jnp.exp(lc_excl), s, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    )
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S' = e^{lc[-1]} ⊙ S + Σ_s (k_s e^{lc[-1]-lc[s]}) v_s^T
    lc_last = lc[C - 1 : C]  # (1, dk)
    # the same chunk total as a (dk, 1) column, to scale the state's rows
    lc_last_col = jax.lax.dot_general(
        logw, jnp.ones((C, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    k_scaled = k * jnp.exp(lc_last - lc)  # (C, dk), ≤1
    s_new = jnp.exp(lc_last_col) * s + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s_ref[...] = s_new

    @pl.when(ci == n_chunks - 1)
    def _finish():
        sout_ref[0, 0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, w, u, s0, *, chunk: int = 64,
                interpret: bool | None = None):
    """r,k,w: (B,H,T,dk); v: (B,H,T,dv); u: (H,dk); s0: (B,H,dk,dv) f32.

    Returns (y: (B,H,T,dv) in r.dtype, s_final: (B,H,dk,dv) f32).
    ``interpret=None`` interprets off the TPU only (:func:`default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    B, H, T, dk = r.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    assert T % C == 0, (T, C)
    n = T // C
    grid = (B, H, n)

    kernel = functools.partial(_wkv_kernel, chunk=C, n_chunks=n)
    y, s_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, C, dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, dv), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, dv), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, dv), r.dtype),
            jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, dk), s0)
    return y, s_fin
