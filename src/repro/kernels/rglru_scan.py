"""Pallas TPU kernel for the RG-LRU linear recurrence.

    h_t = a_t ⊙ h_{t-1} + b_t        a, b: (B, T, W);  h_0: (B, W)

The Griffin paper fuses this into a custom GPU scan kernel; on TPU the
natural blocking is (sequence chunks × width tiles): grid =
(B, W/bw, T/C) with the chunk dimension sequential, carrying the (1, bw)
state in VMEM scratch. Within a chunk the recurrence runs as a C-step
``fori_loop`` of pure VPU element-wise ops on rows already resident in
VMEM — there is no matmul here, so the MXU is idle by construction and the
kernel's job is purely to keep HBM traffic at the 2·C·bw streaming minimum
(a,b in; h out) instead of the scan's per-step round trips.

Width tiles are independent → the W/bw grid dimension is parallel
("embarrassingly channel-parallel", matching the GPU kernel's
thread-per-channel layout).

Validated in interpret mode against :func:`repro.kernels.ref.lru_ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import default_interpret


def _lru_kernel(a_ref, b_ref, h0_ref, y_ref, hout_ref, h_ref,
                *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)  # (1, bw)

    def step(t, h):
        row = pl.ds(t, 1)
        h = (a_ref[0, row, :].astype(jnp.float32) * h
             + b_ref[0, row, :].astype(jnp.float32))  # (1, bw)
        y_ref[0, row, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == n_chunks - 1)
    def _finish():
        hout_ref[0] = h


@functools.partial(jax.jit, static_argnames=("chunk", "block_w", "interpret"))
def lru_pallas(a, b, h0, *, chunk: int = 128, block_w: int = 512,
               interpret: bool | None = None):
    """a, b: (B, T, W); h0: (B, W). Returns (h_seq (B,T,W) in a.dtype, h_final f32).

    ``interpret=None`` interprets off the TPU only (:func:`default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    B, T, W = a.shape
    C = min(chunk, T)
    assert T % C == 0, (T, C)
    bw = min(block_w, W)
    assert W % bw == 0, (W, bw)
    n = T // C
    grid = (B, W // bw, n)

    kernel = functools.partial(_lru_kernel, chunk=C, n_chunks=n)
    y, h_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C, bw), lambda b_, w_, c: (b_, c, w_)),
            pl.BlockSpec((1, C, bw), lambda b_, w_, c: (b_, c, w_)),
            pl.BlockSpec((1, 1, bw), lambda b_, w_, c: (b_, 0, w_)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, bw), lambda b_, w_, c: (b_, c, w_)),
            pl.BlockSpec((1, 1, bw), lambda b_, w_, c: (b_, 0, w_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, W), a.dtype),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b, h0.reshape(B, 1, W))
    return y, h_fin.reshape(B, W)
