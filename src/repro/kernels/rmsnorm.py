"""Pallas TPU fused RMSNorm kernel.

RMSNorm is memory-bound: unfused XLA issues read(x) → mean-of-squares →
read(x) again → scale, plus a weight broadcast. The fused kernel streams
each (block_rows, D) tile through VMEM exactly once: one pass computes the
f32 row moments and writes the scaled result — HBM traffic = x-in + y-out,
the streaming minimum.

Grid = (rows/block_rows,), fully parallel. D stays unblocked (the assigned
archs top out at D=12288 → a 128×12288 f32 tile is 6 MB, within VMEM; the
row-block shrinks automatically for wider models).

Validated in interpret mode against :func:`repro.kernels.ref.rmsnorm_ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import default_interpret


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (rows, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)[None, :]
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_pallas(x, weight, *, eps: float = 1e-6, block_rows: int = 128,
                   interpret: bool | None = None):
    """x: (..., D); weight: (D,). Fused row-wise RMSNorm.

    ``interpret=None`` interprets off the TPU only (:func:`default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    orig_shape = x.shape
    D = orig_shape[-1]
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    br = min(block_rows, N)
    # keep the f32 tile under ~8 MB of VMEM for very wide models
    while br > 1 and br * D * 4 > 8 * 1024 * 1024:
        br //= 2
    pad = (-N) % br
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(xf.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(xf, weight)
    return out[:N].reshape(orig_shape)
