"""Dispatch layer over the Pallas kernels.

Each op has three execution paths, chosen per call site:

* ``use_pallas=True`` → the Pallas kernel (Mosaic on TPU; off the TPU the
  same kernel body runs in the Pallas interpreter, see
  :func:`repro.kernels.backend.default_interpret`);
* ``use_pallas=False`` → the XLA path (chunked-flash attention /
  chunked WKV / associative scan) — identical math, compiler-scheduled;
* gradients: the Pallas kernels are *forward* kernels wrapped in
  ``jax.custom_vjp`` whose backward recomputes through the XLA path
  (flash-style rematerialisation: save only (inputs, outputs), re-run the
  memory-bounded XLA forward under ``jax.vjp``). Training with
  ``use_pallas=True`` is therefore exact, at one extra forward of compute —
  the standard flash-attention trade.

Models call these via the ``ArchConfig.use_pallas`` flag, so kernel-vs-XLA
is a config diff (a §Perf lever), not a code fork.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .rglru_scan import lru_pallas
from .rmsnorm import rmsnorm_pallas
from .rwkv6_scan import wkv6_pallas


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def _attention_pallas(q, k, v, causal, window, logit_cap, kv_chunk):
    return flash_attention(q, k, v, causal=causal, window=window, logit_cap=logit_cap)


def _attention_xla(q, k, v, causal, window, logit_cap, kv_chunk):
    from repro.models.common import attention_chunked

    return attention_chunked(
        q, k, v, causal=causal, window=window, logit_cap=logit_cap,
        kv_chunk=kv_chunk,
    )


def _attention_fwd(q, k, v, causal, window, logit_cap, kv_chunk):
    out = _attention_pallas(q, k, v, causal, window, logit_cap, kv_chunk)
    return out, (q, k, v)


def _attention_bwd(causal, window, logit_cap, kv_chunk, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _attention_xla(q_, k_, v_, causal, window, logit_cap, kv_chunk),
        q, k, v,
    )
    return vjp(g)


_attention_pallas.defvjp(_attention_fwd, _attention_bwd)


def attention(
    q, k, v, *, causal: bool = True, window: int = 0, logit_cap: float = 0.0,
    kv_chunk: int = 1024, use_pallas: bool = False,
):
    """(B,Hq,Sq,d) × (B,Hkv,Skv,d)² → (B,Hq,Sq,dv); GQA by head ratio."""
    if use_pallas:
        return _attention_pallas(q, k, v, causal, window, logit_cap, kv_chunk)
    # XLA path expects expanded KV heads when grouped reshape is needed —
    # attention_chunked handles Hq=G·Hkv natively.
    return _attention_xla(q, k, v, causal, window, logit_cap, kv_chunk)


# ---------------------------------------------------------------------------
# RWKV6 WKV
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _wkv6_p(r, k, v, w, u, s0, chunk):
    return wkv6_pallas(r, k, v, w, u, s0, chunk=chunk)


def _wkv6_xla(r, k, v, w, u, s0, chunk):
    from repro.models.rwkv6 import wkv6_chunked

    return wkv6_chunked(r, k, v, w, u, s0, chunk=chunk)


def _wkv6_fwd(r, k, v, w, u, s0, chunk):
    out = _wkv6_p(r, k, v, w, u, s0, chunk)
    return out, (r, k, v, w, u, s0)


def _wkv6_bwd(chunk, res, g):
    r, k, v, w, u, s0 = res
    _, vjp = jax.vjp(lambda *a: _wkv6_xla(*a, chunk), r, k, v, w, u, s0)
    return vjp(g)


_wkv6_p.defvjp(_wkv6_fwd, _wkv6_bwd)


def wkv6(r, k, v, w, u, s0, *, chunk: int = 64, use_pallas: bool = False):
    """RWKV6 recurrence; returns (y, final_state)."""
    if use_pallas:
        return _wkv6_p(r, k, v, w, u, s0, chunk)
    return _wkv6_xla(r, k, v, w, u, s0, chunk)


# ---------------------------------------------------------------------------
# RG-LRU linear recurrence
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _lru_p(a, b, h0):
    return lru_pallas(a, b, h0)


def _lru_xla(a, b, h0):
    """Log-depth associative scan: (a2, b2) ∘ (a1, b1) = (a1·a2, a2·b1 + b2)."""
    f32 = jnp.float32
    a_f, b_f = a.astype(f32), b.astype(f32)
    # fold h0 into the first step: b'_1 = a_1 h0 + b_1
    b_f = b_f.at[:, 0].add(a_f[:, 0] * h0.astype(f32))

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    A, Bc = jax.lax.associative_scan(combine, (a_f, b_f), axis=1)
    return Bc.astype(a.dtype), Bc[:, -1]


def _lru_fwd(a, b, h0):
    out = _lru_p(a, b, h0)
    return out, (a, b, h0)


def _lru_bwd(res, g):
    a, b, h0 = res
    _, vjp = jax.vjp(_lru_xla, a, b, h0)
    return vjp(g)


_lru_p.defvjp(_lru_fwd, _lru_bwd)


def lru_scan(a, b, h0, *, use_pallas: bool = False):
    """h_t = a_t ⊙ h_{t-1} + b_t; returns (h_seq, h_final)."""
    if use_pallas:
        return _lru_p(a, b, h0)
    return _lru_xla(a, b, h0)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _rmsnorm_p(x, w):
    return rmsnorm_pallas(x, w)


def _rmsnorm_xla(x, w):
    from repro.models.common import rms_norm

    return rms_norm(x, w)


def _rmsnorm_fwd(x, w):
    return _rmsnorm_p(x, w), (x, w)


def _rmsnorm_bwd(res, g):
    x, w = res
    _, vjp = jax.vjp(_rmsnorm_xla, x, w)
    return vjp(g)


_rmsnorm_p.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(x, w, *, use_pallas: bool = False):
    if use_pallas:
        return _rmsnorm_p(x, w)
    return _rmsnorm_xla(x, w)
