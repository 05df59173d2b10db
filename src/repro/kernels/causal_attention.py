"""Fused causal self-attention on the TPU: forward, dq and dkv Pallas kernels.

The score tile of a (query block x key block) pair lives in VMEM only: it is
computed on the MXU, softmaxed online and multiplied into V without a round
trip through HBM, in the forward pass and in both backward kernels.

* Operands go to the MXU in the input dtype (bf16 for the benchmarked
  configurations) with f32 accumulation: ``q·kᵀ``, ``p·v``, ``do·vᵀ``,
  ``pᵀ·do``, ``dsᵀ·q`` and ``ds·k``. The softmax statistics (running max,
  sum, log-sum-exp) are f32.
* Causal block skipping: the grid's last axis walks a table of the live
  (query block, key block) pairs, scalar-prefetched (:func:`_live_blocks`),
  so no grid step, DMA or compute is spent above the diagonal. Only the
  diagonal block is masked.
* Several heads a grid step (up to :data:`MAX_HEADS` query heads, in a
  loop inside the kernel): the per-step cost of the pipeline is paid once
  for all of them.
* GQA in the index maps: query head ``h`` reads key/value head ``h // G``;
  a dkv step takes whole groups of query heads and sums each into its
  key/value block. No expanded K/V is written to HBM.
* One block size for queries and keys, from the sequence length
  (:func:`block_size`): 512 where it divides, else the largest multiple of
  128 that does.

:func:`causal_flash_attention` is the entry point; it takes the same
``(B, H, S, d)`` layout as :func:`repro.models.common.attention_chunked`
and scales by the true query/key head dim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import default_interpret

NEG_INF = -1e30
LANES = 128
MAX_HEADS = 4  # query heads a grid step works through
NT = (((1,), (1,)), ((), ()))  # a · bᵀ
NN = (((1,), (0,)), ((), ()))  # a · b


def block_size(seq: int) -> int:
    """512 where it divides ``seq``, else the largest multiple of 128 that
    does (at most 512)."""
    if seq % LANES:
        raise ValueError(f"sequence length {seq} is not a multiple of {LANES}")
    return max(t for t in range(LANES, 513, LANES) if seq % t == 0)


def heads_per_step(heads: int, groups: int) -> int:
    """Query heads a forward or dq grid step takes: the most, up to
    :data:`MAX_HEADS`, that divide ``heads`` and either fill whole groups of
    one key/value head or sit inside one."""
    return max(h for h in range(1, MAX_HEADS + 1)
               if heads % h == 0 and (h % groups == 0 or groups % h == 0))


def dkv_heads(kv_heads: int, groups: int) -> tuple:
    """How a dkv grid step takes heads: ``(kv heads, query heads, chunks)``.
    Whole groups of ``groups`` query heads, as many key/value heads as fit in
    :data:`MAX_HEADS` query heads; a group larger than that is split into
    ``chunks`` of at most :data:`MAX_HEADS` query heads, visited in turn."""
    if groups > MAX_HEADS:
        q = max(h for h in range(1, MAX_HEADS + 1) if groups % h == 0)
        return 1, q, groups // q
    kb = max(h for h in range(1, MAX_HEADS // groups + 1) if kv_heads % h == 0)
    return kb, kb * groups, 1


def _lanes(x, width: int):
    """A lane-replicated (t, 128) statistic as a (t, width) tile."""
    if width == LANES:
        return x
    return jnp.tile(x, (1, pl.cdiv(width, LANES)))[:, :width]


def _diagonal_mask(s, *, keys_in_rows: bool):
    """Mask the future in a diagonal tile (query and key blocks coincide)."""
    rows = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = cols >= rows if keys_in_rows else cols <= rows
    return jnp.where(keep, s, NEG_INF)


def _live_blocks(n: int, keys_outer: bool = False, chunks: int = 1):
    """The (query block, key block[, head chunk]) visits of a causal grid, as
    int32 tables for scalar prefetch: the grid's last axis walks them, so no
    step is spent above the diagonal. Queries outer: for i, for j <= i (the
    diagonal last). Keys outer (dkv): for j, for each chunk of query heads,
    for i >= j (the diagonal first)."""
    if keys_outer:
        visits = [(i, j, c) for j in range(n) for c in range(chunks) for i in range(j, n)]
    else:
        visits = [(i, j, 0) for i in range(n) for j in range(i + 1)]
    return tuple(jnp.asarray(np.array(col, np.int32)) for col in zip(*visits))


def _for_each_head(n: int):
    """Run the decorated ``body(h)`` for the step's heads ``0..n-1`` as a loop
    in the kernel, not unrolled: the kernel, and its compile time, do not
    grow with the heads a step takes."""

    def run(body):
        lax.fori_loop(0, n, lambda h, carry: (body(h), carry)[1], 0)

    return run


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# Forward (query blocks outer, key blocks inner)
# ---------------------------------------------------------------------------


def _fwd_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, groups: int):
    step = pl.program_id(2)
    i, j = qi_ref[step], ki_ref[step]
    dv = acc_sc.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def update(diagonal: bool):
        @_for_each_head(q_ref.shape[0])
        def _(h):
            v = v_ref[h // groups]
            s = lax.dot_general(q_ref[h], k_ref[h // groups], NT,
                                preferred_element_type=jnp.float32)
            if diagonal:
                s = _diagonal_mask(s, keys_in_rows=False)
            m_prev = m_sc[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))  # (t, 128)
            p = jnp.exp(s - _lanes(m_new, s.shape[-1]))
            alpha = jnp.exp(m_prev - m_new)
            l_sc[h] = alpha * l_sc[h] + p.sum(axis=-1, keepdims=True)
            m_sc[h] = m_new
            pv = lax.dot_general(p.astype(v.dtype), v, NN, preferred_element_type=jnp.float32)
            acc_sc[h] = _lanes(alpha, dv) * acc_sc[h] + pv

    pl.when(j < i)(lambda: update(False))

    @pl.when(j == i)  # the diagonal is a query block's last visit
    def _finish():
        update(True)
        @_for_each_head(q_ref.shape[0])
        def _(h):
            l = l_sc[h]
            o_ref[h] = (acc_sc[h] / _lanes(l, dv)).astype(o_ref.dtype)
            lse_ref[h] = (m_sc[h] + jnp.log(l)).T[:1]  # a row: queries in lanes


def _query_major_specs(H: int, G: int, t: int):
    """Block specs of the forward and dq grids (B, H / hb, visits): ``hb``
    query heads a step, and the key/value heads they read. Returns ``hb``,
    ``q(d)`` and ``kv(d)`` (blocks of head dim ``d``) and ``row`` (the
    per-query statistics)."""
    hb = heads_per_step(H, G)
    kvb = max(1, hb // G)

    def q(d):
        return pl.BlockSpec((None, hb, t, d), lambda b, h, s, qi, ki: (b, h, qi[s], 0))

    def kv(d):
        return pl.BlockSpec((None, kvb, t, d),
                            lambda b, h, s, qi, ki: (b, h * hb // G // kvb, ki[s], 0))

    row = pl.BlockSpec((None, hb, 1, t), lambda b, h, s, qi, ki: (b, h, 0, qi[s]))
    return hb, q, kv, row


def _forward(q, k, v, interpret: bool):
    B, H, S, dk = q.shape
    dv, G, t = v.shape[-1], H // k.shape[1], block_size(S)
    hb, q_spec, kv_spec, row = _query_major_specs(H, G, t)
    qi, ki, _ = _live_blocks(S // t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb, qi.shape[0]),
            in_specs=[q_spec(dk), kv_spec(dk), kv_spec(dv)],
            out_specs=[q_spec(dv), row],
            scratch_shapes=[
                pltpu.VMEM((hb, t, LANES), jnp.float32),
                pltpu.VMEM((hb, t, LANES), jnp.float32),
                pltpu.VMEM((hb, t, dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32),
        ],
        compiler_params=_params(),
        name="causal_flash_fwd",
        interpret=interpret,
    )(qi, ki, q, k, v)


# ---------------------------------------------------------------------------
# Backward: dq (query blocks outer, key blocks inner)
# ---------------------------------------------------------------------------


def _dq_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_sc,
               *, groups: int):
    step = pl.program_id(2)
    i, j = qi_ref[step], ki_ref[step]

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def update(diagonal: bool):
        @_for_each_head(q_ref.shape[0])
        def _(h):
            k = k_ref[h // groups]
            s = lax.dot_general(q_ref[h], k, NT, preferred_element_type=jnp.float32)
            if diagonal:
                s = _diagonal_mask(s, keys_in_rows=False)
            p = jnp.exp(s - jnp.expand_dims(lse_ref[h, 0], -1))
            dp = lax.dot_general(do_ref[h], v_ref[h // groups], NT,
                                 preferred_element_type=jnp.float32)
            ds = (dp - jnp.expand_dims(di_ref[h, 0], -1)) * p
            dq_sc[h] += lax.dot_general(ds.astype(k.dtype), k, NN,
                                        preferred_element_type=jnp.float32)

    pl.when(j < i)(lambda: update(False))

    @pl.when(j == i)
    def _finish():
        update(True)
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _backward_dq(q, k, v, do, lse, di, interpret: bool):
    B, H, S, dk = q.shape
    dv, G, t = v.shape[-1], H // k.shape[1], block_size(S)
    hb, q_spec, kv_spec, row = _query_major_specs(H, G, t)
    qi, ki, _ = _live_blocks(S // t)
    return pl.pallas_call(
        functools.partial(_dq_kernel, groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb, qi.shape[0]),
            in_specs=[q_spec(dk), kv_spec(dk), kv_spec(dv), q_spec(dv), row, row],
            out_specs=q_spec(dk),
            scratch_shapes=[pltpu.VMEM((hb, t, dk), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_params(),
        name="causal_flash_dq",
        interpret=interpret,
    )(qi, ki, q, k, v, do, lse, di)


# ---------------------------------------------------------------------------
# Backward: dk, dv (key blocks outer, query blocks inner; a step takes whole
# groups of query heads, or chunks of a large group, summed into their
# key/value head in VMEM)
# ---------------------------------------------------------------------------


def _dkv_kernel(qi_ref, ki_ref, ci_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, n: int, groups: int, chunks: int):
    step = pl.program_id(2)
    i, j, c = qi_ref[step], ki_ref[step], ci_ref[step]

    def update(diagonal: bool):
        @_for_each_head(q_ref.shape[0])
        def _(h):
            kv = h // groups
            q, do = q_ref[h], do_ref[h]
            # keys in rows, queries in lanes: the row statistics broadcast down
            s = lax.dot_general(k_ref[kv], q, NT, preferred_element_type=jnp.float32)
            if diagonal:
                s = _diagonal_mask(s, keys_in_rows=True)
            p = jnp.exp(s - lse_ref[h])
            dv_sc[kv] += lax.dot_general(p.astype(do.dtype), do, NN,
                                         preferred_element_type=jnp.float32)
            dp = lax.dot_general(v_ref[kv], do, NT, preferred_element_type=jnp.float32)
            ds = (dp - di_ref[h]) * p
            dk_sc[kv] += lax.dot_general(ds.astype(q.dtype), q, NN,
                                         preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(c == 0, i == j))  # a key block's first visit
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    pl.when(i == j)(lambda: update(True))
    pl.when(i > j)(lambda: update(False))

    @pl.when(jnp.logical_and(c == chunks - 1, i == n - 1))
    def _finish():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _backward_dkv(q, k, v, do, lse, di, interpret: bool):
    B, H, S, dk = q.shape
    hkv, dv, t = k.shape[1], v.shape[-1], block_size(S)
    G = H // hkv
    kb, qb, chunks = dkv_heads(hkv, G)
    qi, ki, ci = _live_blocks(S // t, keys_outer=True, chunks=chunks)

    def q_map(b, h, s, qi, ki, ci):  # query heads (h * chunks + c) * qb ..
        return b, h * chunks + ci[s], qi[s], 0

    def kv_map(b, h, s, qi, ki, ci):
        return b, h, ki[s], 0

    def row_map(b, h, s, qi, ki, ci):
        return b, h * chunks + ci[s], 0, qi[s]

    def spec(heads, d, index):
        return pl.BlockSpec((None, heads, t, d), index)

    row = pl.BlockSpec((None, qb, 1, t), row_map)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, n=S // t, groups=G, chunks=chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, hkv // kb, qi.shape[0]),
            in_specs=[spec(qb, dk, q_map), spec(kb, dk, kv_map), spec(kb, dv, kv_map),
                      spec(qb, dv, q_map), row, row],
            out_specs=[spec(kb, dk, kv_map), spec(kb, dv, kv_map)],
            scratch_shapes=[pltpu.VMEM((kb, t, dk), jnp.float32),
                            pltpu.VMEM((kb, t, dv), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=_params(),
        name="causal_flash_dkv",
        interpret=interpret,
    )(qi, ki, ci, q, k, v, do, lse, di)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(q, k, v, interpret):
    return _forward(q, k, v, interpret)[0]


def _attention_fwd(q, k, v, interpret):
    o, lse = _forward(q, k, v, interpret)
    return o, (q, k, v, o, lse)


def _attention_bwd(interpret, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)[:, :, None]
    dq = _backward_dq(q, k, v, do, lse, di, interpret)
    dk, dv = _backward_dkv(q, k, v, do, lse, di, interpret)
    return dq, dk, dv


_attention.defvjp(_attention_fwd, _attention_bwd)


def causal_flash_attention(q, k, v, *, interpret: bool | None = None):
    """Causal self-attention, fused: q (B, Hq, S, d), k (B, Hkv, S, d),
    v (B, Hkv, S, dv) → (B, Hq, S, dv), with Hq = G·Hkv and S a multiple of
    128. Differentiable in q, k and v through the fused backward kernels.

    ``interpret=None`` interprets off the TPU only (:func:`default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads do not group over {k.shape[1]} key heads")
    q = q * (q.shape[-1] ** -0.5)  # in q's dtype, as attention_chunked scales
    return _attention(q, k, v, interpret)
