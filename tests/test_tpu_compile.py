"""Ahead-of-time compiles of every Pallas kernel, and of the decode step, for a
described TPU v5e chip.

Interpret-mode tests (``test_kernels.py``) hold the kernels to their oracles
but accept block shapes and primitives that Mosaic refuses. These compile
each kernel at a real model width with ``interpret=False`` for a v5e that is
described, not attached; nothing runs. The decode step is compiled at real
widths to see what the TPU compiler makes of its cache write. The topology is described inside a
fixture only: loading the TPU compiler while a module is imported would give
pytest-xdist workers different test lists.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.causal_attention import causal_flash_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gating import moe_gating_pallas
from repro.kernels.rglru_scan import lru_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.rwkv6_scan import wkv6_pallas
from repro.models.registry import build_model

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def compile_for_chip(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_gqa(spec):
    """32 query heads over 8 KV heads, head dim 128, 2048 tokens, bf16."""
    q = spec((1, 32, 2048, 128), BF16)
    kv = spec((1, 8, 2048, 128), BF16)
    hlo = compile_for_chip(lambda q, k, v: flash_attention(q, k, v, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in hlo


def test_causal_attention_mla_train_width(spec):
    """MiniCPM3's MLA training attention: batch 2, 40 heads, 4096 tokens,
    q/k head dim 96, v head dim 64, bf16; forward and the q/k/v gradient."""
    qk, v = spec((2, 40, 4096, 96), BF16), spec((2, 40, 4096, 64), BF16)

    def fwd(q, k, v):
        return causal_flash_attention(q, k, v, interpret=False)

    def grad(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(F32).sum(), (0, 1, 2))(q, k, v)

    assert "tpu_custom_call" in compile_for_chip(fwd, qk, qk, v)
    hlo = compile_for_chip(grad, qk, qk, v)
    for kernel in ("causal_flash_fwd", "causal_flash_dq", "causal_flash_dkv"):
        assert re.search(rf"%[\w.]*{kernel}[\w.]* = .*tpu_custom_call", hlo), kernel


def test_causal_attention_mistral_prefill_width(spec):
    """Mistral-7B prefill: batch 8, 32 query heads over 8 KV heads of 128,
    4096 tokens, bf16, forward."""
    hlo = compile_for_chip(
        lambda q, k, v: causal_flash_attention(q, k, v, interpret=False),
        spec((8, 32, 4096, 128), BF16), spec((8, 8, 4096, 128), BF16),
        spec((8, 8, 4096, 128), BF16),
    )
    assert "tpu_custom_call" in hlo


def test_rmsnorm(spec):
    hlo = compile_for_chip(
        lambda x, w: rmsnorm_pallas(x, w, interpret=False),
        spec((4096, 2560), BF16), spec((2560,), BF16),
    )
    assert "tpu_custom_call" in hlo


def test_lru_scan_recurrentgemma_width(spec):
    """RG-LRU width 2560 (recurrentgemma-2b); the model feeds f32 gates."""
    ab = spec((1, 2048, 2560), F32)
    hlo = compile_for_chip(
        lambda a, b, h0: lru_pallas(a, b, h0, interpret=False), ab, ab, spec((1, 2560), F32)
    )
    assert "tpu_custom_call" in hlo


def test_wkv6_rwkv6_heads(spec):
    """64 heads of 64 (rwkv6-7b), bf16 activations, f32 state."""
    x = spec((1, 64, 2048, 64), BF16)
    hlo = compile_for_chip(
        lambda r, k, v, w, u, s0: wkv6_pallas(r, k, v, w, u, s0, interpret=False),
        x, x, x, x, spec((64, 64), BF16), spec((1, 64, 64, 64), F32),
    )
    assert "tpu_custom_call" in hlo


def test_moe_gating_deepseek_routing(spec):
    """64 experts, top-6, 1024-token dispatch groups (deepseek-moe-16b)."""
    capacity = math.ceil(1024 * 6 / 64 * 1.25)
    hlo = compile_for_chip(
        lambda x: moe_gating_pallas(x, top_k=6, capacity=capacity, interpret=False),
        spec((4, 1024, 64), F32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("pos_shape", [(), (8,)], ids=["scalar", "per-row"])
@pytest.mark.parametrize("arch", ["llava-next-mistral-7b", "minicpm3-4b"])
def test_decode_step_updates_a_donated_cache_in_place(spec, arch, pos_shape):
    """A decode step at real widths (GQA 8x128 and MLA 256+32, two layers,
    batch 8, 4352 positions) with its cache donated, as the engines jit it:
    the output is the cache's own buffer, no temporary holds a copy of it,
    and the step reads the cache once and writes only the new token (bytes
    accessed below the weights plus 1.5 caches; a step that rewrites the
    cache moves three). Every instruction that yields a whole cache leaf is
    a write under ``kv_cache_write``, unfused with other work, which is what
    ``decode_cache_write_ms`` reads."""
    model = build_model(get_config(arch).replace(n_layers=2, n_patches=0))
    shapes = lambda tree: jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), tree)  # noqa: E731
    params = shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = shapes(model.cache_defs_fn(8, 4352))
    compiled = jax.jit(model.decode_fn, donate_argnums=(1,)).lower(
        params, cache, spec((8, 1), jnp.int32), spec(pos_shape, jnp.int32)).compile()
    nbytes = lambda tree: sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))  # noqa: E731
    memory, cost = compiled.memory_analysis(), compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert memory.alias_size_in_bytes == nbytes(cache)
    assert memory.temp_size_in_bytes < 0.1 * nbytes(cache)
    assert cost["bytes accessed"] < nbytes(params) + 1.5 * nbytes(cache)
    hlo = compiled.as_text()
    for leaf in jax.tree_util.tree_leaves(cache):
        shape = re.escape(f"bf16[{','.join(map(str, leaf.shape))}]")
        writes = re.findall(rf"= {shape}\{{[^}}]*\}} (?:fusion|dynamic-update-slice)\(.*", hlo)
        assert writes and all("/kv_cache_write/" in w for w in writes), leaf.shape
