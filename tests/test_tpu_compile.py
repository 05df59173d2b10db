"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e chip.

Interpret-mode tests (``test_kernels.py``) hold the kernels to their oracles
but accept block shapes and primitives that Mosaic refuses. These compile
each kernel at a real model width with ``interpret=False`` for a v5e that is
described, not attached; nothing runs. The topology is described inside a
fixture only: loading the TPU compiler while a module is imported would give
pytest-xdist workers different test lists.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gating import moe_gating_pallas
from repro.kernels.rglru_scan import lru_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.rwkv6_scan import wkv6_pallas

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
