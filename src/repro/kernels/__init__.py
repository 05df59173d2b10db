"""Pallas TPU kernels for the perf-critical compute hot-spots.

| kernel              | hot-spot                          | oracle (ref.py)  |
|---------------------|-----------------------------------|------------------|
| causal_attention.py | causal self-attention, fwd + bwd  | attention_ref    |
| flash_attention.py  | prefill/train attention           | attention_ref    |
| rwkv6_scan.py       | RWKV6 WKV recurrence (chunked)    | wkv6_ref         |
| rglru_scan.py       | RG-LRU linear recurrence          | lru_ref          |
| rmsnorm.py          | fused norm (memory-bound)         | rmsnorm_ref      |
| moe_gating.py       | softmax→top-k→capacity routing    | moe_gating_ref   |

``causal_attention.py`` is on the normal path: the models take it on a TPU
for causal full-sequence self-attention (``models.common.attention_path``).
``ops.py`` is the dispatch layer of the others (Pallas ↔ XLA, custom_vjp
training path); models select it with ``ArchConfig.use_pallas``.
"""

from . import ops, ref
from .flash_attention import flash_attention
from .moe_gating import moe_gating_pallas
from .rglru_scan import lru_pallas
from .rmsnorm import rmsnorm_pallas
from .rwkv6_scan import wkv6_pallas

__all__ = [
    "ops", "ref",
    "flash_attention", "lru_pallas", "moe_gating_pallas", "rmsnorm_pallas",
    "wkv6_pallas",
]
