"""The fused causal attention kernel against the chunked XLA path, and the
dispatch that picks between them.

The kernels run in the Pallas interpreter here; ``tests/test_tpu_compile.py``
compiles them for a described v5e at the benchmark cells' widths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.causal_attention import (
    block_size,
    causal_flash_attention,
    dkv_heads,
    heads_per_step,
)
from repro.kernels.ref import attention_ref
from repro.models import common
from repro.models.common import attention_chunked, attention_path
from repro.models.registry import build_model
from repro.models.transformer import gqa_attention, mla_attention
from repro.obs import metrics

RNG = np.random.default_rng(7)


def rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def chunked(q, k, v):
    G = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    return attention_chunked(q, k, v, causal=True, kv_chunk=256)


@pytest.mark.parametrize(
    "B,Hq,Hkv,S,dk,dv",
    [
        pytest.param(2, 2, 2, 256, 48, 32, id="mla-qk48-v32-one-block"),
        pytest.param(1, 2, 2, 1024, 24, 16, id="mla-qk24-v16-two-blocks-of-512"),
        pytest.param(1, 4, 1, 640, 32, 32, id="gqa-g4-five-blocks-of-128"),
        pytest.param(1, 8, 2, 384, 32, 32, id="gqa-g4-one-block-of-384"),
        pytest.param(1, 8, 1, 256, 16, 16, id="gqa-g8-group-split-in-two-for-dkv"),
    ],
)
def test_fused_matches_chunked(B, Hq, Hkv, S, dk, dv):
    """Forward output and q/k/v gradients, f32, against attention_chunked
    (and the output against the naive oracle)."""
    q, k, v = rand((B, Hq, S, dk)), rand((B, Hkv, S, dk)), rand((B, Hkv, S, dv))
    w = rand((B, Hq, S, dv))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * w).sum()

    out, ref = causal_flash_attention(q, k, v), chunked(q, k, v)
    assert out.shape == (B, Hq, S, dv)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(out, attention_ref(q, k, v), atol=2e-5, rtol=1e-5)
    grads = jax.grad(loss(causal_flash_attention), (0, 1, 2))(q, k, v)
    ref_grads = jax.grad(loss(chunked), (0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", grads, ref_grads):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=1e-4, err_msg=name)


def test_fused_bf16_stays_bf16_and_close_to_f32():
    """bf16 operands in, bf16 out, within bf16 rounding of the f32 result."""
    q, k, v = (rand((1, 4, 256, 64), jnp.bfloat16) for _ in range(3))
    out = causal_flash_attention(q, k[:, :1], v[:, :1])
    assert out.dtype == jnp.bfloat16
    f32 = [x.astype(jnp.float32) for x in (q, k[:, :1], v[:, :1])]
    np.testing.assert_allclose(out.astype(jnp.float32), chunked(*f32), atol=3e-2)


@pytest.mark.parametrize("seq,block", [(128, 128), (384, 384), (640, 128), (1024, 512), (4096, 512)])
def test_block_size_from_sequence(seq, block):
    assert block_size(seq) == block


@pytest.mark.parametrize(
    "heads,kv_heads,per_step,dkv",
    [
        (40, 40, 4, (4, 4, 1)),  # MiniCPM3 MLA: four heads a step
        (32, 8, 4, (1, 4, 1)),   # Mistral: a group of four
        (8, 4, 4, (2, 4, 1)),    # two groups of two
        (36, 4, 3, (1, 3, 3)),   # StarCoder2: groups of 9 in chunks of 3
        (96, 8, 4, (1, 4, 3)),   # Mistral-Large: groups of 12 in chunks of 4
        (6, 6, 3, (3, 3, 1)),
    ],
)
def test_heads_a_step_from_the_head_counts(heads, kv_heads, per_step, dkv):
    G = heads // kv_heads
    assert heads_per_step(heads, G) == per_step
    assert dkv_heads(kv_heads, G) == dkv


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


@pytest.fixture
def registry():
    reg = metrics.enable(metrics.MetricsRegistry())
    yield reg
    metrics.disable()


def paths(reg) -> dict:
    fam = reg.get("nbi_attention_path_total")
    return {labels["path"]: child.value for labels, child in fam.samples()} if fam else {}


@pytest.mark.parametrize(
    "platform,S,kwargs,path",
    [
        pytest.param("tpu", 256, {}, "fused", id="tpu-causal-aligned"),
        pytest.param("cpu", 256, {}, "chunked", id="cpu"),
        pytest.param("tpu", 256, {"window": 64}, "chunked", id="window"),
        pytest.param("tpu", 256, {"logit_cap": 30.0}, "chunked", id="logit-cap"),
        pytest.param("tpu", 200, {}, "chunked", id="ragged-seq"),
        pytest.param("tpu", 256, {"q_offset": 128}, "chunked", id="q-offset"),
        pytest.param("tpu", 256, {"causal": False}, "chunked", id="not-causal"),
    ],
)
def test_dispatch(monkeypatch, registry, platform, S, kwargs, path):
    monkeypatch.setattr(common, "attention_platform", lambda: platform)
    q = jax.ShapeDtypeStruct((1, 4, S, 32), jnp.bfloat16)
    assert attention_path(q, q, **kwargs) == path
    assert paths(registry) == {path: 1.0}


def test_dispatch_needs_equal_lengths(monkeypatch, registry):
    monkeypatch.setattr(common, "attention_platform", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, 4, 256, 32), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 4, 512, 32), jnp.float32)
    assert attention_path(q, k) == "chunked"
    assert attention_path(q, k, fallback="pallas") == "pallas"
    assert paths(registry) == {"chunked": 1.0, "pallas": 1.0}


def test_platform_is_the_mesh_devices():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    common.set_logical_rules({}, mesh)
    try:
        assert common.attention_platform() == jax.devices()[0].platform
    finally:
        common.set_logical_rules(None, None)
    assert common.attention_platform() == jax.default_backend()


@pytest.mark.parametrize(
    "arch,attn", [("codeqwen1.5-7b", gqa_attention), ("minicpm3-4b", mla_attention)],
    ids=["gqa", "mla"],
)
def test_model_attention_takes_the_fused_path_on_tpu(monkeypatch, registry, arch, attn):
    """The model's attention on a TPU-causal-aligned shape counts one fused
    call and gives the chunked path's output; on the CPU it counts chunked."""
    cfg = get_smoke_config(arch)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    layer = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["attn"])
    x = rand((2, 128, cfg.d_model))
    positions = jnp.arange(128)
    on_cpu = attn(layer, x, cfg, positions)
    monkeypatch.setattr(common, "attention_platform", lambda: "tpu")
    on_tpu = attn(layer, x, cfg, positions)
    assert paths(registry) == {"chunked": 1.0, "fused": 1.0}
    np.testing.assert_allclose(on_tpu, on_cpu, atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# On a mesh: the kernel runs per device under shard_map
# ---------------------------------------------------------------------------

_MESH_CHILD = """
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import common
from repro.models.registry import build_model
from repro.models.transformer import gqa_attention, mla_attention
from repro.obs import metrics
from repro.parallel.sharding import rules_for, with_rules

out = {}
cases = {
    # 2 KV heads cannot split 4 ways: the fused path expands them first
    "gqa-kv-heads-unsplit": ("codeqwen1.5-7b", gqa_attention, (1, 4), {"n_kv_heads": 2}),
    "mla-batch-and-heads-split": ("minicpm3-4b", mla_attention, (2, 2), {}),
}
for name, (arch, attn, shape, over) in cases.items():
    cfg = get_smoke_config(arch).replace(**over)
    model = build_model(cfg)
    layer = jax.tree_util.tree_map(lambda a: a[0], model.init(jax.random.PRNGKey(0))["blocks"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 128, cfg.d_model))
    pos = jnp.arange(128)
    mesh = make_host_mesh(shape)
    rules = rules_for(cfg, mesh, param_defs=model.param_defs, batch_size=4,
                      extra_dims={"heads": cfg.n_heads})

    def run():
        f = with_rules(lambda l, x: attn(l, x, cfg, pos), rules, mesh)
        g = with_rules(jax.grad(lambda l, x: attn(l, x, cfg, pos).sum(), (0, 1)), rules, mesh)
        with mesh:
            return jax.jit(f)(layer, x), jax.jit(g)(layer, x)

    ref, ref_g = run()
    reg = metrics.enable(metrics.MetricsRegistry())
    common.attention_platform = lambda: "tpu"
    fused, fused_g = run()
    paths = {l["path"]: c.value for l, c in reg.get("nbi_attention_path_total").samples()}
    gaps = [float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))
            for a, b in zip(jax.tree.leaves(fused_g), jax.tree.leaves(ref_g))]
    out[name] = {"rules": [rules["batch"], rules["heads"], rules["kv_heads"]], "paths": paths,
                 "out_gap": float(jnp.max(jnp.abs(fused - ref))), "grad_gap": max(gaps)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def on_four_devices():
    """The sharded cases run in a child with four virtual CPU devices."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src))
    res = subprocess.run([sys.executable, "-c", _MESH_CHILD], env=env,
                         capture_output=True, text=True, timeout=280)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "case,rules",
    [("gqa-kv-heads-unsplit", ["data", "model", None]),
     ("mla-batch-and-heads-split", ["data", "model", "model"])],
)
def test_fused_on_a_mesh_matches_chunked(on_four_devices, case, rules):
    got = on_four_devices[case]
    assert got["rules"] == rules
    assert got["paths"] == {"fused": 2.0}  # the forward and the gradient program
    assert got["out_gap"] < 1e-5
    assert got["grad_gap"] < 1e-5
