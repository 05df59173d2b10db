"""Named scopes in the model step change op metadata only.

For a GQA and an MLA configuration, the train step, the prefill and the
decode step are compiled with the model's ``jax.named_scope`` names and
with every scope replaced by a no-op. Once the metadata is stripped the
compiled programs must be the same, and the scope names must be in the
``op_name`` metadata of the scoped one.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model
from repro.optim import make_optimizer
from repro.parallel.sharding import rules_for
from repro.training.steps import (
    init_train_state,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)

CONFIGS = {"gqa": "codeqwen1.5-7b", "mla": "minicpm3-4b"}
SCOPES = {
    "train": {"embed", "attention", "mlp", "lm_head", "loss", "grad_cast", "clip", "optimizer"},
    "prefill": {"embed", "attention", "mlp", "lm_head"},
    "decode": {"embed", "decode_attention", "kv_cache_write", "mlp", "lm_head"},
}
# XLA's debug tables: source files, functions and stack frames of each op
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
METADATA = re.compile(r",?\s*metadata=\{[^}]*\}")
NAME = re.compile(r"%[\w.\-]+")


def strip(hlo: str) -> str:
    """Compiled HLO text without metadata and debug tables, with every
    instruction renamed by its first appearance (XLA numbers names from a
    counter the tracing path can advance without changing the program)."""
    lines, table = [], False
    for line in hlo.splitlines():
        if line in TABLES:
            table = True
        elif table and not line.strip():
            table = False
        elif not table:
            lines.append(METADATA.sub("", line))
    names: dict = {}
    return NAME.sub(lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                    "\n".join(lines))


def compiled(family: str, program: str) -> str:
    cfg = get_smoke_config(CONFIGS[family]).replace(
        param_dtype="bfloat16", dtype="bfloat16", remat="full")
    model = build_model(cfg)
    mesh = make_host_mesh()
    B, S, max_seq = 2, 16, 32
    rules = rules_for(cfg, mesh, param_defs=model.param_defs, batch_size=B,
                      extra_dims={"heads": cfg.n_heads, "kv_seq": max_seq})
    opt = make_optimizer(cfg.optimizer, lr=1e-3)
    state = jax.eval_shape(lambda r: init_train_state(model, opt, r), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    with mesh:
        if program == "train":
            fn = make_train_step(model, opt, rules, mesh)
            args = (state, {"tokens": tokens, "labels": tokens})
        elif program == "prefill":
            fn = make_prefill_step(model, rules, mesh)
            args = (state["params"], {"tokens": tokens})
        else:
            fn = make_serve_step(model, rules, mesh)
            args = (state["params"], model.cache_defs_fn(B, max_seq),
                    jax.ShapeDtypeStruct((B, 1), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32))
        return jax.jit(fn).lower(*args).compile().as_text()


def op_name_scopes(hlo: str) -> set:
    parts = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        parts.update(re.split(r"[/()]", op_name))
    return parts


@pytest.mark.parametrize("program", sorted(SCOPES))
@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_scopes_change_no_computation(family, program, monkeypatch):
    scoped = compiled(family, program)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = compiled(family, program)
    assert strip(scoped) == strip(plain)
    assert SCOPES[program] <= op_name_scopes(scoped)
    assert not SCOPES[program] & op_name_scopes(plain)


def test_strip_keeps_the_computation():
    a = "%fusion.3 = f32[2]{0} add(%p.1, %p.2), metadata={op_name=\"jit(f)/mlp/add\"}"
    b = "%fusion.7 = f32[2]{0} add(%p.4, %p.5)"
    assert strip(a) == strip(b)
    assert strip(a) != strip(b.replace("add(", "multiply("))
