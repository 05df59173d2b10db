"""Compile each cell's programs for a described TPU v5e, with no chip attached.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/aot_rehearsal.py [cell ...]

For every cell of ``BENCHMARK.json`` (or the ones named) this builds the
program's own jitted train step, or its prefill (one per prompt length) and
decode step, at the cell's shapes on ``chips`` devices of a ``v5e:2x2``
topology, compiles them with the TPU compiler and prints each program's
``memory_analysis()``. A program that does not fit in a chip's memory, or
that the compiler refuses, fails here instead of on the chip. The Pallas
kernels are lowered for Mosaic, as on the chip, and not for the interpreter
that ``default_interpret`` would pick on this CPU.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

GB = 1e9


def _report(label: str, compiled, t0: float) -> dict:
    mem = compiled.memory_analysis()
    row = {
        "program": label,
        "compile_s": round(time.perf_counter() - t0, 1),
        "argument_gb": mem.argument_size_in_bytes / GB,
        "output_gb": mem.output_size_in_bytes / GB,
        "alias_gb": mem.alias_size_in_bytes / GB,
        "temp_gb": mem.temp_size_in_bytes / GB,
    }
    row["peak_gb"] = row["argument_gb"] + row["output_gb"] - row["alias_gb"] + row["temp_gb"]
    print(" ".join(f"{k}={v:.2f}" if isinstance(v, float) else f"{k}={v}"
                   for k, v in row.items()), flush=True)
    return row


def _sharded(tree, shardings):
    import jax

    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings
    )


def rehearse_train(cell, devices) -> list:
    import jax

    from chipbench.spec import arch_config
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model
    from repro.optim import make_optimizer
    from repro.optim.schedules import cosine_warmup
    from repro.parallel.sharding import resolve_tree, rules_for
    from repro.training.steps import abstract_train_state, make_train_step, train_state_logical

    t = cell.traffic
    cfg = arch_config(cell.config)
    model = build_model(cfg)
    mesh = make_host_mesh(devices=devices)
    opt = make_optimizer("adamw", lr=cosine_warmup(t["lr"], t["warmup"], t["steps"]))
    rules = rules_for(cfg, mesh, param_defs=model.param_defs, batch_size=t["global_batch"],
                      extra_dims={"heads": cfg.n_heads})
    state_sh = resolve_tree(mesh, train_state_logical(model, opt), rules)
    batch_sh = resolve_tree(mesh, model.train_input_logical(), rules)
    state = _sharded(abstract_train_state(model, opt), state_sh)
    batch = _sharded(model.train_inputs(t["global_batch"], t["seq"]), batch_sh)
    step = jax.jit(make_train_step(model, opt, rules, mesh), in_shardings=(state_sh, None),
                   out_shardings=(state_sh, None), donate_argnums=(0,))
    t0 = time.perf_counter()
    with mesh:
        compiled = step.lower(state, batch).compile()
    return [_report(f"{cell.name} train_step", compiled, t0)]


def rehearse_serve(cell, devices) -> list:
    import jax
    import jax.numpy as jnp

    from chipbench.spec import arch_config
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model
    from repro.parallel.sharding import resolve_tree, rules_for
    from repro.training.steps import make_prefill_step, make_serve_step

    t = cell.traffic
    cfg = arch_config(cell.config)
    model = build_model(cfg)
    mesh = make_host_mesh(devices=devices)
    B, S = t["batch"], t["max_seq"]
    rules = rules_for(cfg, mesh, param_defs=model.param_defs, batch_size=B,
                      extra_dims={"kv_seq": S, "heads": cfg.n_heads})
    params = _sharded(model.abstract_params(),
                      resolve_tree(mesh, model.param_logical(), rules))
    one = jax.sharding.SingleDeviceSharding(devices[0])
    rows = []
    prefill = jax.jit(make_prefill_step(model, rules, mesh))
    for length in t["prompt_lengths"]:
        tokens = jax.ShapeDtypeStruct((B, length), jnp.int32, sharding=one)
        t0 = time.perf_counter()
        with mesh:
            compiled = prefill.lower(params, {"tokens": tokens}).compile()
        rows.append(_report(f"{cell.name} prefill[{length}]", compiled, t0))
    cache = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
                                   model.cache_defs_fn(B, S))
    decode = jax.jit(make_serve_step(model, rules, mesh))
    t0 = time.perf_counter()
    with mesh:
        compiled = decode.lower(
            params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
        ).compile()
    rows.append(_report(f"{cell.name} decode", compiled, t0))
    return rows


def main(argv: list[str]) -> int:
    from jax.experimental import topologies

    import repro.kernels.causal_attention as causal_attention
    from chipbench.spec import load_spec, resolve

    causal_attention.default_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    spec = load_spec()
    names = argv or [w["name"] for w in spec["workloads"]]
    for name in names:
        cell = resolve(name, spec=spec)
        devices = list(topo.devices[: cell.chips])
        kind = cell.traffic["kind"]
        (rehearse_train if kind == "train" else rehearse_serve)(cell, devices)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
