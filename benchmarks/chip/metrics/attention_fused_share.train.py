"""Share of the full-sequence attention calls the program traced that take
the fused causal kernel, in %: ``nbi_attention_path_total{path=fused}`` over
the counter's sum over paths (the program's counter registry, on in traced
runs)."""


def read(run: dict):
    paths = (run.get("counters") or {}).get("nbi_attention_path_total") or {}
    total = sum(paths.values())
    return 100.0 * paths.get("path=fused", 0.0) / total if total else None
