"""Device idle time inside the engine's token loop per decode step, in ms:
``span_reduce``'s idle seconds inside the ``nbi:serve.decode`` spans over
the count of ``nbi:serve.decode_step`` spans in the traced window. The
host's token round trip and dispatch between decode programs."""


def read(run: dict):
    spans = run.get("spans") or {}
    steps = spans.get("span_stats", {}).get("nbi:serve.decode_step", {}).get("count")
    idle = spans.get("idle_inside", {}).get("nbi:serve.decode")
    return None if not steps or idle is None else 1e3 * idle / steps
