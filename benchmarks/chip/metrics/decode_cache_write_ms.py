"""Device time of the cache update per decode step, in ms: self time of the
ops under the named scope ``kv_cache_write`` (``span_reduce``'s
``scope_incl_s``) over the count of ``nbi:serve.decode_step`` spans in the
traced window."""


def read(run: dict):
    spans = run.get("spans") or {}
    steps = spans.get("span_stats", {}).get("nbi:serve.decode_step", {}).get("count")
    t = spans.get("scope_incl_s", {}).get("kv_cache_write")
    return None if not steps or t is None else 1e3 * t / steps
