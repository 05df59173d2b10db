"""Device time of attention per train step, in ms: self time of the ops
under the named scope ``attention``, nested scopes and the fused kernels
included (``span_reduce``'s ``scope_incl_s``), over the train step
program's runs on the device in the traced window (``program_runs``; a
step still queued at the window's edge has not run)."""


def read(run: dict):
    spans = run.get("spans") or {}
    steps = spans.get("program_runs", {}).get("train_step")
    t = spans.get("scope_incl_s", {}).get("attention")
    return None if not steps or t is None else 1e3 * t / steps
