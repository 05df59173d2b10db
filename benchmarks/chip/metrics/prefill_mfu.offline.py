"""Model FLOP/s utilisation of prefill on the device, in %: a prefill call's
operations (``flops.prefill_flops`` of every row of the engine batch) times
the prefill program's runs in the traced window, over their device seconds
(``span_reduce``'s ``program_runs`` and ``program_s``), over the chips' bf16
peak. Read where every call has one prompt length."""


def read(run: dict):
    spans = run.get("spans") or {}
    runs = spans.get("program_runs", {}).get("prefill")
    secs = spans.get("program_s", {}).get("prefill")
    if not runs or not secs or not run.get("prefill_flops_per_call"):
        return None
    rate = run["prefill_flops_per_call"] * runs / secs
    return 100.0 * rate / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
