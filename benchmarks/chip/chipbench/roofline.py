"""A kernel's share of its roofline, from the values of a traced run."""

from __future__ import annotations

import re


def kernel_share(run: dict) -> float | None:
    """The least time the chip could take for the calls in the traced
    window of the kernels ``run["kernel_work"]`` names, over their device
    self time, in %. A call's least time is the larger of its operations
    over the bf16 peak and its bytes over HBM bandwidth
    (``flops.causal_kernel_work``); calls and self time are
    ``span_reduce``'s ``op_calls`` and ``op_self_s`` of every instruction
    that bears the kernel's name (``causal_flash_fwd.17``,
    ``transpose_jvp_causal_flash_dq__.3``). None where none ran."""
    work, spans, peak = run.get("kernel_work") or {}, run.get("spans") or {}, run.get("peaks")
    named = {k: re.compile(rf"(?<![a-z0-9]){k}(?![a-z0-9])") for k in work}
    least = seconds = 0.0
    for op, t in spans.get("op_self_s", {}).items():
        kernel = next((k for k, pat in named.items() if pat.search(op)), None)
        if kernel is None:
            continue
        flops, moved = work[kernel]
        least += spans["op_calls"][op] * max(flops / peak["bf16_flops_per_s"],
                                             moved / peak["hbm_bytes_per_s"])
        seconds += t
    return 100.0 * least / seconds if seconds else None
