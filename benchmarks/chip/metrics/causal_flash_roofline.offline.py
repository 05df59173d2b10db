"""The fused causal attention kernel's share of its roofline in prefill, in
%: the forward kernel's calls' operations and bytes from shapes
(``flops.causal_kernel_work``) over its device self time in the traced
window (``roofline.kernel_share``)."""

from chipbench.roofline import kernel_share


def read(run: dict):
    return kernel_share(run)
