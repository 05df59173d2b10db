"""The fused causal attention kernels' share of their roofline in training,
in %: forward (run twice a layer under full remat), dq and dkv, their calls'
operations and bytes from shapes (``flops.causal_kernel_work``) over their
device self time in the traced window (``roofline.kernel_share``)."""

from chipbench.roofline import kernel_share


def read(run: dict):
    return kernel_share(run)
