"""The grouped-product kernels' share of their roofline in the traced window: the operations and bytes of the rows that landed on held experts (flops_lm.experts_step, no recompute) against v5e's peaks, over the kernels' device time (kernel_roofline)."""

from benchmarks import kernel_roofline


def read(record: dict):
    return kernel_roofline.share(record, "experts", "trunk_experts", "mtp_experts")
