"""The causal attention kernels' share of their roofline in the traced window: the algorithm's operations and bytes for one step (flops_lm.causal_core_step: lower triangle once, no recompute) against v5e's peaks, over the kernels' device time (kernel_roofline)."""

from benchmarks import kernel_roofline


def read(record: dict):
    return kernel_roofline.share(record, "attn_core", "trunk_attn_core", "mtp_attn_core")
