"""The sliding-window layers' causal kernels' share of their roofline in the traced window: the algorithm's operations and bytes for one step (flops_gqa_lm.swa_core_step: the pairs the mask keeps, once, no recompute; what the kernels compute in blocks beyond them shows as lost share) against v5e's peaks, over the kernels' device time in the part swa_core (kernel_roofline)."""

from benchmarks import kernel_roofline


def read(record: dict):
    return kernel_roofline.share(record, "swa_core", "trunk_swa_core")
