"""The block-diffusion core's kernels' share of their roofline in the traced window: the algorithm's operations and bytes for one step (flops_blockdiff_lm.core_step: the pairs the pattern shows, seq² + seq · B a head and sequence, once, no recompute; what the kernels compute in blocks beyond them shows as lost share) against v5e's peaks, over the kernels' device time in the part bd_core (kernel_roofline). None where the record states no such work or the step has no such part."""

from benchmarks import kernel_roofline


def read(record: dict):
    return kernel_roofline.share(record, "bd_core", "trunk_bd_core")
