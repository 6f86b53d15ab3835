"""The gated short convolutions' elementwise part's share of its roofline in the traced window: the least time the chip could take for what the algorithm requires in one step (flops_conv_moe_lm.sconv_mix_step: the three gates' inputs read and y written forward, the same three and dy read and three gradients written backward, no recompute; the larger of operations over the bf16 peak and bytes over the bandwidth peak, peaks.json: the bytes bind) over the device time of the whole part, a kernel or XLA's fusions, so that a later kernel is read on the same work. None where the record states no such work or the step has no such part."""

from benchmarks import flops, scope_reduce


def read(record: dict):
    todo = record.get("kernel_work", {}).get("sconv_mix")
    ms = scope_reduce.part_ms(record, "trunk_sconv_mix")
    if not todo or not ms:
        return None
    kind = record["device_kind"]
    least_s = max(todo["flops"] / flops.peak(kind),
                  todo["bytes"] / flops.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / (ms / 1e3)
