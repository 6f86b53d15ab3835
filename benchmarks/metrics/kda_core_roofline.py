"""The linear-attention cores' share of their roofline in the traced window: the least time the chip could take for what the algorithm requires in one step (flops_hybrid_lm.kda_core_step: the recurrence's three products, operands and gradients through HBM once; the larger of operations over the bf16 peak and bytes over the bandwidth peak, peaks.json) over the device time of the whole part, kernels or not, so that a later kernel is read on the same work. None where the record states no such work or the step has no such part."""

from benchmarks import flops, scope_reduce


def read(record: dict):
    todo = record.get("kernel_work", {}).get("kda_core")
    ms = scope_reduce.part_ms(record, "trunk_kda_core")
    if not todo or not ms:
        return None
    kind = record["device_kind"]
    least_s = max(todo["flops"] / flops.peak(kind),
                  todo["bytes"] / flops.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / (ms / 1e3)
