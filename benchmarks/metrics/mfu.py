"""Forward+backward FLOPs the model requires per image (flops.py, no recompute) x images/s/chip over the chip's bf16 peak."""

from benchmarks import flops


def read(record: dict):
    if "flops_per_image" not in record:
        return None
    rate = record["images"] / record["window_s"] / record["chips"]
    return 100.0 * record["flops_per_image"] * rate / flops.peak(record["device_kind"])
