from jumbo_mae_tpu_tpu.utils.logging import MetricLogger, StepTimer
from jumbo_mae_tpu_tpu.utils.meters import AverageMeter
from jumbo_mae_tpu_tpu.utils.mfu import (
    PEAK_TFLOPS,
    classify_flops_per_image,
    detect_peak_tflops,
    encoder_flops_per_image,
    mfu_report,
    pretrain_flops_per_image,
)
from jumbo_mae_tpu_tpu.utils.profiling import trace
from jumbo_mae_tpu_tpu.utils.summary import param_summary

__all__ = [
    "AverageMeter",
    "MetricLogger",
    "PEAK_TFLOPS",
    "StepTimer",
    "classify_flops_per_image",
    "detect_peak_tflops",
    "encoder_flops_per_image",
    "mfu_report",
    "param_summary",
    "pretrain_flops_per_image",
    "trace",
]
