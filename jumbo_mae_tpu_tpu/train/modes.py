"""What a training mode is, in one table: which batch leaves its model
takes, what its eval step sums per sample, and by which metric its best
checkpoint is chosen. The step factories, the run configuration and the
trainer's batch plumbing read this table instead of each branching on the
mode's name."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Literal

StepMode = Literal["pretrain", "classify", "lm"]


@dataclass(frozen=True)
class ModeSpec:
    inputs: tuple[str, ...]  # batch leaves handed to the model, in order
    eval_outputs: dict  # eval metric -> the model output summed over valid samples
    best_metric: str  # the validation metric that picks the best checkpoint
    best_mode: Literal["min", "max"]


MODES: dict[str, ModeSpec] = {
    "pretrain": ModeSpec(("images",), {"loss": "loss_per_sample"}, "val/loss", "min"),
    "classify": ModeSpec(("images", "labels"), {"loss": "loss", "acc1": "acc1", "acc5": "acc5"},
                         "val/acc1", "max"),
    "lm": ModeSpec(("tokens",), {"loss": "loss_per_sample"}, "val/loss", "min"),
}

# a run's mode (``run.mode``) -> the step programs' mode
STEP_MODE: dict[str, str] = {"pretrain": "pretrain", "finetune": "classify",
                             "linear": "classify", "lm": "lm"}


def model_inputs(mode: str, batch: dict) -> tuple:
    return tuple(batch[name] for name in MODES[mode].inputs)
