"""Tests for meters, MFU math, and the metric logger."""

import json

import numpy as np

from jumbo_mae_tpu_tpu.models import preset
from jumbo_mae_tpu_tpu.models.config import DecoderConfig
from jumbo_mae_tpu_tpu.utils import (
    AverageMeter,
    MetricLogger,
    StepTimer,
    classify_flops_per_image,
    encoder_flops_per_image,
    mfu_report,
    pretrain_flops_per_image,
)


def test_average_meter_means_and_latest():
    m = AverageMeter()
    m.update({"loss": 1.0, "learning_rate": 0.1})
    m.update({"loss": 3.0, "learning_rate": 0.2})
    out = m.summary("train/")
    assert out["train/loss"] == 2.0
    assert out["train/learning_rate"] == 0.2
    assert m.summary() == {}  # buffer cleared


def test_average_meter_accepts_arrays():
    m = AverageMeter()
    m.update({"loss": np.float32(2.5)})
    assert m.summary()["loss"] == 2.5


def test_flops_masked_encoder_cheaper():
    cfg = preset("vit_b16", mask_ratio=0.75, labels=None)
    masked = encoder_flops_per_image(cfg, masked=True)
    full = encoder_flops_per_image(cfg, masked=False)
    assert masked < 0.5 * full  # 75% masking cuts well over half the FLOPs
    assert masked > 0


def test_pretrain_flops_vs_known_scale():
    """ViT-L/16 MAE fwd+bwd should land in the right order of magnitude
    (~100 GFLOPs/image: ViT-L full fwd is ~62 GFLOPs; masked enc + 8×512
    decoder fwd ≈ 33 GFLOPs, ×3 for training)."""
    enc = preset("vit_l16", mask_ratio=0.75, labels=None)
    dec = DecoderConfig(layers=8, dim=512, heads=16)
    flops = pretrain_flops_per_image(enc, dec, training=True)
    assert 5e10 < flops < 3e11


def test_classify_flops_includes_head():
    with_head = classify_flops_per_image(preset("vit_b16", labels=1000))
    without = classify_flops_per_image(preset("vit_b16", labels=None))
    assert with_head > without


def test_mfu_report_math():
    r = mfu_report(1e12, 100.0, peak_tflops=200.0)
    assert np.isclose(r.achieved_tflops, 100.0)
    assert np.isclose(r.mfu, 0.5)


def test_metric_logger_jsonl(tmp_path):
    logger = MetricLogger(tmp_path, name="t", config={"a": 1}, use_wandb=False)
    logger.log({"loss": 1.5}, step=3)
    logger.log({"loss": 2.5}, step=4)
    logger.close()
    lines = (tmp_path / "t-metrics.jsonl").read_text().strip().splitlines()
    assert len(lines) == 2
    rec = json.loads(lines[0])
    assert rec["step"] == 3 and rec["loss"] == 1.5
    assert json.loads((tmp_path / "t-config.json").read_text()) == {"a": 1}


def test_metric_logger_wandb_plumbing(tmp_path, monkeypatch):
    """project/entity/tags/resume-id reach wandb.init; logs are forwarded."""
    import sys
    import types

    calls = {}

    class _Run:
        def log(self, metrics, step=None):
            calls.setdefault("logged", []).append((dict(metrics), step))

        def finish(self):
            calls["finished"] = True

    def _init(**kw):
        calls["init"] = kw
        return _Run()

    stub = types.ModuleType("wandb")
    stub.init = _init
    monkeypatch.setitem(sys.modules, "wandb", stub)

    logger = MetricLogger(
        tmp_path,
        name="t",
        config={"a": 1},
        wandb_project="proj",
        wandb_entity="team",
        wandb_tags=("vit", "mae"),
        wandb_id="run-123",
    )
    logger.log({"loss": 1.0}, step=1)
    logger.close()

    assert calls["init"] == {
        "name": "t",
        "config": {"a": 1},
        "project": "proj",
        "entity": "team",
        "tags": ["vit", "mae"],
        "id": "run-123",
        "resume": "allow",
    }
    assert calls["logged"] == [({"loss": 1.0}, 1)]
    assert calls["finished"]


def test_metric_logger_wandb_absent_falls_back(tmp_path, monkeypatch):
    import builtins
    import sys

    monkeypatch.delitem(sys.modules, "wandb", raising=False)
    real_import = builtins.__import__

    def no_wandb(name, *a, **k):
        if name == "wandb":
            raise ImportError("no wandb")
        return real_import(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_wandb)
    logger = MetricLogger(tmp_path, name="fb", use_wandb=True)
    logger.log({"x": 1.0}, step=1)
    logger.close()
    assert (tmp_path / "fb-metrics.jsonl").exists()


def test_metric_logger_disabled(tmp_path):
    logger = MetricLogger(tmp_path, name="off", enabled=False)
    logger.log({"x": 1})
    logger.close()
    assert not (tmp_path / "off-metrics.jsonl").exists()


def test_step_timer():
    t = StepTimer(warmup_steps=1)
    for _ in range(5):
        t.tick()
    assert t.steps_per_sec is not None and t.steps_per_sec > 0


def test_param_summary():
    """Startup parameter table (parity: the reference's module.tabulate
    pre-flight print): per-subtree rows + an exact total."""
    import numpy as np

    from jumbo_mae_tpu_tpu.utils import param_summary

    params = {
        "encoder": {
            "block_0": {"kernel": np.zeros((4, 8), np.float32)},
            "block_1": {"kernel": np.zeros((4, 8), np.float32)},
        },
        "head": {"kernel": np.zeros((8, 10), np.float32), "bias": np.zeros(10)},
    }
    out = param_summary(params)
    assert "encoder/block_0" in out and "head" in out
    assert "total" in out and "154" in out  # 32 + 32 + 80 + 10


def test_detect_peak_tflops_device_kind_spellings(monkeypatch):
    """PJRT spells the e-variants 'lite' ('TPU v5 lite'). The CPU backend has
    no peak (None: a CPU count is not a device rate); an accelerator whose
    kind is not in the table is an error, never a default."""
    import jax
    import pytest

    from jumbo_mae_tpu_tpu.utils.mfu import detect_peak_tflops

    class _Dev:
        def __init__(self, kind, platform="tpu"):
            self.device_kind = kind
            self.platform = platform

    cases = {
        "TPU v5 lite": 197.0,
        "TPU v5e": 197.0,
        "TPU v5p": 459.0,
        "TPU v6 lite": 918.0,
        "TPU v4": 275.0,
    }
    for kind, want in cases.items():
        monkeypatch.setattr(jax, "devices", lambda k=kind: [_Dev(k)])
        assert detect_peak_tflops() == want, kind
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("cpu", "cpu")])
    assert detect_peak_tflops() is None
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v9 mystery")])
    with pytest.raises(ValueError, match="TPU v9 mystery"):
        detect_peak_tflops()
