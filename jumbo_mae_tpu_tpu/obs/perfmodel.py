"""Analytic capacity model: predicted step time / throughput / peak HBM.

MoFa-style (arXiv:2511.09837) roofline over the costs ``obs/costmodel.py``
extracts: each compiled program is bounded by the slowest of its compute
term (flops / peak flops), its memory term (bytes accessed / HBM bandwidth),
and — for sharded training — its collective term (comm bytes / ICI
bandwidth). The FSDP comms accounting follows "Memory and Bandwidth are All
You Need for FSDP" (arXiv:2504.03655): per step, each device all-gathers
the parameters twice (forward + backward) and reduce-scatters the grads
once, 3·P·(n−1)/n bytes over the slowest link; plain DP pays one grad
all-reduce, ≈ 2·P·(n−1)/n.

Two uses:

- **capacity planning** (ROADMAP item 5): given (model config, mesh, per-
  device batch, chip), predict step time / images-per-sec / peak HBM before
  burning chip time — ``predict_train_step`` works from the analytic FLOP
  counts alone, no backend needed;
- **live drift**: the train loop and the serving engine publish
  ``perf_predict_vs_measured{program}`` = measured / predicted each log
  window, so a run that detaches from its own roofline (input stall, host
  sync, background noise) is visible as a ratio, not a vibe.

Chip tables are public spec-sheet numbers. The CPU backend gets an
order-of-magnitude generic entry named ``cpu`` so the drift gauge still
publishes on the smoke backend — predictions there are for *plumbing*, not
accuracy. An accelerator that is not in the tables is an error.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass

from jumbo_mae_tpu_tpu.obs.mfu import PEAK_TFLOPS, normalize_device_kind

# HBM bandwidth GB/s per chip, by the same canonical generation keys as
# PEAK_TFLOPS (public spec sheets).
HBM_GBPS = {
    "v2": 700.0,
    "v3": 900.0,
    "v4": 1228.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6e": 1640.0,
}

# One-directional ICI link bandwidth GB/s per chip (approximate; the
# roofline wants the per-device collective drain rate).
ICI_GBPS = {
    "v2": 62.5,
    "v3": 70.0,
    "v4": 100.0,
    "v5e": 100.0,
    "v5p": 200.0,
    "v6e": 200.0,
}

# HBM capacity GiB per chip (public spec sheets) — the denominator of
# mem_doctor's OOM-risk estimate (measured peak / capacity).
HBM_GIB = {
    "v2": 8.0,
    "v3": 16.0,
    "v4": 32.0,
    "v5e": 16.0,
    "v5p": 95.0,
    "v6e": 32.0,
}

# Order-of-magnitude generic host CPU: keeps the predict-vs-measured gauge
# publishing on the smoke backend. Never used for capacity claims —
# capacity 0 means "no HBM to run out of", and consumers must skip the
# OOM-risk math rather than divide by a made-up number.
GENERIC_CPU = ("cpu", 0.5, 20.0, 10.0, 0.0)


@dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_tflops: float
    hbm_gbps: float
    ici_gbps: float
    hbm_bytes: float = 0.0  # capacity; 0 = unknown/not-an-accelerator


def chip_spec(kind: str) -> ChipSpec:
    """Resolve a PJRT ``device_kind`` string to its spec-table entry. The
    CPU backend's kind (``"cpu"``) gets the documented generic entry; any
    other kind that is not in the tables is an error, not a default."""
    canon = normalize_device_kind(kind)
    if canon is not None and canon in HBM_GBPS:
        return ChipSpec(
            canon,
            PEAK_TFLOPS[canon],
            HBM_GBPS[canon],
            ICI_GBPS[canon],
            HBM_GIB[canon] * 1024**3,
        )
    if str(kind).lower() == "cpu":
        return ChipSpec(*GENERIC_CPU)
    raise ValueError(
        f"device_kind {kind!r} has no chip-spec entry — add its spec-sheet "
        "numbers to obs/perfmodel.py (and obs/mfu.py) before modelling it"
    )


def detect_chip() -> ChipSpec:
    """ChipSpec of the current backend's first device."""
    import jax

    return chip_spec(jax.devices()[0].device_kind)


@dataclass
class PerfPrediction:
    """One program's roofline: the three terms and which one binds."""

    step_time_s: float
    throughput_per_sec: float  # items/s if batch given, else steps/s
    peak_hbm_bytes: float
    bound: str  # "compute" | "bandwidth" | "comm"
    t_compute_s: float
    t_memory_s: float
    t_comm_s: float


def roofline(
    flops: float,
    bytes_accessed: float,
    chip: ChipSpec,
    *,
    comm_bytes: float = 0.0,
    batch: int | None = None,
    peak_hbm_bytes: float = 0.0,
) -> PerfPrediction:
    """max(compute, memory, comm) lower bound on one program execution."""
    t_c = flops / (chip.peak_tflops * 1e12)
    t_m = bytes_accessed / (chip.hbm_gbps * 1e9)
    t_x = comm_bytes / (chip.ici_gbps * 1e9)
    step = max(t_c, t_m, t_x, 1e-12)
    bound = {t_c: "compute", t_m: "bandwidth", t_x: "comm"}[max(t_c, t_m, t_x)]
    return PerfPrediction(
        step_time_s=step,
        throughput_per_sec=(batch if batch else 1.0) / step,
        peak_hbm_bytes=peak_hbm_bytes,
        bound=bound,
        t_compute_s=t_c,
        t_memory_s=t_m,
        t_comm_s=t_x,
    )


def prediction_asdict(pred: PerfPrediction | None) -> dict | None:
    return None if pred is None else asdict(pred)


# ----------------------------------------------------------------- comms


def fsdp_comm_bytes(param_bytes: float, *, fsdp: int) -> float:
    """Per-device FSDP collective bytes per step: all-gather params for
    forward, again for backward, reduce-scatter grads — 3·P·(n−1)/n."""
    if fsdp <= 1:
        return 0.0
    return 3.0 * param_bytes * (fsdp - 1) / fsdp


def dp_comm_bytes(param_bytes: float, *, dp: int) -> float:
    """Per-device DP grad all-reduce bytes per step (ring): 2·P·(n−1)/n."""
    if dp <= 1:
        return 0.0
    return 2.0 * param_bytes * (dp - 1) / dp


# ------------------------------------------------- analytic train predictor


def approx_param_count(enc_cfg, dec_cfg=None) -> float:
    """Matmul-weight parameter count from the config (embeddings and norms
    are noise at this precision)."""
    d, h = enc_cfg.dim, enc_cfg.hidden_dim
    per_layer = 4 * d * d + 2 * d * h  # qkv+out proj, MLP in/out
    jumbo = 2 * (enc_cfg.num_cls_tokens * d) * (4 * enc_cfg.num_cls_tokens * d)
    n = enc_cfg.layers * (per_layer + jumbo / max(enc_cfg.layers, 1))
    n += enc_cfg.patch_size**2 * 3 * d  # patchify
    if dec_cfg is not None:
        dd, dh = dec_cfg.dim, dec_cfg.hidden_dim
        n += dec_cfg.layers * (4 * dd * dd + 2 * dd * dh)
        n += d * dd + dd * enc_cfg.patch_size**2 * 3  # in/out projections
    return float(n)


def predict_train_step(
    enc_cfg,
    dec_cfg=None,
    *,
    per_device_batch: int,
    mode: str = "pretrain",
    chip: ChipSpec | None = None,
    dp: int = 1,
    fsdp: int = 1,
    param_bytes_per_elt: float = 4.0,
) -> PerfPrediction:
    """Analytic (no-backend) prediction for one train step on one device.

    Flops come from the ``obs/mfu`` counters; the bytes model is coarse by
    design — optimizer state + grads + params traffic ≈ 8× param bytes per
    step, plus one activation read/write per flop-byte of batch work — and
    is documented as such wherever the number surfaces.
    """
    from jumbo_mae_tpu_tpu.obs.mfu import (
        classify_flops_per_image,
        pretrain_flops_per_image,
    )

    if chip is None:
        chip = detect_chip()
    if mode == "pretrain":
        flops_img = pretrain_flops_per_image(enc_cfg, dec_cfg, training=True)
    else:
        flops_img = classify_flops_per_image(enc_cfg, training=True)
    flops = flops_img * per_device_batch
    p_bytes = approx_param_count(enc_cfg, dec_cfg) * param_bytes_per_elt
    # params + grads + adam m/v read and written once each ≈ 8×P, plus an
    # activation-traffic term proportional to batch compute intensity
    act_bytes = 2.0 * flops / max(enc_cfg.dim, 1)
    bytes_accessed = 8.0 * p_bytes + act_bytes
    comm = fsdp_comm_bytes(p_bytes, fsdp=fsdp) + dp_comm_bytes(p_bytes, dp=dp)
    # optimizer state (m, v) + params + grads live across the step
    peak_hbm = 4.0 * p_bytes + act_bytes / 8.0
    return roofline(
        flops,
        bytes_accessed,
        chip,
        comm_bytes=comm,
        batch=per_device_batch,
        peak_hbm_bytes=peak_hbm,
    )


# ------------------------------------------------------------- drift gauge


def publish_drift(
    predicted_s: float, measured_s: float, *, program: str, registry=None
) -> float:
    """Publish ``perf_predicted_step_seconds{program}`` and the drift ratio
    ``perf_predict_vs_measured{program}`` = measured / predicted (1.0 = on
    the roofline; ≫1 = detached from it). Returns the ratio."""
    if registry is None:
        from jumbo_mae_tpu_tpu.obs.metrics import get_registry

        registry = get_registry()
    ratio = measured_s / max(predicted_s, 1e-12)
    registry.gauge(
        "perf_predicted_step_seconds",
        "roofline-predicted execution seconds",
        labels=("program",),
    ).labels(program).set(predicted_s)
    registry.gauge(
        "perf_predict_vs_measured",
        "measured / roofline-predicted execution time",
        labels=("program",),
    ).labels(program).set(ratio)
    return ratio
