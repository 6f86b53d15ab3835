"""Round benchmark: MAE ViT-L/16 pretrain throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device"}.
Needs a TPU: on any other platform it exits non-zero and prints no result —
a CPU run is not a device measurement.

The reference published no throughput numbers (BASELINE.md), so the baseline
here is a faithful *reference-style* configuration of the same workload run
on the same chip: float32 compute (the reference's flax modules never cast
to bfloat16) with the same model/masking/optimizer. ``vs_baseline`` is
(this framework's bf16 throughput) / (reference-style fp32 throughput).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

MODELS = {
    # test-sized smoke config: a fast sanity run of the bench/profile tools
    "vit_t16": dict(dec=dict(layers=2, dim=64, heads=4), batch=8, remat=False),
    # the reference's OTHER headline pretrain workload (B/16 1600ep,
    # /root/reference/config/pretrain/pretrain-vit-b16-224-in1k-1600ep.sh);
    # same 8x512x16h decoder as L
    "vit_b16": dict(
        dec=dict(layers=8, dim=512, heads=16),
        # swept on-chip: 192 peaks (1285 vs 1210@128, 1236@256, 1184@384,
        # 1115@512); onehot gather loses ~3% at every batch (like L)
        batch=192,
        f32_batch=128,
        remat=False,
        bf16=dict(mu_dtype="bfloat16", nu_dtype="bfloat16"),
    ),
    "vit_l16": dict(
        dec=dict(layers=8, dim=512, heads=16),
        # 192 re-swept fastest once bf16 moments landed (669.6 vs 654.0@128,
        # 653.7@160, 617.3@224 — the pre-bf16 sweeps had 128 winning); the
        # f32 reference leg stays at its established 128.
        batch=192,
        f32_batch=128,
        remat=False,
        # bf16-leg defaults (PERF_ARCHIVE.md §Round 3 on-chip, vit_l16 sweep):
        # bf16 moments +1.3%; onehot gather is a clear LOSS here (−8%,
        # the opposite of vit_h14 — the 0/1 matmuls outgrow the gather
        # saving at batch 128 / decoder dim 512), so take stays.
        bf16=dict(mu_dtype="bfloat16", nu_dtype="bfloat16"),
    ),
    # The reference-style f32 leg doubles every activation, so it gets its
    # own largest-fitting batch (f32 at the bf16 leg's batch needs ~20 GB);
    # the ratio compares per-image throughput, each leg at its feasible
    # batch, plus an equal-batch ratio in the JSON. The f32 leg keeps the
    # dots remat that batch 32 f32 needs to fit on 16 GB.
    "vit_h14": dict(
        dec=dict(layers=8, dim=512, heads=16),
        # batch 72 re-swept fastest once the bf16-moment/no-remat stack
        # landed (294 vs 288@64 / 292@80 img/s) — the shared jumbo-MLP
        # weight traffic amortizes over more rows (PERF_ARCHIVE.md §Round 3)
        batch=72,
        f32_batch=32,
        remat=True,
        remat_policy="dots",
        # framework-leg (bf16) defaults, each A/B'd on chip (PERF_ARCHIVE.md
        # §ViT-H/14 round 3): bf16 moments free ~4.6 GB of HBM, which lets
        # the model run UN-rematerialized at batch 64 (−13 ms of dots
        # recompute), and the one-hot MXU gather beats the XLA dynamic
        # gather at this scale. The f32 leg keeps the reference-style
        # config above (f32 moments, take gather, dots remat to fit).
        # An UNSET env knob now resolves to these defaults — to sweep a
        # default-on knob OFF use its explicit off spelling:
        # BENCH_MU_DTYPE=float32 BENCH_NU_DTYPE=float32
        # BENCH_GATHER_IMPL=take BENCH_REMAT=1 (spec remat+policy).
        bf16=dict(
            remat=False,
            mu_dtype="bfloat16",
            nu_dtype="bfloat16",
            gather="onehot",
        ),
    ),
}


def _parse_dec_heads(value, dec_dim: int) -> int:
    """Eager validation (leg_config contract: bad knobs die with a clear
    message BEFORE anything is measured): must be an int dividing the
    decoder dim, else head_dim would silently floor and the bench would
    record numbers for a different attention than the config claims."""
    try:
        heads = int(value or 0)
    except (TypeError, ValueError):
        raise SystemExit(
            f"BENCH_DEC_HEADS={value!r} not an integer"
        ) from None
    if heads and dec_dim % heads:
        raise SystemExit(
            f"BENCH_DEC_HEADS={heads} does not divide the decoder dim "
            f"{dec_dim}"
        )
    return heads


def _norm_f32(value):
    """Map the explicit "float32" off-spelling (and unset) to None so the
    master-weights wrapper only engages for real low-precision storage."""
    return None if value in (None, "", "float32") else value


def leg_config(model: str, dtype: str, env=None) -> dict:
    """Resolve the per-leg bench knobs — pure and unit-testable.

    The bf16 leg is the framework at its measured-best TPU config (spec
    "bf16" defaults + BENCH_* env overrides); the f32 leg is the FIXED
    reference-style baseline — env knobs and bf16 defaults never touch it,
    so the two legs stay comparable across sweeps.

    Remat subtlety: an explicit BENCH_REMAT_POLICY also turns remat ON for
    models that default to remat=False — otherwise the override would
    silently no-op (maybe_remat ignores the policy when grad_ckpt is
    false); BENCH_REMAT=0/1 force-overrides both (bf16 moments freed
    enough HBM that no-remat ViT-H/14 fits at the bench batch)."""
    env = os.environ if env is None else env
    spec = MODELS[model]
    framework_leg = dtype == "bfloat16"
    leg = spec.get("bf16", {}) if framework_leg else {}

    def knob(env_name: str, default):
        if framework_leg and env.get(env_name):
            return env[env_name]
        return default

    remat_env = env.get("BENCH_REMAT") if framework_leg else None
    if remat_env:
        if remat_env not in ("0", "1"):
            raise SystemExit(
                f"BENCH_REMAT={remat_env!r} not understood; use 0 or 1"
            )
        grad_ckpt = remat_env == "1"
    else:
        grad_ckpt = leg.get("remat", spec["remat"]) or bool(
            knob("BENCH_REMAT_POLICY", "")
        )
    out = dict(
        grad_ckpt=grad_ckpt,
        remat_policy=knob(
            "BENCH_REMAT_POLICY", spec.get("remat_policy", "none")
        ),
        # masking gather lowering: "take" (XLA gather) vs "onehot" (MXU
        # matmul, concat-free unshuffle) — bit-identical, A/B by profile
        gather_impl=knob("BENCH_GATHER_IMPL", leg.get("gather", "take")),
        # decoder-side remat is its own experiment axis (the decoder runs
        # at head_dim 32 and is un-rematerialized by default)
        dec_remat=env.get("BENCH_DEC_REMAT_POLICY") if framework_leg else None,
        mu_dtype=knob("BENCH_MU_DTYPE", leg.get("mu_dtype")) or None,
        nu_dtype=knob("BENCH_NU_DTYPE", leg.get("nu_dtype")) or None,
        # parameter STORAGE dtype: "bfloat16" stores params bf16 with an f32
        # master copy in the optimizer (train/optim.py with_master_weights) —
        # halves weight-read HBM traffic. "float32" is the explicit off
        # spelling for sweeping a default-on model.
        param_dtype=_norm_f32(knob("BENCH_PARAM_DTYPE", leg.get("param_dtype"))),
        # attention lowering (einsum/flash/ring/auto): at long context the
        # flash kernel avoids materializing the O(S^2) score tensor, which
        # is what OOMs the einsum path first (PERF_ARCHIVE.md long-context rows)
        attn_impl=knob("BENCH_ATTN_IMPL", "auto"),
        # decoder head-count override (head_dim = 512/heads): heads=8 gives
        # head_dim 64 — the MAE paper's 16h decoder is a recipe choice, and
        # at B scale the d32 decoder attention is the profile's top target
        dec_heads=_parse_dec_heads(
            knob("BENCH_DEC_HEADS", leg.get("dec_heads", 0)),
            spec["dec"]["dim"],
        ),
    )
    if out["attn_impl"] not in ("einsum", "flash", "ring", "auto"):
        # the model's dispatch would silently fall back to einsum and the
        # bench would attribute an einsum measurement to the wrong kernel
        raise SystemExit(
            f"unknown BENCH_ATTN_IMPL {out['attn_impl']!r}; "
            "choose einsum/flash/ring/auto"
        )
    return out


def bench_image_size() -> int:
    """Long-context benching is one knob away: BENCH_IMAGE_SIZE=448 (etc.)
    scales the patch grid. Single parse point — the metric name and the
    workload must agree (the name carries the size so records never mix
    resolutions)."""
    return int(os.environ.get("BENCH_IMAGE_SIZE", "224"))


def build_step(dtype: str, batch_size: int, model: str = "vit_l16"):
    import jax

    from jumbo_mae_tpu_tpu.models import DecoderConfig, MAEPretrainModel, preset
    from jumbo_mae_tpu_tpu.parallel import (
        MeshConfig,
        batch_sharding,
        create_mesh,
    )
    from jumbo_mae_tpu_tpu.train import (
        OptimConfig,
        create_sharded_state,
        make_optimizer,
        make_train_step,
    )

    spec = MODELS[model]
    knobs = leg_config(model, dtype)

    mesh = create_mesh(
        MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1]
    )
    image_size = bench_image_size()
    enc = preset(
        model,
        mask_ratio=0.75,
        labels=None,
        posemb="sincos2d",
        dtype=dtype,
        image_size=image_size,
        grad_ckpt=knobs["grad_ckpt"],
        remat_policy=knobs["remat_policy"],
        gather_impl=knobs["gather_impl"],
        attn_impl=knobs["attn_impl"],
    )
    dec_remat = knobs["dec_remat"]
    dec_spec = dict(spec["dec"])
    if knobs["dec_heads"]:
        dec_spec["heads"] = knobs["dec_heads"]
    dec = DecoderConfig(
        **dec_spec,
        dtype=dtype,
        attn_impl=knobs["attn_impl"],
        grad_ckpt=bool(dec_remat),
        remat_policy=dec_remat or "none",
    )
    module = MAEPretrainModel(enc, dec, norm_pix_loss=True)

    batch = {
        "images": np.random.RandomState(0).randint(
            0, 256, (batch_size, image_size, image_size, 3), dtype=np.uint8
        )
    }
    tx = make_optimizer(
        OptimConfig(
            name="adamw",
            learning_rate=1.5e-4,
            b2=0.95,
            weight_decay=0.05,
            warmup_steps=100,
            training_steps=10_000,
            mu_dtype=knobs["mu_dtype"],
            nu_dtype=knobs["nu_dtype"],
            param_dtype=knobs["param_dtype"],
        ),
        global_batch_size=batch_size,
    )
    state, sharding = create_sharded_state(
        module, tx, batch, mesh, mode="pretrain",
        param_dtype=knobs["param_dtype"],
    )
    step = make_train_step(mesh, sharding, mode="pretrain")
    # Stage the batch on device once: training overlaps host→device copies
    # with compute (data/loader.py prefetch_to_device), so steady-state
    # throughput is device-bound — that is what this measures.
    batch = jax.device_put(batch, batch_sharding(mesh))

    # analytic step FLOPs → the 100%-MFU step-time floor: a measurement can
    # never beat the chip's peak, so a faster one means the timing is broken.
    # (detect_peak_tflops raises for a device_kind that is not in its table.)
    from jumbo_mae_tpu_tpu.utils.mfu import detect_peak_tflops, pretrain_flops_per_image

    peak = detect_peak_tflops()
    if peak is None:
        raise RuntimeError(
            "bench.build_step needs an accelerator: the CPU backend has no "
            "peak rate to bound a timing with"
        )
    flops_per_step = pretrain_flops_per_image(enc, dec) * batch_size
    floor_ms = flops_per_step / (peak * 1e12) * 1e3
    return step, state, batch, floor_ms


def time_steps(
    step,
    state,
    batch,
    *,
    warmup: int,
    iters: int,
    rounds: int = 3,
    min_plausible_ms: float = 0.0,
) -> float:
    """Best-of-``rounds`` mean step time over ``iters`` chained async steps.

    Each round dispatches ``iters`` steps back-to-back with ONE final
    block_until_ready (the steady-state pattern of the train loop, which
    syncs only at log boundaries). Both bench legs get identical treatment
    so the ratio is defensible. A round faster than ``min_plausible_ms`` —
    the analytic workload FLOPs at 100% MFU — means the timing is broken,
    and raises."""
    import jax

    for _ in range(warmup):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics["loss"])
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = (time.perf_counter() - t0) / iters
        loss = float(metrics["loss"])
        if not np.isfinite(loss):
            raise RuntimeError(f"bench produced non-finite loss {loss}")
        if dt * 1e3 < min_plausible_ms:
            raise RuntimeError(
                f"bench round measured {dt * 1e3:.2f} ms/step, below the "
                f"{min_plausible_ms:.1f} ms floor of the chip's peak — the "
                "timing is broken, not fast"
            )
        best = min(best, dt)
    return best


# XLA cost analysis per measured leg ("<dtype>-b<batch>" → cost dict),
# recorded by _measure_leg as a side table for the ledger row.
_LEG_COSTS: dict = {}


def _record_leg_cost(key: str, step, batch_size: int) -> None:
    """Best-effort: read XLA's cost analysis off the leg's train-step
    executable (the AOT dispatch in train/steps exposes it — no recompile)."""
    try:
        from jumbo_mae_tpu_tpu.obs.costmodel import cost_asdict, extract_cost

        execs = getattr(step, "executables", None) or {}
        for ex in execs.values():
            cost = extract_cost(ex, "train_step")
            if cost is not None:
                _LEG_COSTS[key] = cost_asdict(cost) | {"batch": batch_size}
            break
    except Exception:  # noqa: BLE001 — observability must not fail a leg
        pass


def _measure_leg(dtype: str, batch_size: int, model: str, iters: int) -> float:
    """Build + time one bench leg."""
    step, state, batch, floor = build_step(dtype, batch_size, model)
    dt = time_steps(
        step, state, batch, warmup=3, iters=iters, min_plausible_ms=floor
    )
    _record_leg_cost(f"{dtype}-b{batch_size}", step, batch_size)
    return dt


def _run_bench() -> dict:
    model = os.environ.get("BENCH_MODEL", "vit_l16")
    if model not in MODELS:
        raise SystemExit(
            f"unknown BENCH_MODEL {model!r}; choose from {sorted(MODELS)}"
        )
    batch_size = int(os.environ.get("BENCH_BATCH", str(MODELS[model]["batch"])))
    iters = int(os.environ.get("BENCH_ITERS", "10"))
    size = bench_image_size()

    dt = _measure_leg("bfloat16", batch_size, model, iters)
    imgs_per_sec = batch_size / dt

    result = {
        "metric": f"mae_{model}_{size}_pretrain_imgs_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec/chip",
        "vs_baseline": None,
        "ms_step_bf16": round(dt * 1e3, 2),
    }
    if not os.environ.get("BENCH_SKIP_BASELINE"):
        # The baseline leg (reference-style fp32 compute, same workload)
        # gets IDENTICAL warmup/iters/rounds so the ratio is two equally
        # converged measurements, not a converged one over a noisy one.
        # f32 doubles activation memory; models that need a smaller f32
        # batch declare it, and the ratio compares per-image throughput.
        # never larger than the bf16 leg's batch: a user-shrunk BENCH_BATCH
        # must shrink the f32 leg too (its declared batch is sized for the
        # default config's memory envelope)
        batch_f32 = int(
            os.environ.get(
                "BENCH_F32_BATCH",
                str(min(MODELS[model].get("f32_batch", batch_size), batch_size)),
            )
        )
        dt_f32 = _measure_leg("float32", batch_f32, model, iters)
        result["vs_baseline"] = round(imgs_per_sec / (batch_f32 / dt_f32), 3)
        result["ms_step_f32"] = round(dt_f32 * 1e3, 2)
        if batch_f32 != batch_size:
            # The headline ratio folds batch-size efficiency into the config
            # win. Time a framework leg AT the f32 batch too, so the artifact
            # also carries a framework-config vs reference-style ratio at
            # equal batch (the framework leg keeps its tuned per-model knobs
            # — gather/remat/moment dtypes — so this is NOT dtype-only).
            result["f32_batch"] = batch_f32
            dt_eq = _measure_leg("bfloat16", batch_f32, model, iters)
            result["vs_baseline_equal_batch"] = round(dt_f32 / dt_eq, 3)
    _append_ledger(result, batch_size)
    return result


def _append_ledger(result: dict, batch_size: int) -> None:
    """Land this round in BENCH_HISTORY.jsonl (``obs/perfledger``): legs,
    the XLA-extracted bf16-leg cost, and its roofline prediction. Best
    effort — the one-JSON-line stdout contract is unaffected either way."""
    try:
        from jumbo_mae_tpu_tpu.obs.perfledger import (
            append_row,
            make_row,
            resolve_history_path,
        )

        path = resolve_history_path()
        if path is None:
            return
        legs = {
            k: result[k]
            for k in (
                "value",
                "ms_step_bf16",
                "ms_step_f32",
                "vs_baseline",
                "vs_baseline_equal_batch",
            )
            if result.get(k) is not None
        }
        prediction = None
        cost = _LEG_COSTS.get(f"bfloat16-b{batch_size}")
        if cost:
            from jumbo_mae_tpu_tpu.obs.perfmodel import (
                detect_chip,
                prediction_asdict,
                roofline,
            )

            pred = roofline(
                cost["flops"],
                cost["bytes_accessed"],
                detect_chip(),
                batch=cost.get("batch"),
                peak_hbm_bytes=cost.get("peak_bytes", 0.0),
            )
            prediction = prediction_asdict(pred)
        row = make_row(
            bench="train",
            metric=result["metric"],
            legs=legs,
            prediction=prediction,
            extra={"unit": result.get("unit"), "cost": cost},
        )
        if append_row(path, row):
            print(f"bench: ledger row -> {path}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — the ledger must not fail a bench
        print(f"bench: ledger append failed: {e}", file=sys.stderr)


def main():
    from jumbo_mae_tpu_tpu.utils.procenv import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"bench: needs a TPU, found platform {dev.platform!r} — a run on "
            "it is not a device measurement",
            file=sys.stderr,
        )
        return 1
    result = _run_bench()
    result["device"] = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
