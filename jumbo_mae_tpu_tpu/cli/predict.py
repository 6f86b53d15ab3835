"""Serving entry point: run any model head over images from the CLI.

The thin front end over ``jumbo_mae_tpu_tpu.infer`` — restore once, compile
per bucket once, then stream requests:

    # classification (finetune / linear-probe checkpoints)
    python -m jumbo_mae_tpu_tpu.cli.predict --config recipes/finetune_vit_b16.yaml \
        --ckpt runs/ft/ckpt --task logits --images cat.jpg dog.jpg --topk 5

    # frozen-encoder features
    python -m jumbo_mae_tpu_tpu.cli.predict --config recipes/linear_sgd_vit_b16.yaml \
        --ckpt runs/pretrain/ckpt --task features --pool cls \
        --images *.jpg --out feats.npz

    # MAE reconstruction (pretrain checkpoints)
    python -m jumbo_mae_tpu_tpu.cli.predict --config recipes/pretrain_vit_b16_in1k_1600ep.yaml \
        --ckpt runs/pretrain/ckpt --task reconstruct --images cat.jpg --out recon.npz

Files are resized + center-cropped by the eval transform (same geometry as
validation). ``--serve`` additionally routes the requests through the
micro-batching queue one image at a time — a single-process demo of the
serving path (``--max-delay-ms``/``--max-batch`` are the coalescing knobs);
the default path batches the whole file list directly. Results land in
``--out`` (``.npz``) and, for ``logits``, as one JSON line per image on
stdout.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default=None, help="YAML recipe path")
    p.add_argument(
        "--ckpt",
        default="",
        help="Orbax run/checkpoint dir, .msgpack params, or a published "
        "train→serve artifact dir (publish-NNNNNN); random init if omitted",
    )
    p.add_argument(
        "--task", choices=("features", "logits", "reconstruct"), default="logits"
    )
    p.add_argument(
        "--images", nargs="+", default=[], metavar="FILE", help="image files"
    )
    p.add_argument(
        "--synthetic",
        type=int,
        default=0,
        metavar="N",
        help="use N synthetic images instead of --images (smoke/bench)",
    )
    p.add_argument("--out", default="", help="output .npz path")
    p.add_argument("--pool", choices=("cls", "gap", "tokens"), default="cls")
    p.add_argument("--topk", type=int, default=5, help="logits: classes per line")
    p.add_argument("--seed", type=int, default=0, help="reconstruct: mask seed")
    p.add_argument("--max-batch", type=int, default=64)
    p.add_argument(
        "--max-delay-ms", type=float, default=5.0, help="--serve coalescing deadline"
    )
    p.add_argument(
        "--max-queue",
        type=int,
        default=None,
        metavar="N",
        help="--serve backpressure bound: submits beyond N pending "
        "requests shed with QueueFullError (default: unbounded)",
    )
    p.add_argument(
        "--deadline-ms",
        type=float,
        default=None,
        metavar="MS",
        help="--serve per-request deadline: a request still queued after "
        "MS fails with DeadlineExceededError instead of riding a batch",
    )
    p.add_argument(
        "--serve",
        action="store_true",
        help="submit images one-by-one through the micro-batching queue",
    )
    p.add_argument(
        "--replicas",
        type=int,
        default=0,
        metavar="N",
        help="--serve: run N supervised engine replicas behind the queue "
        "(crash-isolated request retry, restart with capped backoff, "
        "quorum circuit breaker in /healthz); 0 = the single-engine "
        "micro-batcher",
    )
    p.add_argument(
        "--interarrival-ms",
        type=float,
        default=0.0,
        metavar="MS",
        help="--serve: pace submits MS apart instead of firing them all at "
        "once (steady offered load for chaos and canary runs)",
    )
    p.add_argument(
        "--pack",
        action="store_true",
        help="--serve --replicas: token-packed dispatch — the continuous "
        "scheduler coalesces requests by TOKEN budget (mixed resolutions "
        "ride together) and each group runs through engine.predict_packed "
        "as one packed executable. features/logits only; --pool cls|gap",
    )
    p.add_argument(
        "--pack-budget",
        type=int,
        default=0,
        metavar="TOKENS",
        help="--pack: the scheduler's token fill target per dispatch group "
        "(0 = the engine's max_tokens default); the packer itself keeps "
        "rung headroom above this for flushes that merge groups",
    )
    p.add_argument(
        "--pack-resolutions",
        default="",
        metavar="SPEC",
        help="--pack --synthetic: seeded mixed-resolution traffic, e.g. "
        "'224:0.5,448:0.3,896:0.2' (size:weight; sizes must be "
        "patch-aligned and need posemb=sincos2d when non-native); "
        "default: every request at the native size",
    )
    p.add_argument(
        "--pack-parity-n",
        type=int,
        default=8,
        metavar="N",
        help="--pack: packed-vs-unpacked per-request parity gate over the "
        "first N requests before serving traffic (0 = skip); a failed "
        "gate aborts the run",
    )
    p.add_argument(
        "--tenants",
        default="",
        metavar="SPEC",
        help="--replicas: traffic shaping — weighted multi-tenant admission "
        "plus continuous batching and per-tenant cost metering. "
        "Comma-separated name=class[:rate=N][:burst=N][:budget=D][:window=W] "
        "entries (budget = device-seconds per window; classes: "
        "interactive|batch|scavenger); requests round-robin across tenants, "
        "low classes shed first under pressure, and the continuous "
        "scheduler coalesces late arrivals into pending batches",
    )
    p.add_argument(
        "--autoscale",
        default="",
        metavar="MIN:MAX",
        help="--replicas: reconcile the replica count between MIN and MAX "
        "from SLO burn rate, queue depth, and roofline capacity; "
        "scale-down drains the replica first (in-flight work is never "
        "killed) and every resize journals an autoscale event",
    )
    p.add_argument(
        "--autoscale-interval-s",
        type=float,
        default=1.0,
        help="--autoscale reconcile tick seconds",
    )
    p.add_argument(
        "--swap-watch",
        default="",
        metavar="DIR",
        help="--replicas: poll DIR for newly appearing checkpoint files or "
        "dirs and run each through the parity- and canary-gated weight "
        "hot-swap (promote on pass, automatic rollback on breach)",
    )
    p.add_argument(
        "--swap-poll-s",
        type=float,
        default=0.5,
        help="--swap-watch poll interval in seconds",
    )
    p.add_argument(
        "--swap-parity-min",
        type=float,
        default=0.98,
        help="hot-swap parity gate: min feature cosine of the candidate "
        "weights vs the live weights on the probe batch",
    )
    p.add_argument(
        "--swap-canary-requests",
        type=int,
        default=8,
        help="hot-swap canary window: live requests the flipped replica "
        "must serve before promotion",
    )
    p.add_argument(
        "--swap-canary-timeout-s",
        type=float,
        default=10.0,
        help="hot-swap canary window wall-clock bound",
    )
    p.add_argument(
        "--warmup",
        action="store_true",
        help="pre-compile every (task, bucket) executable before the first "
        "request, so request latencies measure serving, not compilation",
    )
    p.add_argument(
        "--access-log",
        default="",
        metavar="DIR",
        help="--serve: write a crash-safe JSONL access log (one row per "
        "finished request) into DIR; read it back with tools/serve_doctor.py",
    )
    p.add_argument(
        "--slo",
        default=None,
        metavar="SPEC",
        help="--serve SLO objectives, e.g. 'p99_latency_ms<=250;"
        "success_rate>=0.99' (default: run.slo from the recipe); breaches "
        "latch the degraded flag in /healthz and the slo_* gauges",
    )
    p.add_argument(
        "--slo-window-s",
        type=float,
        default=None,
        help="SLO rolling window seconds (default: run.slo_window_s)",
    )
    p.add_argument(
        "--slo-fast-window-s",
        type=float,
        default=None,
        help="SLO fast confirmation window seconds "
        "(default: run.slo_fast_window_s; 0 = window/12)",
    )
    p.add_argument(
        "--dtype",
        default=None,
        help="serving compute dtype override (e.g. float32 for the exact path)",
    )
    p.add_argument(
        "--quant",
        choices=("int8",),
        default=None,
        help="weight-only post-training quantization: int8 kernels with "
        "per-output-channel f32 scales, dequantized on use (embeddings, "
        "norms, biases stay f32)",
    )
    p.add_argument(
        "--warmcache",
        default=None,
        metavar="DIR",
        help="persistent executable cache directory (default: warmcache/ "
        "under the compile cache — $JAX_COMPILATION_CACHE_DIR, else "
        "<checkout>/.jax_cache; restarted replicas load instead of "
        "compiling)",
    )
    p.add_argument(
        "--no-warmcache",
        action="store_true",
        help="disable the persistent executable cache for this run",
    )
    p.add_argument(
        "--encoder-cache",
        type=int,
        default=0,
        metavar="N",
        help="reconstruct: LRU-cache up to N encoder outputs keyed by "
        "(image bytes, seed) — repeated decode of the same image skips "
        "the encoder (shared mask mode only)",
    )
    p.add_argument(
        "--encoder-cache-mb",
        type=float,
        default=0.0,
        metavar="MB",
        help="byte bound on the encoder-output LRU on top of "
        "--encoder-cache: whichever cap trips first evicts "
        "(0 = entries-only)",
    )
    p.add_argument(
        "--metrics-port",
        type=int,
        default=None,
        metavar="PORT",
        help="serve Prometheus /metrics + /healthz on this port "
        "(0 = any free port, printed at startup; omit to disable)",
    )
    p.add_argument(
        "--metrics-hold-s",
        type=float,
        default=0.0,
        help="keep the exporter up N seconds after the requests finish "
        "(lets an external scraper read the final counters; CI smoke uses it)",
    )
    p.add_argument(
        "--set",
        dest="overrides",
        metavar="KEY.PATH=VALUE",
        nargs="*",
        action="extend",
        default=[],
        help="dotted config overrides, same grammar as cli.train",
    )
    return p


def main(argv: list[str] | None = None) -> Path | None:
    args = build_parser().parse_args(argv)

    import jax
    import numpy as np

    from jumbo_mae_tpu_tpu.config import load_config
    from jumbo_mae_tpu_tpu.infer import InferenceEngine, MicroBatcher
    from jumbo_mae_tpu_tpu.utils.procenv import enable_compile_cache

    enable_compile_cache()
    if jax.process_count() > 1:
        raise SystemExit("predict is a single-process tool; run it on one host")
    if bool(args.images) == bool(args.synthetic):
        raise SystemExit("pass exactly one of --images or --synthetic N")

    cfg = load_config(args.config, args.overrides)

    telemetry = None
    health = None
    if args.metrics_port is not None:
        from jumbo_mae_tpu_tpu.obs import HealthState, TelemetryServer

        health = HealthState()  # not ready until the engine is constructed
        telemetry = TelemetryServer(health=health, port=args.metrics_port).start()
        print(f"[predict] exporter on :{telemetry.port} (/metrics, /healthz)")

    # memory observability (obs/memwatch.py): sampled per /metrics scrape —
    # device/host gauges, per-component byte accounting of the serving
    # caches, and the HBM predict-vs-measured drift per compiled executable
    memwatch = None
    mem_accountant = None
    if telemetry is not None and cfg.run.memwatch:
        from jumbo_mae_tpu_tpu.obs.memwatch import MemAccountant, MemoryWatcher
        from jumbo_mae_tpu_tpu.obs.perfmodel import detect_chip

        mem_accountant = MemAccountant()
        memwatch = MemoryWatcher(accountant=mem_accountant, chip=detect_chip())
        health.probe("memory", memwatch.last_sample)

    replicated = bool(args.serve and args.replicas > 0)
    if (args.tenants or args.autoscale) and not replicated:
        raise SystemExit("--tenants/--autoscale require --serve --replicas N")
    pack_mix: list[tuple[int, float]] | None = None
    if args.pack:
        if not replicated:
            raise SystemExit("--pack requires --serve --replicas N")
        if args.task not in ("features", "logits"):
            raise SystemExit(
                "--pack serves the encoder-sharing tasks: features|logits"
            )
        if args.pool == "tokens":
            raise SystemExit("--pack pools per segment: --pool cls or gap")
        if args.pack_resolutions:
            pack_mix = []
            for part in args.pack_resolutions.split(","):
                s, _, w = part.partition(":")
                pack_mix.append((int(s), float(w or 1.0)))
    elif args.pack_resolutions:
        raise SystemExit("--pack-resolutions requires --pack")
    # restarts and promoted swaps read the checkpoint through this cell,
    # so a replica rebuilt after a promote comes up on the new weights
    ckpt_ref = {"ckpt": args.ckpt}

    # retrace sentinel (obs/retrace.py): once warmup has pre-compiled the
    # serving executables, the serve loop must be compile-free — armed
    # after the first served batch, every further XLA compile warns with
    # shape/dtype-diff attribution and counts into retrace_events_total
    retrace_sentinel = None
    if args.serve and args.warmup and cfg.run.retrace:
        from jumbo_mae_tpu_tpu.obs.retrace import RetraceSentinel

        retrace_sentinel = RetraceSentinel("predict")

    def make_engine():
        return InferenceEngine(
            cfg,
            ckpt=ckpt_ref["ckpt"],
            dtype=args.dtype,
            max_batch=args.max_batch,
            # the packer's rung ceiling, kept ABOVE the scheduler's fill
            # target (--pack-budget): a busy replica merges consecutive
            # dispatch groups into one flush, and rungs capped at the fill
            # target would force pow2-row padding on those merged flushes
            **(
                {"max_tokens": max(args.pack_budget, 4096)}
                if args.pack_budget
                else {}
            ),
            quant=args.quant,
            warm_cache=(
                False if args.no_warmcache
                else args.warmcache if args.warmcache is not None
                else True
            ),
            encoder_cache=args.encoder_cache,
            encoder_cache_bytes=int(args.encoder_cache_mb * 1024 * 1024),
        )

    if args.ckpt == "":
        print("[predict] WARNING: no --ckpt — serving a random init")
    engine = None
    if not replicated:
        engine = make_engine()
        if engine.warmcache is not None:
            print(f"[predict] warmcache: {engine.warmcache.root}")
        if args.warmup:
            n_compiles = engine.warmup((args.task,), pool=args.pool)
            hits = sum(engine.warm_hits.values())
            print(
                f"[predict] warmup: {n_compiles} executable(s) compiled, "
                f"{hits} loaded from warmcache"
            )
    if memwatch is not None and engine is not None:
        mem_accountant.register("engine_enc_cache", engine.encoder_cache_bytes)
        mem_accountant.register(
            "engine_exec_cache", engine.executable_cache_bytes
        )
        if engine.warmcache is not None:
            mem_accountant.register(
                "warmcache_disk", engine.warmcache.disk_bytes
            )

        def _sync_predicted_peaks(eng=engine):
            # executables compile lazily on the request path too — refresh
            # the prediction side of the drift gauge before every scrape
            for prog, peak in eng.predicted_peak_hbm().items():
                memwatch.record_predicted_peak(prog, peak)

        telemetry.add_pre_scrape(_sync_predicted_peaks)
        telemetry.add_pre_scrape(memwatch.sample)
    if health is not None and not replicated:
        health.set_ready(
            True, detail=f"engine up (ckpt={'yes' if args.ckpt else 'random'})"
        )

    # request observability (obs/reqtrace.py, obs/slo.py) rides the serving
    # path only — the direct batch path stays telemetry-free
    tracer = None
    slo_tracker = None
    if args.serve:
        from jumbo_mae_tpu_tpu.obs import (
            AccessLog,
            RequestTracer,
            SLOTracker,
            parse_slo,
        )

        slo_spec = args.slo if args.slo is not None else cfg.run.slo
        if slo_spec:
            slo_tracker = SLOTracker(
                parse_slo(slo_spec),
                window_s=(
                    args.slo_window_s
                    if args.slo_window_s is not None
                    else cfg.run.slo_window_s
                ),
                fast_window_s=(
                    args.slo_fast_window_s
                    if args.slo_fast_window_s is not None
                    else cfg.run.slo_fast_window_s
                ),
                burn_threshold=cfg.run.slo_burn_threshold,
            )
            print(
                f"[predict] SLO: {slo_spec} over "
                f"{slo_tracker.window_s:g}s/{slo_tracker.fast_window_s:g}s windows"
            )
        access = AccessLog(args.access_log) if args.access_log else None
        if access is not None:
            print(f"[predict] access log -> {access.path}")
        if access is not None or slo_tracker is not None or telemetry is not None:
            tracer = RequestTracer(
                access_log=access,
                # replicated: each flush passes its own engine's breakdown
                breakdown=engine.last_breakdown if engine is not None else None,
                on_finish=(
                    slo_tracker.observe_trace if slo_tracker is not None else None
                ),
            )
        if slo_tracker is not None:
            if health is not None:
                health.degraded_when(slo_tracker.degraded)
                health.probe("slo", slo_tracker.healthz_info)
            if telemetry is not None:
                telemetry.add_pre_scrape(slo_tracker.evaluate)

    rs = None
    swap_ctl = None
    if replicated:
        from jumbo_mae_tpu_tpu.infer import ReplicaSet, WeightSwapController

        def _warm(eng):
            if not args.warmup:
                return
            if args.pack:
                # warm the per-resolution embed stages + the packed
                # executable the representative mix's plan lands on
                res_list = (
                    [s for s, _ in pack_mix] if pack_mix else [eng.image_size]
                )
                eng.warmup_packed(res_list, (args.task,), pool=args.pool)
            else:
                eng.warmup((args.task,), pool=args.pool)

        def engine_provider(idx):
            # a (re)built replica compiles its own executables — during
            # chaos restarts that happens while the sentinel is armed, and
            # it is legitimate, not a retrace
            if retrace_sentinel is not None:
                with retrace_sentinel.expected("replica build"):
                    eng = make_engine()
                    _warm(eng)
                    return eng
            eng = make_engine()
            _warm(eng)
            return eng

        def run_replica(eng, batch, metas):
            def _go():
                if args.pack:
                    # batch is the raw image list for mixed shapes (see
                    # ReplicaSet._flush); one packed dispatch serves it
                    return eng.predict_packed(
                        list(batch), args.task, pool=args.pool
                    )
                return eng.predict(batch, task=args.task, **kw)

            if retrace_sentinel is None:
                return _go()
            retrace_sentinel.note("replica_batch", batch)
            out = _go()
            retrace_sentinel.arm()  # first batch served: steady state
            return out

        rs = ReplicaSet(
            engine_provider,
            run_replica,
            replicas=args.replicas,
            max_batch=args.max_batch,
            max_delay_ms=args.max_delay_ms,
            max_queue=args.max_queue,
            tracer=tracer,
            task=args.task,
            health=health,
            breakdown=lambda eng: eng.last_breakdown(),
        )
        eng0 = rs.replica(0).engine
        if eng0.warmcache is not None:
            print(f"[predict] warmcache: {eng0.warmcache.root}")
        if memwatch is not None:
            # per-replica accounting: probes resolve the CURRENT engine at
            # sample time, so restarted/rebuilt replicas stay accounted
            for i in range(args.replicas):
                mem_accountant.register(
                    f"replica{i}_enc_cache",
                    lambda i=i: rs.replica(i).engine.encoder_cache_bytes(),
                )
                mem_accountant.register(
                    f"replica{i}_exec_cache",
                    lambda i=i: rs.replica(i).engine.executable_cache_bytes(),
                )
            if eng0.warmcache is not None:
                mem_accountant.register(
                    "warmcache_disk",
                    lambda: rs.replica(0).engine.warmcache.disk_bytes(),
                )

            def _sync_replica_peaks():
                for i in range(args.replicas):
                    try:
                        peaks = rs.replica(i).engine.predicted_peak_hbm()
                    except Exception:  # noqa: BLE001 — replica mid-restart
                        continue
                    for prog, peak in peaks.items():
                        memwatch.record_predicted_peak(prog, peak)

            telemetry.add_pre_scrape(_sync_replica_peaks)
            telemetry.add_pre_scrape(memwatch.sample)
        print(
            f"[predict] replica pool: {args.replicas} replicas, "
            f"quorum {rs.quorum}"
        )
        if health is not None:
            health.set_ready(True, detail=f"pool up ({args.replicas} replicas)")
            if slo_tracker is not None:
                health.degraded_when(
                    lambda: slo_tracker.degraded() or rs.degraded()
                )
            else:
                health.degraded_when(rs.degraded)
        if args.swap_watch:

            def _swap_restore(path):
                # publish artifacts (serve/publisher.py) resolve their
                # delta chain with fingerprint verification; anything else
                # takes the plain checkpoint restore path
                from jumbo_mae_tpu_tpu.serve.publisher import (
                    is_publish_artifact,
                    resolve_chain,
                )

                if is_publish_artifact(path):
                    params, stats, _ = resolve_chain(path)
                    return params, stats
                from jumbo_mae_tpu_tpu.train.checkpoint import (
                    restore_inference_state,
                )

                return restore_inference_state(path, to_device=False)

            swap_ctl = WeightSwapController(
                rs,
                restore_fn=_swap_restore,
                parity_min_cosine=args.swap_parity_min,
                canary_requests=args.swap_canary_requests,
                canary_timeout_s=args.swap_canary_timeout_s,
                on_promote=lambda c: ckpt_ref.__setitem__("ckpt", c),
                # refuse a push the double-buffered restore cannot fit:
                # rejected at the "headroom" stage before any replica flips
                headroom_fn=(
                    memwatch.headroom_check if memwatch is not None else None
                ),
            )
        engine = eng0  # image geometry below; requests go through the pool

    size = engine.image_size
    if args.synthetic:
        if pack_mix:
            # seeded mixed-resolution traffic: same seed, same trace —
            # the packed-vs-bucketed A/B compares like against like
            rs_img = np.random.RandomState(0)
            sizes = [s for s, _ in pack_mix]
            w = np.array([max(wt, 0.0) for _, wt in pack_mix], np.float64)
            w /= w.sum()
            picks = rs_img.choice(len(sizes), size=args.synthetic, p=w)
            images = [
                rs_img.randint(
                    0, 256, (sizes[c], sizes[c], 3)
                ).astype(np.uint8)
                for c in picks
            ]
            names = [
                f"synthetic[{i}]@{im.shape[0]}" for i, im in enumerate(images)
            ]
        else:
            images = (
                np.random.RandomState(0)
                .randint(0, 256, (args.synthetic, size, size, 3))
                .astype(np.uint8)
            )
            names = [f"synthetic[{i}]" for i in range(args.synthetic)]
    else:
        from PIL import Image

        from jumbo_mae_tpu_tpu.data.transforms import eval_transform

        images = np.stack(
            [
                eval_transform(
                    np.asarray(Image.open(f).convert("RGB"), np.uint8),
                    size,
                    crop_ratio=cfg.data.test_crop_ratio,
                )
                for f in args.images
            ]
        )
        names = list(args.images)

    kw = {"pool": args.pool} if args.task == "features" else (
        {"seed": args.seed} if args.task == "reconstruct" else {}
    )
    if args.serve and rs is not None:
        import threading
        import time as _time

        if slo_tracker is not None:
            slo_tracker.add_probe(
                "queue_depth", lambda: rs.stats()["queue_depth"]
            )
            slo_tracker.add_probe(
                "healthy_replicas", lambda: rs.stats()["healthy"]
            )
            slo_tracker.add_probe(
                "batch_occupancy", lambda: rs.stats()["batch_occupancy"]
            )
        # traffic shaping (jumbo_mae_tpu_tpu/serve): tenant-weighted
        # admission + continuous batching in front of the pool
        sched = None
        admission = None
        tenant_names: list[str] = []
        meter = None
        if args.tenants:
            from jumbo_mae_tpu_tpu.serve import (
                AdmissionController,
                CostMeter,
                parse_tenants,
            )

            tenant_specs = parse_tenants(args.tenants)
            tenant_names = [t.name for t in tenant_specs]
            # meter every dispatched batch: per-tenant device-seconds/FLOPs
            # ledgers feed serve_tenant_* metrics, tenant_usage journal
            # rows, the access log's device_ms/cost_flops columns, and the
            # budget= checks below
            meter = CostMeter(tenant_specs, tracer=tracer)
            rs.set_costmeter(meter)
            admission = AdmissionController(tenant_specs, meter=meter)
            print(
                "[predict] traffic shaping: "
                + ", ".join(f"{t.name}={t.tclass}" for t in tenant_specs)
            )
        if args.tenants or args.pack:
            from jumbo_mae_tpu_tpu.serve import ContinuousScheduler

            # the scheduler's accumulator becomes the admission-visible
            # queue; give the pool headroom above it so a dispatched group
            # doesn't race the pool's own hard cap and shed an
            # already-admitted interactive request
            if rs.max_queue is not None:
                rs.max_queue = rs.max_queue + 2 * args.max_batch
            pack_budget = args.pack_budget or engine.max_tokens
            sched = ContinuousScheduler(
                rs.submit_group,
                max_batch=args.max_batch,
                max_delay_ms=args.max_delay_ms,
                max_queue=args.max_queue,
                admission=admission,
                tracer=tracer,
                task=args.task,
                packed=args.pack,
                token_budget=pack_budget if args.pack else None,
                seq_len_fn=(
                    (lambda arr: engine.seq_len(arr.shape[0]))
                    if args.pack
                    else None
                ),
            )
            if args.pack:
                print(
                    f"[predict] token packing: budget={pack_budget} "
                    f"tokens/dispatch, pool={args.pool}"
                )
            # combined pressure: scheduler accumulator OR pool backlog —
            # either filling sheds low classes before interactive traffic
            # hits a hard queue-full
            if admission is not None:
                admission.set_pressure_fn(
                    lambda: max(sched.pressure(), rs.pressure())
                )
        autoscaler = None
        if args.autoscale:
            from jumbo_mae_tpu_tpu.serve import Autoscaler, roofline_capacity

            try:
                lo, hi = (int(x) for x in args.autoscale.split(":"))
            except ValueError:
                raise SystemExit("--autoscale expects MIN:MAX, e.g. 2:6")
            # roofline capacity estimate for the serving bucket: forward
            # FLOPs per image + the coarse activation-traffic bytes model
            capacity_fn = None
            enc_cfg = getattr(engine, "_enc", None)
            if enc_cfg is not None:
                from jumbo_mae_tpu_tpu.obs.mfu import encoder_flops_per_image

                flops = encoder_flops_per_image(enc_cfg, masked=False)
                act_bytes = 2.0 * flops / max(enc_cfg.dim, 1)
                capacity_fn = lambda: roofline_capacity(flops, act_bytes)  # noqa: E731
            autoscaler = Autoscaler(
                rs,
                min_replicas=lo,
                max_replicas=hi,
                interval_s=args.autoscale_interval_s,
                slo=slo_tracker,
                capacity_fn=capacity_fn,
                tracer=tracer,
            )
            print(
                f"[predict] autoscaler: [{lo}, {hi}] replicas, "
                f"tick {args.autoscale_interval_s:g}s"
            )
        swap_stop = threading.Event()
        swap_thread = None
        if swap_ctl is not None:
            import os

            from jumbo_mae_tpu_tpu.obs.metrics import get_registry

            watch_root = Path(args.swap_watch)
            watch_root.mkdir(parents=True, exist_ok=True)
            c_quarantined = get_registry().counter(
                "serve_publish_quarantined_total",
                "publish artifacts the swap watcher quarantined before restore",
            )

            def _quarantine_artifact(p):
                # a torn/poisoned publish artifact is evidence, not trash:
                # move it aside (atomic, same filesystem) so the doctor can
                # autopsy it and the watcher never retries it
                qdir = watch_root / ".quarantine"
                try:
                    qdir.mkdir(exist_ok=True)
                    os.replace(p, qdir / p.name)
                except OSError:
                    pass  # leave it in place; `seen` already skips it
                c_quarantined.inc()

            def _watch_swaps():
                from jumbo_mae_tpu_tpu.serve.publisher import (
                    PublishIntegrityError,
                    is_publish_artifact,
                    verify_artifact,
                )

                # entries present at startup are the baseline, not pushes;
                # push checkpoints by atomic rename so a partial write
                # never gets picked up
                seen = {p.name for p in watch_root.iterdir()}
                while True:
                    stopping = swap_stop.is_set()
                    for p in sorted(watch_root.iterdir()):
                        if p.name in seen or p.name.startswith("."):
                            continue
                        seen.add(p.name)
                        print(f"[predict] swap-watch: new checkpoint {p}")
                        if is_publish_artifact(p):
                            # manifest fingerprint check BEFORE any bytes
                            # reach a restore: torn or corrupted artifacts
                            # are quarantined, never crash the watcher
                            try:
                                verify_artifact(p)
                            except PublishIntegrityError as e:
                                print(
                                    f"[predict] swap {p.name}: "
                                    f"verdict=quarantined stage=verify ({e})"
                                )
                                _quarantine_artifact(p)
                                continue
                        rep = swap_ctl.swap(str(p))
                        msg = (
                            f"[predict] swap {p.name}: "
                            f"verdict={rep['verdict']} stage={rep['stage']}"
                        )
                        if rep.get("parity"):
                            msg += (
                                f" cosine_min="
                                f"{rep['parity']['cosine_min']:.4f}"
                            )
                        print(msg)
                    if stopping:
                        return  # one final sweep ran after stop was set
                    swap_stop.wait(args.swap_poll_s)

            swap_thread = threading.Thread(target=_watch_swaps, daemon=True)
            swap_thread.start()
            print(
                f"[predict] swap-watch: polling {watch_root} "
                f"every {args.swap_poll_s:g}s"
            )
        if args.pack and args.pack_parity_n > 0:
            # correctness gate before traffic: every packed output must
            # match its own unpacked forward (cosine / top-1 agreement)
            par = engine.packed_parity(
                list(images[: args.pack_parity_n]),
                args.task,
                pool=args.pool,
            )
            cos = par["feature_cosine_min"]
            t1 = par["logits_top1_agree"]
            print(
                f"[predict] pack parity: pass={par['pass']} n={par['n']} "
                f"cosine_min={'-' if cos is None else format(cos, '.6f')} "
                f"top1_agree={'-' if t1 is None else format(t1, '.4f')}"
            )
            if not par["pass"]:
                raise SystemExit("[predict] pack parity gate FAILED")
        futs = []
        shed = 0
        for i, img in enumerate(images):
            try:
                if sched is not None:
                    futs.append(
                        sched.submit(
                            img,
                            deadline_ms=args.deadline_ms,
                            tenant=(
                                tenant_names[i % len(tenant_names)]
                                if tenant_names
                                else None
                            ),
                        )
                    )
                else:
                    futs.append(rs.submit(img, deadline_ms=args.deadline_ms))
            except Exception as e:  # noqa: BLE001 — admission sheds are tallied, not fatal
                shed += 1
                futs.append(None)
                print(f"[predict] request shed: {type(e).__name__}: {e}")
            if args.interarrival_ms > 0:
                _time.sleep(args.interarrival_ms / 1000.0)
        rows, failed = [], shed
        for f in futs:
            if f is None:
                rows.append(None)
                continue
            try:
                rows.append(f.result())
            except Exception as e:  # noqa: BLE001 — typed failures are tallied, not fatal
                failed += 1
                rows.append(None)
                print(f"[predict] request failed: {type(e).__name__}: {e}")
        print(
            f"[predict] pool served {len(rows) - failed}/{len(rows)} ok "
            f"({failed} failed)"
        )
        if swap_thread is not None:
            swap_stop.set()
            swap_thread.join(timeout=args.swap_canary_timeout_s + 60.0)
        if autoscaler is not None:
            autoscaler.close()
            print(f"[predict] autoscale events: {len(autoscaler.events)}")
        if sched is not None:
            sched.close()
            if args.pack:
                st = sched.stats()
                print(
                    f"[predict] pack stats: dispatched={st['dispatched']} "
                    f"batches={st['batches']} expired={st['expired']}"
                )
            if admission is not None:
                print(f"[predict] admission: {json.dumps(admission.stats())}")
        if meter is not None:
            meter.flush()  # final tenant_usage rows before the log closes
            bill = meter.snapshot()
            costs = ", ".join(
                f"{t}={b['device_s']:.3f}s"
                for t, b in bill["tenants"].items()
            )
            print(
                f"[predict] tenant cost: {costs} "
                f"(total {bill['total_device_s']:.3f} device-s, "
                f"{bill['total_batches']} batches)"
            )
        st = rs.stats()
        print(f"[predict] replicas: {json.dumps(st['replicas'])}")
        rs.close()
        kept = [(n, r) for n, r in zip(names, rows) if r is not None]
        if not kept:
            raise SystemExit("[predict] every request failed")
        names = [n for n, _ in kept]
        rows = [r for _, r in kept]
        out = (
            {k: np.stack([r[k] for r in rows]) for k in rows[0]}
            if isinstance(rows[0], dict)
            else np.stack(rows)
        )
        if slo_tracker is not None:
            rep = slo_tracker.evaluate()
            objs = "; ".join(
                f"{o['name']}: value={o['value']:g} "
                f"burn={o['burn_slow']:g} breached={o['breached']}"
                for o in rep["objectives"]
            )
            print(
                f"[predict] SLO verdict: degraded={rep['degraded']} "
                f"shed_rate={rep['shed_rate']:g} — {objs}"
            )
            if tracer is not None:
                tracer.event("slo_summary", report=rep)
        if tracer is not None:
            tracer.close()
    elif args.serve:
        def run_fn(batch):
            if health is not None:
                health.beat("infer_batch")
            if retrace_sentinel is None:
                return engine.predict(batch, task=args.task, **kw)
            retrace_sentinel.note("serve_batch", batch)
            out = engine.predict(batch, task=args.task, **kw)
            retrace_sentinel.arm()  # first batch served: steady state
            return out

        with MicroBatcher(
            run_fn,
            max_batch=args.max_batch,
            max_delay_ms=args.max_delay_ms,
            max_queue=args.max_queue,
            tracer=tracer,
            task=args.task,
        ) as mb:
            if health is not None:
                # live autoscaler snapshot (queue depth / occupancy / shed
                # rate) in the /healthz info payload while serving
                health.probe("serving", mb.stats)
            if mem_accountant is not None:
                mem_accountant.register(
                    "batcher_queue", lambda: mb.stats()["queue_bytes"]
                )
            if slo_tracker is not None:
                # ...and the same signals as slo_* gauges per scrape
                slo_tracker.add_probe(
                    "queue_depth", lambda: mb.stats()["queue_depth"]
                )
                slo_tracker.add_probe(
                    "batch_occupancy", lambda: mb.stats()["batch_occupancy"]
                )
            rows = [
                f.result()
                for f in [
                    mb.submit(img, deadline_ms=args.deadline_ms)
                    for img in images
                ]
            ]
        out = (
            {k: np.stack([r[k] for r in rows]) for k in rows[0]}
            if isinstance(rows[0], dict)
            else np.stack(rows)
        )
        print(f"[predict] micro-batch sizes: {mb.batch_sizes}")
        if slo_tracker is not None:
            rep = slo_tracker.evaluate()
            objs = "; ".join(
                f"{o['name']}: value={o['value']:g} "
                f"burn={o['burn_slow']:g} breached={o['breached']}"
                for o in rep["objectives"]
            )
            print(
                f"[predict] SLO verdict: degraded={rep['degraded']} "
                f"shed_rate={rep['shed_rate']:g} — {objs}"
            )
            if tracer is not None:
                tracer.event("slo_summary", report=rep)
        if tracer is not None:
            tracer.close()
    else:
        out = engine.predict(images, task=args.task, **kw)

    if args.task == "logits":
        probs = np.exp(out - out.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        k = min(args.topk, out.shape[-1])
        for name, p_row in zip(names, probs):
            top = np.argsort(-p_row)[:k]
            print(
                json.dumps(
                    {
                        "image": name,
                        "classes": top.tolist(),
                        "probs": [round(float(p_row[i]), 6) for i in top],
                    }
                )
            )
    payload = out if isinstance(out, dict) else {args.task: out}
    result: Path | None = None
    if args.out:
        result = Path(args.out)
        result.parent.mkdir(parents=True, exist_ok=True)
        np.savez(result, **payload)
        print(f"[predict] wrote {args.task} for {len(names)} image(s) -> {result}")
    if retrace_sentinel is not None:
        rsum = retrace_sentinel.summary()
        print(
            f"[predict] retrace sentinel: {rsum['violations']} unexpected "
            f"recompile(s) after warmup ({rsum['compiles']} compiles seen, "
            f"{rsum['expected']} expected)"
        )
        retrace_sentinel.close()
    if telemetry is not None:
        if args.metrics_hold_s > 0:
            import time

            print(
                f"[predict] holding exporter for {args.metrics_hold_s:g}s "
                f"(scrape :{telemetry.port}/metrics)"
            )
            time.sleep(args.metrics_hold_s)
        telemetry.close()
    return result


if __name__ == "__main__":
    main()
