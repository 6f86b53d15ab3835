#!/usr/bin/env python3
"""Two-leg inference benchmark: naive per-request jit vs the batched engine.

Both legs run the identical forward (the feature head by default) on the identical request
stream — N single-image requests — and the JSON line reports throughput,
latency percentiles, and compile counts for each leg:

- **naive** — what a server without the engine does: one ``jax.jit``
  forward per request at the request's own shape, dispatched serially.
  Compiles lazily on the hot path (the first request pays it; a new shape
  would pay it again) and wastes the MXU on batch-1 matmuls.
- **engine** — requests submitted concurrently through the micro-batching
  queue (``max_delay_ms``, ``max_batch``), coalesced into power-of-two
  buckets served by AOT-compiled executables, all compiled during an
  explicit warmup; the measured window recompiles nothing
  (``recompiles_after_warmup`` is asserted into the JSON).
- **engine_int8** (``--quant int8``, the default) — the engine leg again
  with weight-only int8 kernels; the report carries the measured parity
  (feature cosine / top-1 agreement vs the f32 leg) next to the speedup,
  so the accuracy cost of the throughput win is never quoted separately.

``--warm-start on`` (default) additionally runs the persistent-warmup A/B:
two fresh engines (the ``infer.warmcache`` restart probe, in this process —
a chip belongs to one process) against one empty cache dir — the first
compiles and publishes, the second must report ``compiles: 0`` — and records
cold vs warm startup seconds.

    python tools/bench_infer.py                         # CPU smoke config
    python tools/bench_infer.py recipes/finetune_vit_b16.yaml --ckpt C \
        --task logits --requests 2048 --max-batch 64    # chip numbers

Env-free by design — every knob is a flag; PERF_ARCHIVE.md §Inference records the
methodology and numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "recipe",
        nargs="?",
        default=None,
        help="YAML recipe (default: the CPU smoke profile — smoke_cpu.yaml "
        "at patch 16, a per-request-overhead-dominated micro config that "
        "isolates the coalescing mechanism on hosts where big batches are "
        "compute-bound; chip numbers use real recipes)",
    )
    p.add_argument("--ckpt", default="", help="checkpoint (random init if omitted)")
    p.add_argument(
        "--task", choices=("features", "logits", "reconstruct"), default="features"
    )
    p.add_argument("--requests", type=int, default=1024, help="stream length")
    p.add_argument("--clients", type=int, default=8, help="concurrent submitters")
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument(
        "--rounds",
        type=int,
        default=3,
        help="best-of-N throughput rounds per leg (same convention as the "
        "training bench — shields the ratio from scheduler noise)",
    )
    p.add_argument("--max-delay-ms", type=float, default=2.0)
    p.add_argument("--dtype", default=None, help="compute dtype override")
    p.add_argument(
        "--telemetry",
        choices=("on", "off"),
        default="on",
        help="off swaps the default registry for the no-op NullRegistry "
        "before any engine/batcher construction — the A/B leg PERF_ARCHIVE.md's "
        "exporter-overhead number comes from",
    )
    p.add_argument(
        "--metrics-port",
        type=int,
        default=None,
        metavar="PORT",
        help="expose /metrics + /healthz during the bench (0 = any free "
        "port); the final scrape is summarized into the JSON report",
    )
    p.add_argument("--naive-requests", type=int, default=0,
                   help="naive-leg stream length (default: min(requests, 128); "
                   "the serial leg is slow by construction)")
    p.add_argument(
        "--quant",
        choices=("int8", "off"),
        default="int8",
        help="run the third (weight-only quantized) engine leg and report "
        "its throughput + parity vs the f32/bf16 leg",
    )
    p.add_argument(
        "--parity-images",
        type=int,
        default=64,
        metavar="N",
        help="sample size for the quant parity check (capped at --requests)",
    )
    p.add_argument(
        "--warm-start",
        choices=("on", "off"),
        default="on",
        help="run the persistent-warmup A/B: two fresh engines against "
        "one empty cache dir; the second must load every executable "
        "(compiles=0) instead of compiling",
    )
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument(
        "--history",
        default=None,
        metavar="PATH",
        help="perf ledger to append one schema-versioned row to (default: "
        "$BENCH_HISTORY or ./BENCH_HISTORY.jsonl; 'off' disables)",
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


def _percentiles(lat_s: list[float]) -> dict:
    import numpy as np

    ms = np.asarray(lat_s) * 1000.0
    return {
        # exact quantiles over the raw per-request samples — NOT the
        # LATENCY_BUCKETS-quantized Histogram.quantile readout, whose
        # bucket-edge resolution is fine for dashboards but too coarse for
        # a bench's A/B deltas
        "p50_ms": round(float(np.percentile(ms, 50)), 3),
        "p99_ms": round(float(np.percentile(ms, 99)), 3),
        "mean_ms": round(float(ms.mean()), 3),
        "quantile_source": "exact_samples",
    }


def _trace_summary(rows: list) -> dict:
    """Per-leg trace summary: outcome counts + mean per-leg milliseconds
    over the finished traces (queue wait / coalescing / compute / fetch)."""
    out: dict = {"requests": len(rows), "outcomes": {}}
    for tr in rows:
        out["outcomes"][tr.outcome] = out["outcomes"].get(tr.outcome, 0) + 1
    for name in ("queue_wait_s", "admission_s", "compute_s", "fetch_s"):
        vals = [getattr(tr, name) for tr in rows if getattr(tr, name) is not None]
        if vals:
            out[f"mean_{name[:-2]}_ms"] = round(
                sum(vals) / len(vals) * 1000.0, 3
            )
    return out


def main(argv: list[str] | None = None) -> dict:
    args = build_parser().parse_args(argv)

    import concurrent.futures

    import jax
    import numpy as np

    from jumbo_mae_tpu_tpu.config import load_config
    from jumbo_mae_tpu_tpu.infer import InferenceEngine, MicroBatcher
    from jumbo_mae_tpu_tpu.obs import NULL_REGISTRY, TelemetryServer, set_registry

    if args.telemetry == "off":
        # must happen before the engine/batcher resolve their handles
        set_registry(NULL_REGISTRY)
    telemetry = None
    if args.metrics_port is not None:
        telemetry = TelemetryServer(port=args.metrics_port).start()
        print(f"[bench] exporter on :{telemetry.port}", file=sys.stderr)

    recipe = args.recipe
    overrides = list(args.overrides)
    if recipe is None:
        recipe = str(REPO / "recipes" / "smoke_cpu.yaml")
        # the smoke profile: few tokens per image, so per-request dispatch
        # and sub-SIMD batch-1 GEMMs — the costs coalescing removes — are
        # the dominant term even on a small CPU host
        overrides = ["model.overrides.patch_size=16"] + overrides
    cfg = load_config(recipe, overrides)
    # warm_cache=False everywhere: the bench measures the compile behavior
    # itself, so a populated host cache must not short-circuit the legs —
    # the persistent cache gets its own A/B below (--warm-start)
    engine = InferenceEngine(
        cfg,
        ckpt=args.ckpt,
        dtype=args.dtype,
        max_batch=args.max_batch,
        warm_cache=False,
    )
    size = engine.image_size
    rs = np.random.RandomState(0)
    images = rs.randint(0, 256, (args.requests, size, size, 3)).astype(np.uint8)
    kw = {"seed": 0} if args.task == "reconstruct" else {}

    # ---- naive leg: serial per-request jit dispatch at batch 1 ----------
    t = engine._task(args.task if args.task != "features" else "features")
    fn = engine._fn(args.task, "cls" if args.task == "features" else None)
    naive_fwd = jax.jit(fn)
    n_naive = args.naive_requests or min(args.requests, 128)
    extra = (np.int32(0),) if args.task == "reconstruct" else ()
    # one untimed call so the measured window shows steady-state dispatch
    # (the compile itself is reported separately below)
    t0 = time.perf_counter()
    jax.block_until_ready(naive_fwd(t["variables"], images[:1], *extra))
    naive_compile_s = time.perf_counter() - t0
    fetch = (
        (lambda o: {k: np.asarray(v) for k, v in o.items()})
        if args.task == "reconstruct"
        else np.asarray
    )
    lat = []
    naive_wall = float("inf")
    for _ in range(max(1, args.rounds)):
        t0 = time.perf_counter()
        for i in range(n_naive):
            r0 = time.perf_counter()
            fetch(naive_fwd(t["variables"], images[i : i + 1], *extra))
            lat.append(time.perf_counter() - r0)
        naive_wall = min(naive_wall, time.perf_counter() - t0)
    naive = {
        "requests": n_naive,
        "imgs_per_sec": round(n_naive / naive_wall, 2),
        **_percentiles(lat),
        "compiles": int(naive_fwd._cache_size()),
        "first_request_compile_ms": round(naive_compile_s * 1000.0, 1),
    }

    # ---- engine leg: request stream through the micro-batcher -----------
    # Two phases, because the two numbers answer different questions.
    # Throughput: open-loop — the full stream enqueued as it arrives (an
    # async server's event loop), wall time to drain it. Closed-loop
    # clients would measure THREAD WAKEUP cost, not the engine: on a
    # 1-core host, N blocking clients each pay a context switch per
    # response. Latency: closed-loop with --clients concurrent blocking
    # callers over a slice of the stream — each request's submit→result
    # time under moderate concurrency, the number an operator quotes.
    def engine_leg(eng_obj, *, traced: bool) -> dict:
        compiles_warm = eng_obj.warmup((args.task,), buckets=None)
        warm_counts = dict(eng_obj.compile_counts)

        def run_batch(batch):
            return eng_obj.predict(batch, task=args.task, **kw)

        # with telemetry on, the (traced) leg runs fully instrumented —
        # per-request contexts + engine breakdown; the measured cost IS the
        # tracing overhead the off leg A/Bs against
        trace_rows: list = []
        tracer = None
        if traced and args.telemetry == "on":
            from jumbo_mae_tpu_tpu.obs import RequestTracer

            tracer = RequestTracer(
                breakdown=eng_obj.last_breakdown, on_finish=trace_rows.append
            )

        with MicroBatcher(
            run_batch,
            max_batch=args.max_batch,
            max_delay_ms=args.max_delay_ms,
            tracer=tracer,
            task=args.task,
        ) as mb:
            engine_wall = float("inf")
            for _ in range(max(1, args.rounds)):
                t0 = time.perf_counter()
                futs = [mb.submit(img) for img in images]
                # FIFO batcher: the last future resolves last — one waiter
                # instead of one condition registration per request
                futs[-1].result()
                engine_wall = min(engine_wall, time.perf_counter() - t0)
            sizes = list(mb.batch_sizes)

            n_lat = min(args.requests, 256)
            lat = [0.0] * n_lat

            def client(idx):
                r0 = time.perf_counter()
                mb.submit(images[idx]).result()
                lat[idx] = time.perf_counter() - r0

            with concurrent.futures.ThreadPoolExecutor(args.clients) as pool:
                list(pool.map(client, range(n_lat)))

        recompiles = (
            sum(eng_obj.compile_counts.values()) - sum(warm_counts.values())
        )
        leg = {
            "requests": args.requests,
            "imgs_per_sec": round(args.requests / engine_wall, 2),
            **_percentiles(lat),
            "latency_requests": n_lat,
            "latency_clients": args.clients,
            "warmup_compiles": compiles_warm,
            "recompiles_after_warmup": recompiles,
            "mean_batch": round(float(np.mean(sizes)), 2),
            "batches": len(sizes),
        }
        if tracer is not None:
            leg["trace"] = _trace_summary(trace_rows)
        return leg

    eng = engine_leg(engine, traced=True)
    if "trace" in eng:
        # the registry's bucket-edge readout, kept alongside the exact
        # numbers and explicitly marked approximate
        from jumbo_mae_tpu_tpu.obs import get_registry

        hist = get_registry().histogram(
            "infer_request_latency_seconds",
            "request latency: submit() to resolved future",
        )
        for label, q in (("hist_p50_ms", 0.5), ("hist_p99_ms", 0.99)):
            v = hist.quantile(q) * 1000.0
            eng[label] = round(v, 3) if v != float("inf") else "inf"
        eng["hist_quantile_source"] = "bucket_edges_approximate"

    # ---- int8 leg: same stream, weight-only quantized kernels -----------
    eng_q = None
    parity = None
    if args.quant == "int8":
        from jumbo_mae_tpu_tpu.infer import parity_report

        engine_q = InferenceEngine(
            cfg,
            ckpt=args.ckpt,
            dtype=args.dtype,
            max_batch=args.max_batch,
            quant="int8",
            warm_cache=False,
        )
        eng_q = engine_leg(engine_q, traced=False)
        base = args.task.split(".", 1)[0]
        rep = engine_q._task(base).get("quant_report")
        if rep:
            eng_q["quant"] = {
                k: rep[k]
                for k in ("n_quantized", "n_kept", "bytes_before",
                          "bytes_after", "compression")
            }
        # parity is measured against the SAME reference engine the f32/bf16
        # leg ran — logits tasks compare top-1 agreement, everything else
        # compares pooled-feature cosine
        parity = parity_report(
            engine,
            engine_q,
            images[: min(args.parity_images, args.requests)],
            task="logits" if args.task == "logits" else "features",
        )

    # ---- persistent-warmup A/B: cold engine vs restarted engine ----------
    # In this process: a chip belongs to one process, and this one holds it
    # after the legs above, so a child that needed it would fail or hang.
    # Two fresh engines against one empty cache dir are the same contract —
    # the first compiles and publishes, the second must load everything.
    warm_start = None
    if args.warm_start == "on":
        import contextlib
        import tempfile

        from jumbo_mae_tpu_tpu.infer.warmcache import _probe_main

        probe_args = [
            "--task", args.task,
            "--max-batch", str(min(args.max_batch, 8)),
            "--recipe", str(recipe),
        ]
        if args.ckpt:
            probe_args += ["--ckpt", args.ckpt]
        if args.dtype:
            probe_args += ["--dtype", args.dtype]
        if overrides:
            probe_args += ["--set", *overrides]
        with tempfile.TemporaryDirectory(prefix="jumbo-warmstart-") as d:
            runs = {}
            for phase in ("cold", "warm"):
                # the probe prints its own JSON line; stdout is this
                # bench's one-line report
                with contextlib.redirect_stdout(sys.stderr):
                    runs[phase] = _probe_main(probe_args + ["--dir", d])
        cold, warm = runs["cold"], runs["warm"]
        keep = ("init_s", "warmup_s", "compiles", "warm_hits",
                "hot_path_compiles")
        warm_start = {
            "cold": {k: cold[k] for k in keep},
            "warm": {k: warm[k] for k in keep},
            # the contract CI asserts: a restarted engine performs zero
            # compiles — warmup and hot path both served from the cache
            "warm_reused": (
                warm["compiles"] == 0
                and warm["hot_path_compiles"] == 0
                and warm["warm_hits"] >= cold["compiles"]
            ),
            "warmup_speedup": round(
                cold["warmup_s"] / max(warm["warmup_s"], 1e-9), 2
            ),
        }

    report = {
        "bench": "infer",
        "task": args.task,
        "model": cfg.model.preset,
        "image_size": size,
        "backend": jax.default_backend(),
        "max_batch": args.max_batch,
        "max_delay_ms": args.max_delay_ms,
        "clients": args.clients,
        "telemetry": args.telemetry,
        "naive": naive,
        "engine": eng,
        "speedup": round(eng["imgs_per_sec"] / naive["imgs_per_sec"], 2),
    }
    if eng_q is not None:
        report["engine_int8"] = eng_q
        report["quant_parity"] = parity
        report["speedup_int8"] = round(
            eng_q["imgs_per_sec"] / naive["imgs_per_sec"], 2
        )
        report["int8_vs_base"] = round(
            eng_q["imgs_per_sec"] / eng["imgs_per_sec"], 3
        )
    if warm_start is not None:
        report["warm_start"] = warm_start
    if telemetry is not None:
        # scrape over the real socket — the same path an external Prometheus
        # takes — and record proof-of-life in the report
        from urllib.request import urlopen

        with urlopen(
            f"http://127.0.0.1:{telemetry.port}/metrics", timeout=10
        ) as resp:
            scrape = resp.read().decode()
        keys = (
            "infer_request_latency_seconds",
            "infer_batch_occupancy",
            "infer_bucket_cache_hits_total",
            "infer_bucket_cache_misses_total",
        )
        report["metrics"] = {
            "scrape_lines": len(scrape.splitlines()),
            "families_seen": [k for k in keys if k in scrape],
        }
        telemetry.close()
    _append_ledger(args, report, engine)
    line = json.dumps(report)
    print(line)
    if args.out:
        Path(args.out).write_text(line + "\n")
    return report


def _append_ledger(args, report: dict, engine) -> None:
    """One BENCH_HISTORY.jsonl row for this bench: per-leg throughput, the
    engine leg's exact latency quantiles, and the roofline prediction of the
    largest-bucket executable (from the engine's compile-time cost reports).
    Best-effort; the one-JSON-line stdout contract is unaffected."""
    try:
        from jumbo_mae_tpu_tpu.obs.perfledger import (
            append_row,
            make_row,
            resolve_history_path,
        )

        path = resolve_history_path(args.history)
        if path is None:
            return
        legs = {"naive_imgs_per_sec": report["naive"]["imgs_per_sec"],
                "engine_imgs_per_sec": report["engine"]["imgs_per_sec"]}
        if report.get("engine_int8"):
            legs["engine_int8_imgs_per_sec"] = report["engine_int8"][
                "imgs_per_sec"
            ]
        quantiles = {
            k: report["engine"][k]
            for k in ("p50_ms", "p99_ms", "mean_ms")
            if isinstance(report["engine"].get(k), (int, float))
        }
        prediction = None
        if getattr(engine, "cost_reports", None):
            from jumbo_mae_tpu_tpu.obs.costmodel import cost_asdict
            from jumbo_mae_tpu_tpu.obs.perfmodel import (
                detect_chip,
                prediction_asdict,
                roofline,
            )

            key = max(engine.cost_reports, key=lambda k: k[1])
            cost = engine.cost_reports[key]
            pred = roofline(
                cost.flops,
                cost.bytes_accessed,
                detect_chip(),
                batch=key[1],
                peak_hbm_bytes=cost.peak_bytes,
            )
            prediction = prediction_asdict(pred) | {
                "program": f"{key[0]}/b{key[1]}",
                "cost": cost_asdict(cost),
            }
        metric = (
            f"infer_{report['model']}_{report['image_size']}_"
            f"{report['task']}_imgs_per_sec"
        )
        row = make_row(
            bench="infer",
            metric=metric,
            legs=legs,
            quantiles=quantiles,
            prediction=prediction,
            extra={"max_batch": report["max_batch"]},
        )
        if append_row(path, row):
            print(f"bench_infer: ledger row -> {path}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — the ledger must not fail a bench
        print(f"bench_infer: ledger append failed: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
