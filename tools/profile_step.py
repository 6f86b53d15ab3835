#!/usr/bin/env python3
"""Per-op device-trace breakdown of one bench train step.

Captures a ``jax.profiler`` trace of the bench step (same builder as
bench.py, so the profiled program IS the benched program) and aggregates
device-track op durations by ``hlo_category`` plus the top self-time ops —
the table PERF_ARCHIVE.md's "Where a step goes" is built from, as one command:

    python tools/profile_step.py --model vit_h14 --steps 5 --out /tmp/h14

Capture runs through the ``obs/trace.py`` helpers (the same ones
``run.profile_dir`` / ``run.chrome_trace`` use), so alongside the XLA
device trace it writes a host-side span timeline
(``<out>/host_spans.trace.json``) in the SAME chrome-trace format as a
training run's ``run.chrome_trace`` — and merges both onto ONE timeline
(``<out>/combined.trace.json``: device tracks + a 'host spans' track) so
a single Perfetto tab shows dispatch gaps against device programs.
``--journal RUN_DIR`` appends a ``profile`` event with the artifact paths
to the run's journal, so ``run_doctor`` can point at the capture.

The reference had no profiling surface at all (SURVEY §5).
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from jumbo_mae_tpu_tpu.obs.trace import (  # noqa: E402
    export_chrome_trace,
    span_timer,
    start_chrome_trace,
    trace,
)


def capture(
    model: str, steps: int, out_dir: str, batch: int | None
) -> tuple[str, str]:
    """Returns ``(device_trace_path, host_span_trace_path)``."""
    import jax

    import bench

    if batch is not None:
        os.environ["BENCH_BATCH"] = str(batch)
    batch_size = int(
        os.environ.get("BENCH_BATCH", str(bench.MODELS[model]["batch"]))
    )
    step, state, batch_dev, _ = bench.build_step("bfloat16", batch_size, model)
    for _ in range(3):  # compile + warm
        state, metrics = step(state, batch_dev)
    jax.block_until_ready(metrics["loss"])

    # device trace + host spans through the shared obs/trace helpers: the
    # span timeline (dispatch per step, then the sync) lands in the same
    # chrome-trace JSON shape run.chrome_trace produces
    start_chrome_trace()
    sp_step = span_timer("profile_step")
    sp_sync = span_timer("block_until_ready")
    with trace(out_dir):
        for _ in range(steps):
            with sp_step:
                state, metrics = step(state, batch_dev)
        with sp_sync:
            jax.block_until_ready(metrics["loss"])
    host_trace = export_chrome_trace(
        os.path.join(out_dir, "host_spans.trace.json")
    )

    traces = glob.glob(
        os.path.join(out_dir, "**", "*.trace.json.gz"), recursive=True
    )
    if not traces:
        raise FileNotFoundError(f"no trace written under {out_dir}")
    return max(traces, key=os.path.getmtime), str(host_trace)


def merge_traces(device_trace: str, host_trace: str, out_path: str) -> str:
    """One combined chrome-trace JSON: the XLA device tracks plus the host
    span track on a single timeline.

    The two captures use different clock origins (host spans stamp
    ``time.perf_counter``; the device trace has its own epoch), so host
    events are shifted to share the device trace's origin — within-capture
    ordering is exact, cross-capture alignment is to the capture window.
    Host events land under their own pid with a process_name so Perfetto
    shows them as a separate 'host spans' track.
    """
    with gzip.open(device_trace, "rt") as f:
        combined = json.load(f)
    events = combined.setdefault("traceEvents", [])
    with open(host_trace) as f:
        host_events = [
            e for e in json.load(f).get("traceEvents", []) if e.get("ph") == "X"
        ]
    if host_events:
        dev_ts = [e["ts"] for e in events if e.get("ph") == "X" and "ts" in e]
        shift = (min(dev_ts) if dev_ts else 0.0) - min(
            e["ts"] for e in host_events
        )
        host_pid = max(
            [e.get("pid", 0) for e in events if isinstance(e.get("pid"), int)],
            default=0,
        ) + 1
        events.append(
            {
                "ph": "M",
                "name": "process_name",
                "pid": host_pid,
                "args": {"name": "host spans (obs/trace)"},
            }
        )
        for e in host_events:
            events.append({**e, "ts": e["ts"] + shift, "pid": host_pid})
    combined.setdefault("displayTimeUnit", "ms")
    out = os.path.join(out_path, "combined.trace.json") if os.path.isdir(
        out_path
    ) else out_path
    with open(out, "w") as f:
        json.dump(combined, f)
    return out


def aggregate(trace_path: str, steps: int) -> tuple[dict, list, list, list]:
    """Sum device-track event durations by hlo_category and by op name,
    plus per-source-line totals and per-tf_op (time, flops, bytes) rows
    for achieved-TF/s / GB/s attribution.

    Device tracks are the pids whose process names mention the accelerator
    (\"/device:TPU\" etc.); host/python tracks are excluded so the table is
    chip time, not dispatch time.
    """
    with gzip.open(trace_path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])

    pid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e.get("args", {}).get("name", "")
    device_pids = {
        pid
        for pid, pname in pid_names.items()
        if any(t in pname.lower() for t in ("tpu", "gpu", "device", "xla"))
        and "host" not in pname.lower()
    }
    if not device_pids:
        # No device track (CPU backend). Prefer tracks whose events carry an
        # hlo_category (real op events); failing that, fall back to all host
        # tracks — those spans NEST (parent+child both counted), so totals
        # overstate wall time and are smoke-test-only.
        cat_pids = {
            e["pid"]
            for e in events
            if e.get("ph") == "X" and e.get("args", {}).get("hlo_category")
        }
        device_pids = cat_pids or set(pid_names)
        kind = "hlo-op host" if cat_pids else "HOST (nested spans double-count)"
        print(
            f"[profile_step] no device track found (tracks: "
            f"{sorted(pid_names.values())}); aggregating {kind} tracks — "
            "smoke only, host time != chip time"
        )

    by_cat: dict[str, float] = collections.defaultdict(float)
    by_op: dict[str, float] = collections.defaultdict(float)
    by_src: dict[str, float] = collections.defaultdict(float)
    # tf_op → [device_us, model_flops, raw_bytes]: per-op achieved TF/s and
    # GB/s — tells FLOP-bound from HBM-bound apart op by op, which is what
    # actually picks the next optimization (PERF_ARCHIVE.md §Round 3 workflow)
    by_tf: dict[str, list] = collections.defaultdict(lambda: [0.0, 0.0, 0.0])
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        a = e.get("args", {})
        dur_ms = e.get("dur", 0) / 1e3 / steps
        cat = a.get("hlo_category") or "(uncategorized)"
        by_cat[cat] += dur_ms
        by_op[e.get("name", "?")] += dur_ms
        if a.get("hlo_category"):  # real op events only — module spans
            # carry no category and would double-count their children
            by_src[a.get("source") or "(no source)"] += dur_ms
            r = by_tf[a.get("tf_op") or "(no tf_op)"]
            r[0] += e.get("dur", 0)
            # some trace exporters emit formatted/empty strings here —
            # skip the stat rather than abort the whole aggregation
            for i, key in ((1, "model_flops"), (2, "raw_bytes_accessed")):
                try:
                    r[i] += float(a.get(key) or 0)
                except (TypeError, ValueError):
                    pass
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:20]
    top_src = sorted(by_src.items(), key=lambda kv: -kv[1])[:15]
    top_tf = sorted(by_tf.items(), key=lambda kv: -kv[1][0])[:15]
    return dict(by_cat), top_ops, top_src, top_tf


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="vit_h14")
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument("--out", default="/tmp/profile_step")
    parser.add_argument(
        "--trace",
        default=None,
        help="skip capture; aggregate an existing .trace.json.gz",
    )
    parser.add_argument(
        "--journal",
        default=None,
        help="run dir / journal dir: append a 'profile' event with the "
        "artifact paths so run_doctor can point at this capture",
    )
    args = parser.parse_args(argv)

    host_path = combined = None
    if args.trace:
        path = args.trace
    else:
        path, host_path = capture(args.model, args.steps, args.out, args.batch)
        combined = merge_traces(path, host_path, args.out)
    if args.journal:
        from jumbo_mae_tpu_tpu.obs.journal import RunJournal, journal_dir

        loc = journal_dir(args.journal)
        jdir = loc if loc is not None and loc.is_dir() else args.journal
        with RunJournal(jdir) as j:
            j.event(
                "profile",
                model=args.model,
                steps=args.steps,
                device_trace=path,
                host_spans=host_path,
                combined_trace=combined,
            )
    by_cat, top_ops, top_src, top_tf = aggregate(path, args.steps)
    total = sum(by_cat.values())
    print(f"\ndevice time by hlo_category (ms/step, {args.steps} steps):")
    for cat, ms in sorted(by_cat.items(), key=lambda kv: -kv[1]):
        print(f"  {cat:<28} {ms:8.2f}  {100 * ms / max(total, 1e-9):5.1f}%")
    print(f"  {'TOTAL':<28} {total:8.2f}")
    print("\ntop ops by self time (ms/step):")
    for name, ms in top_ops:
        print(f"  {ms:8.3f}  {name[:100]}")
    print("\ndevice time by source line (ms/step):")
    for src, ms in top_src:
        print(f"  {ms:8.2f}  {src}")
    print("\ntop tf_ops: ms/step, achieved TF/s, GB/s (FLOP- vs HBM-bound):")
    for op, (us, flops, nbytes) in top_tf:
        secs = us / 1e6
        tf = flops / secs / 1e12 if secs else 0.0
        gb = nbytes / secs / 1e9 if secs else 0.0
        print(f"  {us / 1e3 / args.steps:8.2f} ms {tf:7.1f} TF/s {gb:7.0f} GB/s  {op[:85]}")
    print(f"\ntrace: {path}")
    if host_path:
        print(f"host spans (chrome-trace, same format as run.chrome_trace): {host_path}")
    if combined:
        print(f"combined device+host timeline: {combined}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
