"""Offline batch inference entry point: tar shards → durable part files.

Runs a resumable :class:`~jumbo_mae_tpu_tpu.batch.BatchJobRunner` over the
full serving stack — continuous scheduler, tenant admission (the job is a
budget-capped ``batch``-class tenant by default), cost meter, supervised
replica pool — so an offline dataset pass shares capacity, admission, and
chargeback with interactive traffic instead of bypassing them.

    python -m jumbo_mae_tpu_tpu.cli.batch shard-{0..9}.tar --out runs/job1
    # killed? preempted? just run the same command again: it resumes
    # sample-exactly and the final manifest is byte-identical

SIGTERM/SIGINT request a graceful drain: workers finish their in-flight
window, release their shard leases, and the job exits resumable (the
driver's preemption contract). A second signal aborts hard — which is
also safe, only slower to resume.

Without ``--config`` a deterministic service-time model stands in for the
engine (CI and smoke tests); with it, real ``InferenceEngine`` replicas
serve the job. The last stdout line is one JSON summary object (manifest
path, samples, lease steals, replica preemptions, per-tenant usage) for
scripted callers.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import time

import numpy as np

from jumbo_mae_tpu_tpu.batch import BatchJobRunner, JobSpec


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("shards", nargs="+", help="tar shard URLs/paths, in order")
    p.add_argument("--out", required=True, help="job output directory")
    p.add_argument("--task", default="features")
    p.add_argument("--tenant", default="batch")
    p.add_argument(
        "--tenants",
        default="batch=batch",
        help="tenant spec list (serve.parse_tenants syntax); the job "
        "submits as --tenant and shares the gate with any others listed",
    )
    p.add_argument("--workers", type=int, default=2, help="shard-parallel job workers")
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-delay-ms", type=float, default=2.0)
    p.add_argument("--max-queue", type=int, default=64)
    p.add_argument("--lease-s", type=float, default=30.0, help="shard lease horizon")
    p.add_argument("--submit-window", type=int, default=8)
    p.add_argument("--deadline-ms", type=float, default=None)
    p.add_argument("--config", default=None, help="model config -> real engine replicas")
    p.add_argument("--service-overhead-ms", type=float, default=1.0)
    p.add_argument("--service-per-item-ms", type=float, default=0.2)
    p.add_argument("--model-gflops-per-item", type=float, default=1.0)
    return p


class _StubEngine:
    """Deterministic service-time model (same role as loadgen's): output
    depends only on the input bytes, so restarted jobs recompute
    byte-identical part files."""

    def __init__(self, overhead_s: float, per_item_s: float):
        self.overhead_s = overhead_s
        self.per_item_s = per_item_s

    def run(self, batch: np.ndarray) -> list[dict]:
        time.sleep(self.overhead_s + len(batch) * self.per_item_s)
        return [
            {"sum": int(row.astype(np.int64).sum()), "dim": int(row.size)}
            for row in batch
        ]


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)

    from jumbo_mae_tpu_tpu.infer.replicaset import ReplicaSet
    from jumbo_mae_tpu_tpu.obs import AccessLog, RequestTracer
    from jumbo_mae_tpu_tpu.obs.journal import read_journal
    from jumbo_mae_tpu_tpu.serve import (
        AdmissionController,
        ContinuousScheduler,
        CostMeter,
        default_cost_fn,
        parse_tenants,
    )

    tenants = parse_tenants(args.tenants)
    access_dir = f"{args.out}/access"
    access = AccessLog(access_dir)
    tracer = RequestTracer(access_log=access)

    if args.config:
        from jumbo_mae_tpu_tpu.config import load_config
        from jumbo_mae_tpu_tpu.infer import InferenceEngine
        from jumbo_mae_tpu_tpu.utils.procenv import enable_compile_cache

        enable_compile_cache()
        cfg = load_config(args.config, [])

        def provider(idx):
            return InferenceEngine(cfg, max_batch=args.max_batch)

        def run(engine, batch, metas):
            return engine.predict(batch, task=args.task)

        cost_fn = default_cost_fn
    else:
        overhead = args.service_overhead_ms / 1000.0
        per_item = args.service_per_item_ms / 1000.0

        def provider(idx):
            return _StubEngine(overhead, per_item)

        def run(engine, batch, metas):
            return engine.run(batch)

        flops_per_row = args.model_gflops_per_item * 1e9

        def cost_fn(engine, task, bucket):
            return {"flops": bucket * flops_per_row}

    # continuous mode headroom: the scheduler's accumulator is the
    # admission-visible queue; the pool takes dispatched groups above it
    meter = CostMeter(tenants, cost_fn=cost_fn, tracer=tracer)
    rs = ReplicaSet(
        provider,
        run,
        replicas=args.replicas,
        max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms,
        max_queue=args.max_queue + 2 * args.max_batch,
        tracer=tracer,
        task=args.task,
        costmeter=meter,
    )
    admission = AdmissionController(tenants, meter=meter)
    sched = ContinuousScheduler(
        rs.submit_group,
        max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms,
        max_queue=args.max_queue,
        admission=admission,
        tracer=tracer,
        task=args.task,
    )
    admission.set_pressure_fn(lambda: max(sched.pressure(), rs.pressure()))

    spec = JobSpec(
        shards=tuple(args.shards),
        output_dir=args.out,
        task=args.task,
        tenant=args.tenant,
        workers=args.workers,
        submit_window=args.submit_window,
        lease_s=args.lease_s,
        deadline_ms=args.deadline_ms,
    )
    runner = BatchJobRunner(spec, sched.submit)

    def _drain(signum, frame):
        # first signal: graceful, resumable drain; a repeat falls through
        # to the default handler (hard kill — still resumable, just rude)
        print(f"[batch] signal {signum}: draining (resumable)", file=sys.stderr)
        runner.request_stop()
        signal.signal(signum, signal.SIG_DFL)

    signal.signal(signal.SIGTERM, _drain)
    signal.signal(signal.SIGINT, _drain)

    try:
        summary = runner.run()
    finally:
        sched.close()
        rs.close()
        meter.flush()  # final tenant_usage rows before the log closes
        tracer.close()

    # per-tenant usage + preemptions from the access journal: what the
    # costmeter billed and what the pool survived while this job ran
    usage: dict[str, dict] = {}
    preemptions = 0
    try:
        for e in read_journal(access_dir):
            if e.get("type") == "tenant_usage" and e.get("tenant"):
                usage[str(e["tenant"])] = {
                    "device_s": e.get("device_s"),
                    "requests": e.get("requests"),
                }
            elif e.get("type") == "replica_preempted":
                preemptions += 1
    except FileNotFoundError:
        pass
    summary["tenant_usage"] = usage
    summary["replica_preemptions"] = preemptions
    print(json.dumps(summary, sort_keys=True))
    return 0 if summary["complete"] else 3


if __name__ == "__main__":
    sys.exit(main())
