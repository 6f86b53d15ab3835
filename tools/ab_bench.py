#!/usr/bin/env python3
"""A/B matrix runner over bench.py env knobs.

Runs ``bench.py`` once per configuration (cartesian product of the swept
env knobs), one subprocess each — fresh backend, no cross-run state — and
appends every result line to a JSONL log with its knobs attached. This is
how PERF_ARCHIVE.md A/B tables are produced without babysitting:

    python tools/ab_bench.py --model vit_h14 \
        --sweep BENCH_DEC_REMAT_POLICY=,dots \
        --sweep BENCH_BATCH=64,96 \
        --sweep BENCH_MU_DTYPE=,bfloat16 \
        --skip-baseline --out /tmp/h14_ab.jsonl

Each --sweep is KNOB=v1,v2,... (empty string = unset → the MODEL'S
defaults, which for vit_h14's bf16 leg are the baked-in winners:
remat off, bf16 moments, onehot gather — bench.py MODELS). To put a
default-ON knob in its off state, sweep its explicit off spelling
instead of the empty string: BENCH_MU_DTYPE=float32,
BENCH_NU_DTYPE=float32, BENCH_GATHER_IMPL=take, BENCH_REMAT=1.
Failed runs are recorded with the tail of their stderr and the sweep
continues.

A chip belongs to one process: this parent only starts ``bench.py`` children,
one at a time, and never imports jax itself.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def parse_sweep(spec: str) -> tuple[str, list[str]]:
    knob, _, values = spec.partition("=")
    if not knob or not _:
        raise SystemExit(f"bad --sweep {spec!r}; expected KNOB=v1,v2,...")
    return knob, values.split(",")


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="vit_h14")
    parser.add_argument(
        "--sweep", action="append", default=[], help="KNOB=v1,v2,... (repeatable)"
    )
    parser.add_argument("--iters", type=int, default=None)
    parser.add_argument("--skip-baseline", action="store_true")
    parser.add_argument("--out", default=None, help="JSONL log path")
    parser.add_argument(
        "--timeout", type=float, default=1800, help="per-run seconds"
    )
    args = parser.parse_args(argv)

    sweeps = [parse_sweep(s) for s in args.sweep]
    knob_names = [k for k, _ in sweeps]
    dupes = {k for k in knob_names if knob_names.count(k) > 1}
    if dupes:
        raise SystemExit(
            f"knob(s) {sorted(dupes)} swept more than once — merge the "
            "values into one --sweep KNOB=v1,v2,..."
        )
    out_path = Path(args.out or f"/tmp/ab_{args.model}.jsonl")

    # no sweeps → one run at the defaults (product of zero iterables = [()])
    combos = list(itertools.product(*(vals for _, vals in sweeps)))
    print(f"[ab_bench] {len(combos)} configurations → {out_path}")
    results = []
    for combo in combos:
        env = dict(os.environ)
        env["BENCH_MODEL"] = args.model
        if args.iters is not None:
            env["BENCH_ITERS"] = str(args.iters)
        if args.skip_baseline:
            env["BENCH_SKIP_BASELINE"] = "1"
        setting = {}
        for (knob, _), value in zip(sweeps, combo):
            setting[knob] = value
            if value == "":
                env.pop(knob, None)
            else:
                env[knob] = value
        label = " ".join(f"{k}={v or '<unset>'}" for k, v in setting.items())
        print(f"[ab_bench] run: {label or '(defaults)'}", flush=True)
        t0 = time.monotonic()
        try:
            proc = subprocess.run(
                [sys.executable, str(REPO / "bench.py")],
                env=env,
                cwd=str(REPO),
                capture_output=True,
                text=True,
                timeout=args.timeout,
            )
            lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
            try:
                parsed = json.loads(lines[-1]) if lines else None
            except json.JSONDecodeError:
                parsed = None
            record = {
                "knobs": setting,
                "rc": proc.returncode,
                "wall_s": round(time.monotonic() - t0, 1),
                "result": parsed,
            }
            if proc.returncode != 0:
                record["stderr_tail"] = proc.stderr[-400:]
        except subprocess.TimeoutExpired as e:
            def _tail(buf):
                if not buf:
                    return ""
                s = buf if isinstance(buf, str) else buf.decode(errors="replace")
                return s[-400:]

            record = {
                "knobs": setting,
                "rc": "timeout",
                "wall_s": round(time.monotonic() - t0, 1),
                "result": None,
                # how far it got before the fuse — don't make reruns blind
                "stdout_tail": _tail(e.stdout),
                "stderr_tail": _tail(e.stderr),
            }
        results.append(record)
        with out_path.open("a") as f:
            f.write(json.dumps(record) + "\n")
        val = (record.get("result") or {}).get("value")
        print(f"[ab_bench]   → rc={record['rc']} value={val}", flush=True)

    ok = [
        r
        for r in results
        if r["rc"] == 0 and (r.get("result") or {}).get("value")
    ]
    if ok:
        best = max(ok, key=lambda r: r["result"]["value"])
        print(
            f"[ab_bench] best: {best['result']['value']} "
            f"({best['result'].get('unit', '')}) with {best['knobs']}"
        )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
