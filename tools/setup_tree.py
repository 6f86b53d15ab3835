#!/usr/bin/env python
"""One benchmark cell's set-up as the span log's tree.

``python3 tools/setup_tree.py --workload <cell> --seed <n> [--seconds 20]
[--trace 1] [--out FILE.json]`` runs the cell exactly as ``benchmarks/run.py``
does (this file's ``T0`` stands where that file's does, so the readers of the
span log find the same set-up) and then prints what ``setup_s`` was made of:

- the harness's own clock lines (``devices found``, ``driver built``, ``warm:
  window starts``) as three phases, each with the seconds some record of the
  main thread covers and the remainder no record saw;
- ``obs.trace.setup_report`` of ``[T0, T0 + setup_s]``: every top-level record
  with its self time and its children by kind, two levels deep;
- the longest stretches no record covers, each named by the records on either
  side of it;
- the four per-layer metrics that read the log, and the record count.

PERF.md section 5's "set-up by record" tables are this output. On the chip:
``chiprun -- python3 tools/setup_tree.py --workload l16_pretrain_b128 --seed 7``.
"""

import sys
import time

T0 = time.perf_counter()  # set-up is counted from here, as in benchmarks/run.py

import argparse
import io
import json
import re
import threading
from importlib import import_module
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

PHASES = ("devices found", "driver built", "warm: window starts")
READERS = ("jit_trace_s", "jit_lower_s", "cache_load_s", "setup_spanned_share")


class _Tee(io.TextIOBase):
    def __init__(self, stream):
        self.stream, self.kept = stream, []

    def write(self, text):
        self.kept.append(text)
        return self.stream.write(text)

    def flush(self):
        self.stream.flush()


def phases(clock: dict, setup_s: float, records: list, t0: float) -> list[dict]:
    """The harness's clock lines as intervals of set-up, each with the union
    of the main thread's records inside it."""
    tr = import_module("jumbo_mae_tpu_tpu.obs.trace")
    main = threading.main_thread().ident
    edges = [0.0] + [clock[p] for p in PHASES[:-1] if p in clock] + [setup_s]
    names = [p for p in PHASES[:-1] if p in clock] + [PHASES[-1]]
    out = []
    for name, a, b in zip(names, edges, edges[1:]):
        seen = tr.union_seconds(
            (max(r["start"], t0 + a), min(r["end"], t0 + b)) for r in records
            if r["thread"] == main and r["end"] > t0 + a and r["start"] < t0 + b)
        out.append({"until": name, "seconds": b - a, "spanned_s": seen,
                    "unspanned_s": b - a - seen})
    return out


def gaps(records: list, t0: float, setup_s: float, least_s: float = 0.2) -> list[dict]:
    """The stretches of set-up no record of the main thread covers, longest
    first, each between the records that end and begin it: what the log cannot
    name is named by its neighbours."""
    main = threading.main_thread().ident
    mine = sorted((r for r in records if r["thread"] == main), key=lambda r: r["start"])
    out, reach, last = [], t0, "T0"
    for r in mine + [{"name": "warm: window starts", "start": t0 + setup_s, "end": t0 + setup_s}]:
        if r["start"] - reach >= least_s:
            out.append({"at": reach - t0, "seconds": r["start"] - reach, "after": last,
                        "before": r["name"]})
        if r["end"] > reach:
            reach, last = r["end"], r["name"]
    return sorted(out, key=lambda g: -g["seconds"])


def run(argv, *, t0: float = T0, **main_kwargs) -> dict:
    from benchmarks import harness

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    seen, real = {}, harness._metric_values
    harness._metric_values = lambda entries, record, root: (
        seen.update(record=record) or real(entries, record, root))
    tee = _Tee(sys.stdout)
    sys.stdout = tee
    try:
        rc = harness.main(["--workload", args.workload, "--seed", str(args.seed), "--seconds",
                           str(args.seconds), "--trace", str(args.trace)], t0=t0, **main_kwargs)
    finally:
        sys.stdout, harness._metric_values = tee.stream, real
    if rc or "record" not in seen:
        raise SystemExit(rc or 1)
    tr = import_module("jumbo_mae_tpu_tpu.obs.trace")
    record, text = seen["record"], "".join(tee.kept)
    setup_s = record["setup_s"]
    clock = {what: float(at) for at, what in re.findall(r"\[\s*([\d.]+) s\] (.+)", text)}
    report = tr.setup_report(t0, t0 + setup_s)
    inside = [r for r in tr.spans() if r["start"] >= t0 and r["end"] <= t0 + setup_s]
    out = {
        "workload": args.workload, "seed": args.seed, "setup_s": setup_s,
        "compile_s": record["compile_s"], "clock": clock,
        # the measured window of a traced run is not traced: its rate, for the run beside it
        "train_img_per_s": harness.load_module("metrics", "train_img_per_s").read(record),
        "process_start_to_t0_s": None if tr.process_start() is None else t0 - tr.process_start(),
        "phases": phases(clock, setup_s, inside, t0),
        "gaps": gaps(inside, t0, setup_s),
        "metrics": {n: harness.load_module("metrics", n).read(record) for n in READERS},
        "records_in_setup": len(inside), "records_in_log": len(tr.spans()),
        # the main thread's top-level records follow one another, so with what
        # the phases did not see they make up setup_s
        "top_level_s": sum(n["seconds"] for n in report["roots"] if n["main"]),
        "report": report,
    }
    print(f"[setup_tree] {args.workload} seed {args.seed}: setup_s {setup_s:.2f}, compile_s "
          f"{record['compile_s']:.2f}, {len(inside)} records in set-up "
          f"({out['records_in_log']} in the log at the end)")
    for p in out["phases"]:
        print(f"[setup_tree] until `{p['until']}`: {p['seconds']:.2f} s = {p['spanned_s']:.2f} "
              f"under records + {p['unspanned_s']:.2f} unseen")
    for line in tr.format_setup_report(report, min_s=0.2):
        print(f"[setup_tree] {line}")
    for g in out["gaps"][:8]:
        print(f"[setup_tree] unseen {g['seconds']:.2f} s at {g['at']:.2f} s, after `{g['after']}` "
              f"before `{g['before']}`")
    unseen = sum(p["unspanned_s"] for p in out["phases"])
    print(f"[setup_tree] top-level records {out['top_level_s']:.2f} s + unseen {unseen:.2f} s = "
          f"{out['top_level_s'] + unseen:.2f} s of setup_s {setup_s:.2f}")
    print(f"[setup_tree] metrics {json.dumps(out['metrics'])}; train_img_per_s "
          f"{out['train_img_per_s']}", flush=True)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    run(sys.argv[1:])
