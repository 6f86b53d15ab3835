"""Read the numbers that decide ``correct`` over many seeds in one process,
for sound runs of the program and for the lower-precision control: the two
readings each limit in ``drivers/*.limits.json`` is set from.

``python3 benchmarks/check.py --workload <cell> --seeds 1,2,3 --control 1,2,3``
prints one JSON line per seed and kind. Not part of a benchmark run.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    from benchmarks import harness

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--any-platform", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices = harness.open_devices(cell["chips"], require_tpu=not args.any_platform)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control.split(",") if s}
    driver = harness.load_module("drivers", cell["traffic"]["driver"], cell["root"])
    for row in driver.limit_readings(cell, devices=devices, seeds=seeds,
                                     control_seeds=control):
        row["checks"] = {name: value for name, value, _ in row["checks"]}
        print(json.dumps({"workload": args.workload, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
