"""The benchmark's command.

``python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the accelerator this
machine holds and prints the result as the last line of its output.
"""

import sys
import time

T0 = time.perf_counter()  # set-up is counted from here

from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

if __name__ == "__main__":
    from benchmarks import harness

    sys.exit(harness.main(sys.argv[1:], t0=T0))
