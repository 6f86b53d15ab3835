"""CPU rehearsal of the benchmark harness: every cell of ``BENCHMARK.json``
runs end to end at the test-sized model, through an entry that skips only
the look for a chip, and the last line has exactly the contract's keys. The
real entry refuses the CPU. A cell added later is covered by its entry."""

import json
import time
from pathlib import Path

import pytest

from bench_tiny import tiny_cell
from benchmarks import harness

pytestmark = pytest.mark.usefixtures("cpu_has_no_peak")

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes", "program_bytes"}


@pytest.fixture(autouse=True)
def _test_size(monkeypatch):
    # the committed limits are set from the chip's readings at the published
    # widths; a 2-layer 64-wide model's few-element leaves read noisier
    from benchmarks.drivers import train_steps

    monkeypatch.setitem(train_steps.LIMITS, "first_grad_norm_gap", 0.1)
    monkeypatch.setitem(train_steps.LIMITS, "param_change_norm_gap", 0.1)


def _run(name, trace, tmp_path, seconds=0.6):
    cell = tiny_cell(harness.load_cell(name))
    return cell, harness.run_cell(
        cell, seed=2_147_483_777, seconds=seconds, trace=trace,
        t0=time.perf_counter(), require_tpu=False, compile_cache=False,
        scratch=tmp_path,
    )


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end_traced(name, tmp_path):
    cell, result = _run(name, True, tmp_path)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    assert result["device"]["program_bytes"] > 0
    reported = {m["name"] for m in cell["end_to_end"]}
    # the CPU's trace has no device plane, so no reader of one finds anything
    want = {m["name"] for m in BENCH["per_layer"] if m["source"] != "device_trace"
            and m["moves"] in reported and name in m.get("workloads", [name])}
    assert set(result["metrics"]) == want and want
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    assert len(result["breakdown"]["device_ops"]) <= 10
    json.dumps(result)  # the line is printable as it stands


def _one_cell_per_driver():
    """Untraced, one cell of each driver is enough: the traced test above
    already drives every cell."""
    seen = {}
    for name in CELLS:
        seen.setdefault(harness.load_cell(name)["traffic"]["driver"], name)
    return sorted(seen.values())


@pytest.mark.parametrize("name", _one_cell_per_driver())
def test_cell_untraced_reports_its_end_to_end_metrics(name, tmp_path):
    cell, result = _run(name, False, tmp_path)
    assert set(result) == RESULT_KEYS
    assert set(result["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in result["metrics"]
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_real_entry_refuses_the_cpu(capsys):
    rc = harness.main(
        ["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        t0=time.perf_counter(), compile_cache=False,
    )
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "needs a TPU" in out.err


def test_every_metric_has_a_reader_and_every_cell_its_files():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        harness.load_module("metrics", m["name"].split(".", 1)[0])
    for name in CELLS:
        cell = harness.load_cell(name)
        harness.load_module("drivers", cell["traffic"]["driver"])
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]


def test_benchmark_alone_refuses_to_run(tmp_path):
    """In a directory that holds only ``BENCHMARK.json`` and the files under
    ``paths`` there is no program to measure: non-zero exit, no result."""
    import shutil
    import subprocess
    import sys

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no program beside the benchmark" in r.stderr
