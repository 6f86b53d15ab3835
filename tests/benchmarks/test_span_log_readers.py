"""The readers of the program's span log (``jit_trace_s``, ``jit_lower_s``,
``cache_load_s``, ``setup_spanned_share``) and the rule that finds this run's
set-up in a log the whole process shares: on made-up logs of both shapes
(the benchmark's command, and a caller that runs several cells a process),
and in the CPU rehearsal of one ViT and one language cell, one in each shape."""

import sys
import time

import pytest

from bench_tiny import ROOT, load_bench, set_tiny_limits, tiny_cell
from benchmarks import flops, harness, span_log

BENCH = load_bench()
READERS = ("jit_trace_s", "jit_lower_s", "cache_load_s", "setup_spanned_share")
# (cell, whether the run is found as the benchmark's command finds it)
RUNS = [("l16_pretrain_b128", True), ("joyai_flash_pretrain_2x8k", False)]


def rec(name, start, end, rid=0, parent=None, thread=1):
    return {"id": rid, "parent": parent, "name": name, "start": start, "end": end,
            "thread": thread}


def one_run(at):
    """A run's records from ``at``: the mesh, the state, a compile after it."""
    return [rec("mesh_build", at + 0.01, at + 0.02), rec("state_shapes", at + 0.1, at + 1.0),
            rec("state_init", at + 1.0, at + 3.0), rec("backend_compile:step", at + 3.0, at + 8.0)]


@pytest.mark.parametrize("main_t0, want", [
    (100.0, (100.0, 109.0)),   # the command: run.py's T0 holds the state
    (None, (100.01, 109.01)),  # a caller with no T0: from the mesh, the first call
    (50.0, (100.01, 109.01)),  # a T0 too old to hold the state is not this run's
    (102.0, (100.01, 109.01)),  # nor is one taken after the state began
])
def test_setup_window_in_both_shapes(main_t0, want):
    earlier = one_run(60.0) + [rec("backend_compile:reference", 75.0, 80.0)]
    log = earlier + one_run(100.0) + [rec("h2d", 109.2, 109.2004)]
    got = span_log.setup_window({"setup_s": 9.0}, log, main_t0=main_t0 or object())
    assert got == pytest.approx(want)


@pytest.mark.parametrize("log, record", [
    ([], {"setup_s": 9.0}),                                    # an empty log
    ([rec("mesh_build", 1.0, 1.1)], {"setup_s": 9.0}),         # no state built
    (one_run(100.0), {}),                                      # a record without setup_s
    (one_run(100.0)[1:], {"setup_s": 9.0}),                    # no mesh before the state
    (one_run(100.0), {"setup_s": 2.0}),                        # a set-up too short to hold the state
])
def test_setup_window_finds_nothing(log, record):
    assert span_log.setup_window(record, log, main_t0=object()) is None


def test_union_counts_nested_and_overlapping_seconds_once():
    assert span_log.union_s([(0, 4), (1, 2), (3, 6), (8, 9)]) == 7
    assert span_log.union_s([]) == 0


def test_a_program_without_the_log_gives_nothing(monkeypatch):
    """The parent of the PR that brought the log: every reader returns None."""
    from jumbo_mae_tpu_tpu.obs import metrics  # any module of the program without ``spans``

    monkeypatch.setitem(sys.modules, "jumbo_mae_tpu_tpu.obs.trace", metrics)
    assert span_log.program_log() is None
    for name in READERS:
        assert harness.load_module("metrics", name).read({"setup_s": 9.0}) is None


def test_setup_tree_names_what_no_record_covers():
    """``tools/setup_tree.py``: the harness's clock lines as phases with the
    main thread's union inside each, and the uncovered stretches by their
    neighbours, longest first."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("setup_tree", ROOT / "tools" / "setup_tree.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    import threading

    main = threading.main_thread().ident
    records = [r | {"thread": main} for r in one_run(100.0)]
    records.append(rec("jit_trace:elsewhere", 100.0, 109.0, thread=main + 1))
    clock = {"devices found": 0.05, "driver built": 3.5}
    got = tool.phases(clock, 9.0, records, 100.0)
    assert [p["until"] for p in got] == list(tool.PHASES)
    assert [round(p["spanned_s"], 2) for p in got] == [0.01, 3.4, 4.5]
    assert sum(p["seconds"] for p in got) == pytest.approx(9.0)
    gaps = tool.gaps(records, 100.0, 9.0)
    assert [(g["after"], g["before"]) for g in gaps] == [
        ("backend_compile:step", "warm: window starts")]
    assert gaps[0]["seconds"] == pytest.approx(1.0) and gaps[0]["at"] == pytest.approx(8.0)
    assert tool.gaps(records, 100.0, 9.0, least_s=0.05)[1]["after"] == "mesh_build"


@pytest.fixture(scope="module", params=RUNS, ids=lambda run: run[0])
def traced_run(request, tmp_path_factory):
    """One traced CPU rehearsal of the cell: the record its readers saw, the
    result, and the ``t0`` it was run from."""
    name, as_command = request.param
    seen = {}
    real = harness._metric_values
    with pytest.MonkeyPatch.context() as mp:
        set_tiny_limits(mp, BENCH)
        mp.setattr(flops, "peak", lambda kind, key="bf16_flops": 1e12)
        mp.setattr(harness, "_metric_values",
                   lambda entries, record, root: seen.update(record=record)
                   or real(entries, record, root))
        t0 = time.perf_counter()
        if as_command:
            mp.setattr(sys.modules["__main__"], "T0", t0, raising=False)
        result = harness.run_cell(
            tiny_cell(harness.load_cell(name)), seed=2_147_483_999, seconds=0.6, trace=True,
            t0=t0, require_tpu=False, compile_cache=False, scratch=tmp_path_factory.mktemp(name))
        yield {"record": seen["record"], "result": result, "t0": t0, "as_command": as_command,
               "read": {n: harness.load_module("metrics", n).read(seen["record"])
                        for n in READERS}}


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_a_number_in_the_rehearsal(traced_run, name):
    value = traced_run["result"]["metrics"][name]["value"]
    assert value == traced_run["read"][name] and value >= 0
    if name in ("jit_trace_s", "jit_lower_s", "setup_spanned_share"):
        assert value > 0  # the set-up of a cell traces and lowers its step


def test_readers_stay_inside_what_holds_them(traced_run):
    record, read = traced_run["record"], traced_run["read"]
    assert read["setup_spanned_share"] <= 100
    assert read["cache_load_s"] <= record["compile_s"]
    assert read["jit_trace_s"] + read["jit_lower_s"] <= record["setup_s"]


def test_the_window_is_this_runs_own(traced_run):
    """Set-up is counted from the caller's ``t0``: exactly where the process
    is the benchmark's command, to the milliseconds before the mesh is built
    where it is not; and it holds this run's state and step and no other."""
    record = traced_run["record"]
    log = span_log.program_log()
    start, end = span_log.setup_window(record, log)
    assert end - start == pytest.approx(record["setup_s"])
    if traced_run["as_command"]:
        assert start == traced_run["t0"]
    else:
        assert 0 <= start - traced_run["t0"] < 0.5
    inside = [r["name"] for r in span_log.setup_records(record)]
    assert inside.count("state_init") == 1 and inside.count("program_build:train_step") == 1
    assert inside.count("mesh_build") == 1


def test_a_jit_compiled_after_the_window_changes_nothing(traced_run):
    import jax
    import numpy as np

    jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(np.ones(5, np.float32)))
    assert any(r["name"] == "backend_compile:<lambda>" for r in span_log.program_log()[-4:])
    for name, before in traced_run["read"].items():
        assert harness.load_module("metrics", name).read(traced_run["record"]) == before
