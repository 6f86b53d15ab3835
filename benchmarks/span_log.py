"""What the readers of the program's span log share: the records of this
run's set-up, and the arithmetic over their intervals.

The program keeps a log of its host spans and of JAX's own trace / lower /
compile events (``jumbo_mae_tpu_tpu.obs.trace.spans()``: ``name``, ``start``,
``end`` on ``time.perf_counter()``, ``id``, ``parent``, ``thread``), for the
whole process. A reader wants one interval of it: this run's set-up,
``[t0, t0 + record["setup_s"]]``, which leaves out the traced window's
``h2d``s, the reference's compiles and, in a process that has run other
cells, theirs. The harness hands a reader the record and not ``t0``, so
``setup_window`` finds it:

- the run's own state is the newest ``state_init`` record (a driver builds
  one state a run; the reference builds none), and set-up holds it;
- under the benchmark's command ``t0`` is ``benchmarks/run.py``'s ``T0``, the
  ``__main__`` module's, taken where it holds that record;
- any other caller (the tests call ``run_cell`` with ``t0=perf_counter()``,
  several cells a process) reads the clock just before the driver is built,
  whose first call into the program is ``create_mesh``: ``t0`` is the start
  of the newest ``mesh_build`` record before that state, late by the
  milliseconds in between. So is the upper cut then, where nothing but the
  window's first sub-millisecond ``h2d`` lies.

A program without the log (the parent of the PR that brought it) gives every
reader nothing to read.
"""

from __future__ import annotations

import sys
import threading


def program_log():
    """The program's span log as a list of records, or None where the
    program keeps none."""
    try:
        from importlib import import_module

        spans = import_module("jumbo_mae_tpu_tpu.obs.trace").spans
    except (ImportError, AttributeError):
        return None
    return spans()


def setup_window(record: dict, log: list, main_t0=None):
    """``(start, end)`` of this run's set-up on the log's clock, or None where
    the log does not hold it. ``main_t0`` is the ``T0`` of the ``__main__``
    module (read from it where not given)."""
    setup_s = record.get("setup_s")
    states = [r for r in log if r["name"] == "state_init"]
    if setup_s is None or not states:
        return None
    state = states[-1]
    if main_t0 is None:
        main_t0 = getattr(sys.modules.get("__main__"), "T0", None)
    if (isinstance(main_t0, float) and main_t0 <= state["start"]
            and state["end"] <= main_t0 + setup_s):
        return main_t0, main_t0 + setup_s
    meshes = [r for r in log if r["name"] == "mesh_build" and r["end"] <= state["start"]]
    if not meshes or state["end"] > meshes[-1]["start"] + setup_s:
        return None
    return meshes[-1]["start"], meshes[-1]["start"] + setup_s


def setup_records(record: dict):
    """The records that lie inside this run's set-up, or None."""
    log = program_log()
    window = setup_window(record, log) if log else None
    if window is None:
        return None
    return [r for r in log if r["start"] >= window[0] and r["end"] <= window[1]]


def union_s(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals: records nest (a jit
    traced inside a jit's trace), so a sum would count a second twice."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def kind_union_s(record: dict, kind: str):
    """Seconds of set-up under records ``<kind>:*``, all threads."""
    records = setup_records(record)
    if records is None:
        return None
    return union_s((r["start"], r["end"]) for r in records
                   if r["name"].startswith(kind + ":"))


def main_thread_union_s(record: dict):
    """Seconds of set-up under any record of the main thread."""
    records = setup_records(record)
    if records is None:
        return None
    main = threading.main_thread().ident
    return union_s((r["start"], r["end"]) for r in records if r["thread"] == main)
