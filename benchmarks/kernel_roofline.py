"""A kernel's share of its roofline: the least time the chip could take for
the work the algorithm requires (the larger of operations over the bf16 peak
and HBM bytes over the bandwidth peak, ``peaks.json``), over the device time
of the kernel's events in the traced window.

The kernel's events are the step program's Pallas calls (``tpu_custom_call``)
that ``scope_reduce`` places in the given parts; the work is what the
driver's record states for one run of the step program under
``record["kernel_work"][<name>] = {"flops": ..., "bytes": ...}`` (counted by
``flops_lm.py``: no recompute, so a share can only understate). None where
the trace has no device plane, the program keeps no text to join with, or
the record states no such work."""

from __future__ import annotations

import re

from benchmarks import flops, scope_reduce

_KERNEL = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+.*custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"')


def kernel_ms(record: dict, *parts: str):
    """Device self time (ms per run of the step program) of the Pallas calls
    in ``parts``, all phases; the trace is read once for all kernels."""
    if "_kernel_table" not in record:
        record["_kernel_table"] = _kernel_table(record)
    table = record["_kernel_table"]
    if table is None:
        return None
    return 1e3 * sum(s for (_, part), s in table.items() if part in parts) or None


def _kernel_table(record: dict):
    """``{(phase, part): seconds per run}`` of the Pallas calls alone."""
    programs = record.get("trace", {}).get("programs")
    if not programs or "scopes" not in record or "trace_file" not in record:
        return None
    name, runs = programs[0][:2]
    text = scope_reduce._hlo_text(name)
    if not text:
        return None
    from jax.profiler import ProfileData

    scopes = scope_reduce.instruction_scopes(text, scope_reduce.vocabulary(record["scopes"]))
    kernels = {m.group(1) for line in text.splitlines() if (m := _KERNEL.match(line))}
    mine = {n: s for n, s in scopes.items() if n in kernels}
    planes = ProfileData.from_file(record["trace_file"]).planes
    table = scope_reduce.by_scope(planes, mine, name, runs)
    table.pop(scope_reduce.UNSCOPED, None)  # every other instruction of the program
    return table


def share(record: dict, work: str, *parts: str):
    """Roofline share in percent of the kernel whose work is
    ``record["kernel_work"][work]``."""
    todo = record.get("kernel_work", {}).get(work)
    ms = kernel_ms(record, *parts)
    if not todo or not ms:
        return None
    kind = record["device_kind"]
    least_s = max(todo["flops"] / flops.peak(kind), todo["bytes"] / flops.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / (ms / 1e3)
