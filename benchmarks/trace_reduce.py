"""From a profiler trace (``.xplane.pb``) to busy time, the window, the top
device operations, the programs that ran and the idle gaps. Every number
here comes from the trace; nothing is taken from the host's clock and
nothing is clamped, so a busy share over 100% shows as one.

Device operations are the events of the ``XLA Ops`` line of each
``/device:`` plane, and a program's runs are the events of its ``XLA
Modules`` line. ``busy_s`` is the union of the operations' intervals and
``window_s`` the union of the programs' intervals: the time in which a
compiled program held the device. The time between two programs is not in
the window, because under the profiler it is not the loop's: the tracer
drains its buffers there (ten B/16 steps took 5.16 s traced against 2.04 s
untraced, with the same device time a step). It is reported as the gap
``between_programs_traced``, beside the gaps inside programs, which are
named by the harness span they fall in.

On a backend with no device plane (the CPU rehearsal) the operations are the
host-thread events that carry an ``hlo_op`` stat, there are no program
events, and the window is the span from the first event to the last.
"""

from __future__ import annotations

import statistics
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAMED_GAPS = 4000  # the longest gaps get a span's name, the rest one bucket


def op_name(event_name: str) -> str:
    """XLA's own name of an operation: a TPU trace gives the whole HLO line
    (``%fusion.12 = bf16[...] fusion(...)``), of which this keeps
    ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def _union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _line_events(planes, line_name):
    """``{device plane: [(start_ns, end_ns, name), ...]}`` of one line."""
    out = {}
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != line_name:
                continue
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
            if evs:
                out.setdefault(plane.name, []).extend(evs)
    return out


def device_events(planes):
    """``{device name: [(start_ns, end_ns, op name), ...]}``."""
    out = _line_events(planes, OPS_LINE)
    if out:
        return out
    evs = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0 and any(k == "hlo_op" for k, _ in e.stats):
                    evs.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return {"host-as-device": evs} if evs else {}


def host_spans(planes, names):
    """``[(start_ns, end_ns, name), ...]`` of the harness's own spans."""
    names = set(names)
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return sorted(spans)


def _span_at(spans, t):
    """Name of the innermost (shortest) span that covers ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside_spans"


def _inside(gaps, windows):
    """The parts of the sorted, disjoint ``gaps`` that lie inside the
    sorted, disjoint ``windows``."""
    out, j = [], 0
    for a, b in gaps:
        while j < len(windows) and windows[j][1] <= a:
            j += 1
        k = j
        while k < len(windows) and windows[k][0] < b:
            s, e = max(a, windows[k][0]), min(b, windows[k][1])
            if e > s:
                out.append((s, e))
            k += 1
    return out


def reduce_planes(planes, span_names, top: int = 10) -> dict:
    planes = list(planes)
    per_device = device_events(planes)
    if not per_device:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [], "idle_gaps": [],
                "programs": []}
    programs = _line_events(planes, MODULES_LINE)
    spans = host_spans(planes, span_names)
    lo = min([s for evs in per_device.values() for s, _, _ in evs] + [s for s, _, _ in spans])
    hi = max([e for evs in per_device.values() for _, e, _ in evs] + [e for _, e, _ in spans])
    n = len(per_device)
    busy = window = 0.0
    by_op, gaps, runs = defaultdict(float), defaultdict(float), defaultdict(list)
    for device, evs in per_device.items():
        merged = _union((s, e) for s, e, _ in evs)
        busy += sum(e - s for s, e in merged)
        for s, e, name in evs:
            by_op[op_name(name)] += (e - s) / n
        if device in programs:
            held = _union((s, e) for s, e, _ in programs[device])
            for s, e, name in programs[device]:
                runs[name.split("(", 1)[0]].append(e - s)  # without its fingerprint
            between = (held[-1][1] - held[0][0]) - sum(e - s for s, e in held)
            if between:
                gaps["between_programs_traced"] += between / n
        else:
            held = [[lo, hi]]
        window += sum(e - s for s, e in held)
        edges = [held[0][0]] + [t for iv in merged for t in iv] + [held[-1][1]]
        idle = _inside([(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a], held)
        idle.sort(key=lambda g: g[0] - g[1])  # longest first
        for a, b in idle[:NAMED_GAPS]:
            gaps[_span_at(spans, (a + b) / 2)] += (b - a) / n
        if idle[NAMED_GAPS:]:
            gaps["short_gaps_between_ops"] += sum(b - a for a, b in idle[NAMED_GAPS:]) / n
    rank = lambda d: [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "busy_s": busy / n / 1e9,
        "window_s": window / n / 1e9,
        "device_ops": rank(by_op),
        "idle_gaps": rank(gaps),
        # [name, runs, seconds in all, median seconds a run], over all devices
        "programs": [[name, len(d), sum(d) / 1e9, statistics.median(d) / 1e9]
                     for name, d in sorted(runs.items(), key=lambda kv: -sum(kv[1]))[:top]],
    }


def reduce_file(path: str, span_names, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, span_names, top)
