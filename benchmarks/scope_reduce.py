"""Device time by the program's own names: a trace's ``XLA Ops`` events
joined with the ``op_name`` metadata of the compiled step program.

A TPU trace names a device operation by its HLO line (``%fusion.12 = ...``)
and carries no scope, so the join goes through the instruction's name:
``instruction_scopes`` reads ``compiled.as_text()`` once, ``classify`` turns
each ``op_name`` into (phase, part), and ``by_scope`` sums the **self time**
of the events of one program's runs (an operation such as ``conditional`` or
``while`` covers its children's events; a child's time belongs to the
child), so that the table sums to the union ``trace_reduce`` calls
``busy_s``. Everything here is a pure function of the trace and the HLO
text; the readers in ``metrics/`` get both through ``table(record)``.

``op_name`` is a ``/``-separated path. JAX wraps a component in the
transform that produced it (``jvp(MAEPretrainModel)``,
``transpose(jvp(MAEPretrainModel))``), flax adds each module's name, remat
adds ``checkpoint`` / ``rematted_computation``, and the program adds the
scope vocabulary of ``jumbo_mae_tpu_tpu/obs/trace.py``. The last component
is the primitive.
"""

from __future__ import annotations

import glob
import json
import re
from collections import Counter, defaultdict
from pathlib import Path

from benchmarks.trace_reduce import MODULES_LINE, OPS_LINE, _line_events, op_name

ROOT = Path(__file__).resolve().parents[1]

PHASES = ("fwd", "recompute", "bwd", "update", "other", "unscoped")
PARTS = (
    "enc_attn_core", "enc_attn_proj", "enc_mlp", "jumbo_mlp", "enc_other",
    "dec_attn_core", "dec_attn_proj", "dec_mlp", "dec_other",
    "loss", "mask", "preprocess",
    "grad_scale", "grad_norm", "guard", "optimizer", "grad_accum",
    "rng", "metrics", "unscoped",
)
UNSCOPED = ("unscoped", "unscoped")

# a scope of the program's vocabulary that names the part by itself, most
# specific first (the optimizer sits inside the guard's cond, patchify and the
# pixel normalisation make the loss's target)
_PART_OF_SCOPE = (
    ("optimizer", "optimizer"), ("grad_norm", "grad_norm"), ("guard", "guard"),
    ("grad_scale", "grad_scale"), ("metrics", "metrics"), ("rng", "rng"),
    ("loss", "loss"), ("patchify", "loss"), ("mask", "mask"),
    ("preprocess", "preprocess"),
)
_PHASE_OF_PART = {
    "optimizer": "update", "guard": "update", "grad_norm": "update",
    "grad_scale": "update", "metrics": "other", "rng": "other",
    "grad_accum": "other",
}
_WRAPPED = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$")


def _components(name: str):
    """Split ``op_name`` at the slashes outside parentheses and peel each
    component's transform wrappers: ``(scopes, wrappers)``."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(name[start:i])
            start = i + 1
    parts.append(name[start:])
    scopes, wrappers = [], set()
    for part in parts:
        while (m := _WRAPPED.match(part)):
            wrappers.add(m.group(1))
            part = m.group(2)
        scopes.append(part)
    return scopes, wrappers


def _tower(scopes: set, side: str) -> str:
    if "attn_core" in scopes:
        return f"{side}_attn_core"
    if "attn" in scopes:  # q/k/v/out and the query scaling beside them
        return f"{side}_attn_proj"
    if "jumbo_mlp" in scopes:
        return "jumbo_mlp"
    if "mlp" in scopes:
        return f"{side}_mlp"
    return f"{side}_other"


def classify(name: str) -> tuple[str, str]:
    """``op_name`` -> (phase, part). An instruction with no scope of the
    program's (no module path, no vocabulary scope) is ``UNSCOPED``."""
    scopes, wrappers = _components(name or "")
    have = set(scopes[:-1])  # the last component is the primitive
    part = next((p for s, p in _PART_OF_SCOPE if s in have), None)
    if part is None:
        if "encoder" in have:
            part = _tower(have, "enc")
        elif "decoder" in have:
            part = _tower(have, "dec")
        elif have & {"decoder_proj", "pixel_proj"}:
            part = "dec_other"
        elif "grad_accum" in have:
            part = "grad_accum"
        else:
            return UNSCOPED
    if part in _PHASE_OF_PART:
        return _PHASE_OF_PART[part], part
    if "rematted_computation" in have:
        return "recompute", part
    if "transpose" in wrappers:
        return "bwd", part
    if "jvp" in wrappers:
        return "fwd", part
    return "other", part


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_CONTROL_FLOW = re.compile(
    r"\b(?:branch_computations=\{([^}]*)\}"
    r"|(?:true_computation|false_computation|body|condition)=(%?[\w.\-]+))")


def instruction_scopes(hlo_text: str) -> dict:
    """``{instruction name: (phase, part)}`` for every instruction of the
    module. Where its own ``op_name`` names no scope, a fusion takes the
    majority of the scoped instructions it fuses, and an instruction in a
    branch or a loop body takes the scope of the ``conditional`` or ``while``
    that runs it: the copies the compiler puts into the guard's branches
    carry no metadata, and they are the guard's cost."""
    own, inside, fused, runs_in = {}, defaultdict(list), {}, {}
    computation = None
    for line in hlo_text.splitlines():
        if (m := _INSTRUCTION.match(line)):
            name = m.group(1)
            found = _OP_NAME.search(line)
            own[name] = classify(found.group(1)) if found else UNSCOPED
            inside[computation].append(name)
            if " fusion(" in line and (c := _CALLS.search(line)):
                fused[name] = c.group(1)
            else:
                for several, one in _CONTROL_FLOW.findall(line):
                    for callee in (several or one).split(","):
                        runs_in[callee.strip().lstrip("%")] = name
        elif (m := _COMPUTATION.match(line)):
            computation = m.group(1)
    for name, callee in fused.items():
        if own[name] == UNSCOPED:
            votes = Counter(own[i] for i in inside.get(callee, ()) if own[i] != UNSCOPED)
            if votes:
                own[name] = votes.most_common(1)[0][0]
    # HLO text prints a computation before the one that uses it, so the last
    # caller seen is the outermost: resolve from there inwards
    for callee, caller in reversed(list(runs_in.items())):
        for name in inside.get(callee, ()):
            if own[name] == UNSCOPED:
                own[name] = own[caller]
    return own


def self_times(events):
    """``[(name, self_ns), ...]`` for the ``(start, end, name)`` events of one
    line: an event's duration less what the events inside it cover."""
    out, stack = [], []  # stack of [end, index into out]
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            end = min(end, stack[-1][0])  # a child never outlasts its parent
            out[stack[-1][1]][1] -= end - start
        out.append([name, end - start])
        stack.append([end, len(out) - 1])
    return [(name, ns) for name, ns in out]


def by_scope(planes, scopes: dict, program: str, runs: int) -> dict:
    """``{(phase, part): seconds per run}`` of the device operations inside
    the runs of ``program``, self time, mean over the devices."""
    planes = list(planes)
    ops = _line_events(planes, OPS_LINE)
    held = _line_events(planes, MODULES_LINE)
    total = defaultdict(float)
    for device, events in ops.items():
        windows = sorted((s, e) for s, e, name in held.get(device, ())
                         if name.split("(", 1)[0] == program)
        mine, j = [], 0
        for ev in sorted(events):
            while j < len(windows) and windows[j][1] <= ev[0]:
                j += 1
            if j < len(windows) and windows[j][0] <= ev[0]:
                mine.append(ev)
        for name, ns in self_times(mine):
            total[scopes.get(op_name(name), UNSCOPED)] += ns
    if not ops or not runs:
        return {}
    return {key: ns / 1e9 / len(ops) / runs for key, ns in total.items()}


def newest_trace() -> str | None:
    """The trace the harness left for this run: it clears the cell's
    directory first, so the newest file under the scratch is this run's."""
    files = glob.glob(str(ROOT / ".bench_scratch" / "trace" / "*" / "plugins"
                          / "profile" / "*" / "*.xplane.pb"))
    return max(files, key=lambda f: Path(f).stat().st_mtime) if files else None


def _hlo_text(program: str) -> str | None:
    """HLO text of the compiled program a trace calls ``program``, from the
    program's own record; None where the program keeps none."""
    try:
        from jumbo_mae_tpu_tpu.obs.trace import programs
    except ImportError:
        return None
    for compiled in programs().values():
        text = compiled.as_text()
        if text.split(None, 2)[1].rstrip(",") == program:
            return text
    return None


def _table(record: dict):
    programs = record.get("trace", {}).get("programs")
    path = newest_trace() if programs else None
    text = _hlo_text(programs[0][0]) if path else None
    if not text:
        return None
    from jax.profiler import ProfileData

    name, runs = programs[0][:2]
    seconds = by_scope(ProfileData.from_file(path).planes, instruction_scopes(text),
                       name, runs)
    if not seconds:
        return None
    result = {key: s * 1e3 for key, s in seconds.items()}
    rows = {ph: {pt: round(ms, 4) for (p, pt), ms in sorted(result.items()) if p == ph}
            for ph in PHASES}
    print(f"scope table (ms per run of {name}): "
          + json.dumps({ph: row for ph, row in rows.items() if row}), flush=True)
    return result


def table(record: dict):
    """``{(phase, part): ms per run of the step program}`` for the traced
    window of ``record``, computed once and printed once as a line of JSON;
    None where there is no device plane or no program text to join with."""
    if "_scope_table" not in record:
        record["_scope_table"] = _table(record)
    return record["_scope_table"]


def phase_ms(record: dict, phase: str):
    t = table(record)
    return None if t is None else sum(ms for (p, _), ms in t.items() if p == phase)


def part_ms(record: dict, *parts: str):
    t = table(record)
    return None if t is None else sum(ms for (_, pt), ms in t.items() if pt in parts)


def span_stats(name: str, prefix: bool = False):
    """(count, seconds) of the program's host span ``name`` (or of every span
    that starts with it) in this process's registry; None where the program
    records no such span."""
    try:
        from jumbo_mae_tpu_tpu.obs.metrics import get_registry
    except ImportError:
        return None
    spans = get_registry().snapshot().get("span_seconds", {})
    hit = [v for k, v in spans.items() if k == name or (prefix and k.startswith(name))]
    if not hit:
        return None
    return sum(v["count"] for v in hit), sum(v["sum"] for v in hit)
