"""The program's own names in a profiler trace and in its span log: host
spans, JAX's trace / lower / compile events, device scopes, and the compiled
step programs a trace is joined with.

- :func:`span` / :class:`span_timer` / :func:`spanned` — ``with
  span("data_wait"):`` times a host-side stage three ways. Into the
  registry's ``span_seconds{name=...}`` histogram (what an operator reads at
  ``/metrics``). Into a ``jax.profiler.TraceAnnotation`` for the same
  interval: while a profiler runs (``run.profile_dir``, the benchmark's
  ``--trace 1``) the span sits on a host line of the ``.xplane.pb`` on the
  same clock as the device's operations; with none running it is a no-op
  TraceMe. And into the **span log**: one record ``(id, parent, name, start,
  end, thread)``, where ``parent`` is the span that was open on the same
  thread (a per-thread stack; a span opened on a fresh thread has none).
- The log is always on (no switch) and bounded: the newest
  :data:`LOG_RECORDS` records, in memory, nothing written anywhere. JAX's own
  events enter it as children of the span open on their thread — one
  ``jax.monitoring`` listener of each kind for the whole program, registered
  when this module is imported: ``jit_trace:<fun>``, ``jit_lower:<fun>``,
  ``backend_compile:<fun>`` with the start and end JAX gives, ``cache_load``
  (a load from the persistent cache, inside its ``backend_compile``). Traces
  nest (a jit traced inside a jit's trace lies inside its interval), so every
  reader takes the **union** of intervals (:func:`union_seconds`), never the
  sum. Stamps are ``time.perf_counter()``; :func:`to_wall` / :func:`from_wall`
  / :func:`to_trace_ns` carry them to ``time.time()`` and to a trace's host
  lines; :func:`process_start` is the operating system's word on when the
  process began.
- :func:`spans` (a copy of the log), :func:`self_seconds` (a record's
  duration minus the union of its children), :func:`setup_report` (the tree
  of the records inside an interval, with self times) and
  :func:`format_setup_report` (the lines ``cli.train`` prints as ``[setup]``
  when the first losses are on the host: ``program_build:train_step 27.2 s =
  self 0.3 + jit_trace 11.. + jit_lower 5.. + backend_compile 10..
  (cache_load 9..)``: what the left side took, split by what its children
  were; ``cache_load`` in brackets because it is part of ``backend_compile``).
- The scope vocabulary — the ``jax.named_scope`` names the step program
  gives to what flax's module paths leave anonymous. A scope costs nothing
  at run time: it only prefixes the ``op_name`` metadata of the HLO
  instructions traced under it, which is what a trace's device events are
  joined with (``benchmarks/scope_reduce.py``).
- :func:`note_program` / :func:`programs` — the compiled step programs of
  this process by name, recorded where they are built, so that whoever
  reduces a trace can ask the executable for its HLO text. Nothing is
  serialised or parsed here. :func:`keeping_programs` holds the ones noted
  inside a block past their builder's life.
- :func:`trace` — capture a ``jax.profiler`` device trace into a directory.
"""

from __future__ import annotations

import functools
import itertools
import os
import threading
import time
import weakref
from collections import defaultdict, deque
from contextlib import contextmanager

import jax.monitoring
from jax.profiler import TraceAnnotation

from jumbo_mae_tpu_tpu.obs.metrics import get_registry

_SPAN_HELP = "host-side span durations by stage"

# Host spans placed by the library (cli/train.py's loop adds data_wait,
# train_step and checkpoint_save). PERF.md §3 says which metric reads each.
SPAN_COMPILE_CACHE_SETUP = "compile_cache_setup"  # enable_compile_cache: the cache's path set
SPAN_MESH_BUILD = "mesh_build"  # create_mesh: devices to a Mesh
SPAN_MODEL_BUILD = "model_build"  # cli.train.build_model: configs, the flax module, its FLOPs
SPAN_OPTIMIZER_BUILD = "optimizer_build"  # make_optimizer: schedule and transformation chain
SPAN_STATE_SHAPES = "state_shapes"  # create_sharded_state: eval_shape of init + sharding rules
SPAN_STATE_INIT = "state_init"  # create_sharded_state: trace + compile/load + run
SPAN_PROGRAM_BUILD = "program_build"  # AOT lower + compile/load; ":<program>" appended
SPAN_H2D = "h2d"  # one batch handed to the device by prefetch_to_device

# Device scopes (jax.named_scope) of the step program, outside the model ...
SCOPE_RNG = "rng"  # per-step key derivation (threefry fold-ins)
SCOPE_GRAD_ACCUM = "grad_accum"  # the micro-batch scan, its sums and scaling
SCOPE_GRAD_SCALE = "grad_scale"  # gradients x the fault harness's multiplier
SCOPE_GRAD_NORM = "grad_norm"  # optax.global_norm for the divergence guard
SCOPE_GUARD = "guard"  # the finite flag and the per-leaf selects that gate the update
SCOPE_OPTIMIZER = "optimizer"  # tx.update + apply_updates
SCOPE_METRICS = "metrics"  # reductions of the model's outputs to scalars
# ... and inside it, where flax's module path says nothing.
SCOPE_PREPROCESS = "preprocess"  # uint8 -> normalized compute dtype
SCOPE_MASK = "mask"  # random masking, its gathers, the unshuffle
SCOPE_PATCHIFY = "patchify"  # images -> target patches
SCOPE_LOSS = "loss"  # pixel normalisation + masked MSE
SCOPE_ATTN_CORE = "attn_core"  # scores, softmax, weighted sum (any implementation)
# ... and in the latent-attention sparse-expert language model (models/lm.py)
SCOPE_EMBED = "embed"  # token embedding lookup over the vocabulary rows held
SCOPE_MLA_LATENT = "mla_latent"  # the four latent q/kv projections and their norms
SCOPE_ROPE = "rope"  # rotary embedding of the rope columns of q and of the shared k
SCOPE_ATTN_OUT = "attn_out"  # the output projection over (heads, v width)
SCOPE_ROUTER = "router"  # float32 scores, top-k, weights, counts, the bias rule
SCOPE_MOE_DISPATCH = "moe_dispatch"  # sort/gather in, scatter/combine out
SCOPE_EXPERTS = "experts"  # grouped products over the experts held, and their SwiGLU
SCOPE_SHARED_EXPERT = "shared_expert"  # the expert every token passes
SCOPE_DENSE_MLP = "dense_mlp"  # the SwiGLU MLP of a leading dense layer
SCOPE_MTP_MERGE = "mtp_merge"  # multi-token prediction: norms, concatenation, W_eh
SCOPE_LM_HEAD = "lm_head"  # final norm; a tile of tokens at a time (ops/head_loss.py): logits over the rows held, loss, and both gradient products, all in the forward pass
# ... and in its linear-attention (Kimi delta attention) layers. A block of
# that kind has no mla_latent / rope / attn_core / attn_out. An MLA block's
# head-wise gate lies under attn_out, the expert groups' choice under router.
SCOPE_KDA_PROJ = "kda_proj"  # the projections of x: q, k, v, decay gate, beta, output gate (a gate's two factors where it goes through a rank)
SCOPE_KDA_CONV = "kda_conv"  # q, k, v through ops/kda.short_conv: causal depthwise filter, SiLU, q's and k's L2 norm
SCOPE_KDA_GATE = "kda_gate"  # log-decay, beta, the counters; under kda_out: output norm and output gate
SCOPE_KDA_CORE = "kda_core"  # (q, k, v, g, beta) -> o: the chunked gated delta rule
SCOPE_KDA_OUT = "kda_out"  # output norm and head-wise gate (also under kda_gate), W_o
# ... and in its grouped-query layers (full or sliding-window softmax attention
# over shared key/value heads). A block of that kind has rope (unless its kind
# has no rotary embedding: then no rope scope opens), attn_out and one of the
# two cores: a full layer's is attn_core, as every causal core's.
SCOPE_GQA_PROJ = "gqa_proj"  # the q, k, v and head-gate projections of x
SCOPE_SWA_CORE = "swa_core"  # a sliding-window layer's causal kernels and what feeds them
# ... and in its gated short-convolution layers (``models/lm.ShortConv``). A
# block of that kind has no attention scope at all: no projection to heads,
# no rope, no core, no attn_out.
SCOPE_SCONV_IN = "sconv_in"  # W_in: the block input to the three gates' 3 x dim columns
SCOPE_SCONV_MIX = "sconv_mix"  # B ⊙ x̃, the depth-wise causal filter's taps, C ⊙: elementwise
SCOPE_SCONV_OUT = "sconv_out"  # W_out
# ... and where it is trained as a block-diffusion model (``diffusion_block`` >
# 0): a grouped-query block's core is bd_core and never attn_core, which stays
# the causal models'; the noise is drawn once a step, outside the blocks.
SCOPE_BD_CORE = "bd_core"  # the causal kernels under the block-diffusion pattern over the clean and the noisy copy, and what feeds them
SCOPE_BD_NOISE = "bd_noise"  # a noise level a (sequence, block), the tokens masked, the noisy ids, the loss's 1/t weights, the masked share


def _span_hist(name: str, registry):
    reg = registry if registry is not None else get_registry()
    return reg.histogram("span_seconds", _SPAN_HELP, labels=("name",)).labels(name)


# ------------------------------------------------------------ the span log
#
# Stamps are ``time.perf_counter()`` seconds: the clock a caller's own ``t0``
# is on. JAX's events arrive on ``time.time()`` and a profiler's trace on the
# same wall clock in nanoseconds (an event's ``start_ns`` counts from the
# trace's ``profile_start_time``, Unix epoch), so one offset, taken when this
# module is imported, carries a stamp to both.

# newest records kept: a benchmark cell's whole run writes under a thousand
# (PERF.md section 5), a trainer four a step
LOG_RECORDS = 1 << 14
NESTED_RECORD_MIN_S = 1e-3  # a shorter event of JAX's inside another of JAX's is not kept

_t = (time.perf_counter(), time.time(), time.perf_counter())
WALL_MINUS_PERF = _t[1] - (_t[0] + _t[2]) / 2  # time.time() - time.perf_counter()
del _t

_log: deque = deque(maxlen=LOG_RECORDS)  # (id, parent, name, start, end, thread)
_ids = itertools.count(1)
# per thread: .stack, the ids of the spans open on it, innermost last;
# .jax_events, how many of JAX's events have begun on it and not ended
_open = threading.local()


def to_wall(t: float) -> float:
    """A stamp of the log as ``time.time()`` seconds."""
    return t + WALL_MINUS_PERF


def from_wall(t: float) -> float:
    """``time.time()`` seconds (JAX's events) as a stamp of the log."""
    return t - WALL_MINUS_PERF


def to_trace_ns(t: float, profile_start_ns: int) -> float:
    """A stamp of the log as an event's ``start_ns`` in a profiler trace whose
    ``Task Environment`` plane states ``profile_start_time = profile_start_ns``."""
    return to_wall(t) * 1e9 - profile_start_ns


@functools.cache
def process_start() -> float | None:
    """When the operating system started this process, as a stamp of the log
    (before this module was imported, so before every record); None where the
    system does not say (no ``/proc``)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])  # field 22, starttime
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.perf_counter() - age


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _append(name: str, start: float, end: float) -> None:
    """A record that opened no span of its own (one of JAX's events): a child
    of the span open on this thread."""
    stack = _stack()
    _log.append((next(_ids), stack[-1] if stack else None, name, start, end,
                 threading.get_ident()))


def span(name: str, registry=None) -> "span_timer":
    """``with span("data_wait"):`` times a host-side stage into
    ``span_seconds{name=...}``, marks it in a running profiler's trace and
    appends its record to the log. The histogram handle is resolved per call —
    for per-step hot loops, hoist with :class:`span_timer`."""
    return span_timer(name, registry)


def spanned(name: str):
    """Decorator: every call of the function is a :func:`span` ``name``
    (for set-up functions called once or twice a process)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span_timer(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


class span_timer:  # noqa: N801 - context-manager factory, used like span()
    """Pre-resolved reusable span: same contract as :func:`span` but the
    histogram lookup happens once at construction — the shape for per-step
    loops (train step, data wait). Not re-entrant: one interval at a time."""

    __slots__ = ("name", "_hist", "_t0", "_mark", "_id", "_parent", "_stack", "last_s")

    def __init__(self, name: str, registry=None):
        self.name = name
        self._hist = _span_hist(name, registry)
        self._t0 = 0.0
        self._mark = None
        self.last_s = 0.0  # duration of the most recent exit (loop bookkeeping)

    def __enter__(self) -> "span_timer":
        self._stack = stack = _stack()
        self._parent = stack[-1] if stack else None
        self._id = next(_ids)
        stack.append(self._id)
        self._mark = TraceAnnotation(self.name)
        self._mark.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self._mark.__exit__(*exc)
        self._stack.pop()
        _log.append((self._id, self._parent, self.name, self._t0, end, threading.get_ident()))
        self.last_s = end - self._t0
        self._hist.observe(self.last_s)


# ---------------------------------------------------- JAX's events in the log
#
# jax.monitoring tells a process-wide listener of every jit it traces, lowers
# and compiles (start and end on time.time(), the function's name) and of
# every load from the persistent cache (a duration, inside the compile event
# that asked for it). JAX has no per-listener state, so the program registers
# one listener of each kind (an event's start is a scalar, its end a time
# span, a cache load a duration), here, when this module is imported; whoever
# else wants the compile event (``obs.retrace``'s sentinels) is called from it.

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
RECORD_JIT_TRACE = "jit_trace"  # ":<fun_name>" appended: Python tracing to a jaxpr
RECORD_JIT_LOWER = "jit_lower"  # jaxpr to an MLIR module
RECORD_BACKEND_COMPILE = "backend_compile"  # XLA's compile, or the load from the cache
RECORD_CACHE_LOAD = "cache_load"  # reading and deserialising a cached executable
_JAX_RECORDS = {
    "/jax/core/compile/jaxpr_trace_duration": RECORD_JIT_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": RECORD_JIT_LOWER,
    COMPILE_EVENT: RECORD_BACKEND_COMPILE,
}

# called with a backend compile's seconds, on the thread that compiled
compile_watchers: "weakref.WeakSet" = weakref.WeakSet()


def _on_scalar(event: str, value, **_kw) -> None:
    if event in _JAX_RECORDS:  # JAX announces the start of what _on_time_span will end
        _open.jax_events = getattr(_open, "jax_events", 0) + 1


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    kind = _JAX_RECORDS.get(event)
    if kind is None:
        return
    _open.jax_events = inside = max(getattr(_open, "jax_events", 1) - 1, 0)
    # tracing a model runs tens of thousands of sub-millisecond traces of
    # jax.numpy functions inside the one that matters (19 000 for the L/16
    # step): such a record is inside a kept record's interval and is not kept
    if not inside or end - start >= NESTED_RECORD_MIN_S:
        fun = str(kw.get("fun_name", "?"))
        if fun.startswith("jit(") and fun.endswith(")"):
            fun = fun[4:-1]  # lowering and compile name the module, tracing the function
        _append(f"{kind}:{fun}", from_wall(start), from_wall(end))
    if event == COMPILE_EVENT:
        for watcher in list(compile_watchers):
            watcher._on_compile(end - start)


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == CACHE_LOAD_EVENT:
        end = time.perf_counter()
        _append(RECORD_CACHE_LOAD, end - duration, end)


jax.monitoring.register_scalar_listener(_on_scalar)
jax.monitoring.register_event_time_span_listener(_on_time_span)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


# ------------------------------------------------------------ reading the log


def spans() -> list[dict]:
    """A copy of the log, oldest first by end: ``{"id", "parent", "name",
    "start", "end", "thread"}``. ``parent`` is the ``id`` of the span that was
    open on the same thread when this one opened (None: none was), ``thread``
    a ``threading.get_ident()``."""
    keys = ("id", "parent", "name", "start", "end", "thread")
    return [dict(zip(keys, r)) for r in _log.copy()]


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals: records nest and
    overlap (a jit traced inside a jit's trace), so nothing here is a sum."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def _children(records) -> dict:
    kids = defaultdict(list)
    for r in records:
        if r["parent"] is not None:
            kids[r["parent"]].append(r)
    return kids


def self_seconds(records) -> dict:
    """``{id: seconds}``: each record's duration minus the union of its
    children among ``records``, cut to its own interval."""
    kids = _children(records)
    out = {}
    for r in records:
        s, e = r["start"], r["end"]
        out[r["id"]] = (e - s) - union_seconds(
            (max(c["start"], s), min(c["end"], e))
            for c in kids.get(r["id"], ()) if c["end"] > s and c["start"] < e)
    return out


def _nested(records) -> list[dict]:
    """Copies of ``records`` in which a record that lies inside another of
    the same parent and thread is that one's child: JAX's events open no span,
    so a jit traced inside a jit's trace, or a cache load inside its compile,
    is its sibling in the log and its child here."""
    out = [dict(r) for r in records]
    groups = defaultdict(list)
    for r in out:
        groups[r["parent"], r["thread"]].append(r)
    for group in groups.values():
        group.sort(key=lambda r: (r["start"], -r["end"]))
        around: list[dict] = []
        for r in group:
            while around and around[-1]["end"] < r["end"]:
                around.pop()
            if around:
                r["parent"] = around[-1]["id"]
            around.append(r)
    return out


def _by_kind(records) -> dict:
    kinds = defaultdict(list)
    for r in records:
        kinds[r["name"].split(":", 1)[0]].append((r["start"], r["end"]))
    return {k: union_seconds(v) for k, v in kinds.items()}


def _nodes(siblings, kids, own, main) -> list[dict]:
    """Siblings merged by name (and by whether on the main thread), in the
    order they first started."""
    groups: dict = {}
    for r in sorted(siblings, key=lambda r: r["start"]):
        groups.setdefault((r["name"], r["thread"] == main), []).append(r)
    out = []
    for (name, on_main), rs in groups.items():
        below = [c for r in rs for c in kids.get(r["id"], ())]
        node = {"name": name, "count": len(rs), "main": on_main,
                "seconds": union_seconds((r["start"], r["end"]) for r in rs),
                "self_s": sum(own[r["id"]] for r in rs)}
        if below:
            node["kinds"] = _by_kind(below)
            node["children"] = _nodes(below, kids, own, main)
        out.append(node)
    return out


def setup_report(start: float | None = None, end: float | None = None) -> dict:
    """The tree of the records that lie inside ``[start, end]`` (stamps of
    the log; by default from the process's start, or the oldest record where
    the system does not say, to now). JAX's events are nested by their
    intervals first, siblings of one name are merged, and each node holds
    ``count``, ``seconds`` (the union of its records), ``self_s`` (their
    durations minus the union of their children), ``kinds`` (its children's
    union by kind: ``jit_trace``, ``jit_lower``, ``backend_compile``,
    ``cache_load``, a span's name) and ``children``. ``spanned_s`` is the
    union of the main thread's records: what is left of ``seconds`` no record
    saw."""
    log = spans()
    if start is None:
        start = process_start() or min((r["start"] for r in log), default=0.0)
    if end is None:
        end = time.perf_counter()
    inside = _nested(r for r in log if r["start"] >= start and r["end"] <= end)
    ids = {r["id"] for r in inside}
    main = threading.main_thread().ident
    roots = [r for r in inside if r["parent"] not in ids]
    return {
        "start": start, "end": end, "seconds": end - start, "records": len(inside),
        "spanned_s": union_seconds((r["start"], r["end"]) for r in inside
                                   if r["thread"] == main),
        "roots": _nodes(roots, _children(inside), self_seconds(inside), main),
    }


def format_setup_report(report: dict, min_s: float = 0.05) -> list[str]:
    """``setup_report`` as lines, two levels deep, nodes under ``min_s`` left
    out: ``program_build:train_step 27.2 s = self 0.3 + jit_trace 11.0 + ...
    (cache_load 9.1)``: a node's seconds as its self time plus its children
    by kind; ``cache_load`` in brackets, because it lies inside a
    ``backend_compile``."""
    def line(node, indent):
        text = f"{indent}{node['name']}"
        if node["count"] > 1:
            text += f" x{node['count']}"
        text += f" {node['seconds']:.2f} s"
        kinds = node.get("kinds", {})
        if kinds:
            parts = [f"self {node['self_s']:.2f}"] + [
                f"{k} {v:.2f}" for k, v in sorted(kinds.items(), key=lambda kv: -kv[1])]
            text += " = " + " + ".join(parts)
        loaded = sum(c.get("kinds", {}).get(RECORD_CACHE_LOAD, 0.0)
                     for c in node.get("children", ()))
        if loaded:
            text += f" ({RECORD_CACHE_LOAD} {loaded:.2f})"
        return text if node["main"] else text + " [off the main thread]"

    lines = [f"{report['seconds']:.2f} s, of which {report['spanned_s']:.2f} s under the "
             f"main thread's records ({report['records']} records)"]
    for node in report["roots"]:
        if node["seconds"] < min_s:
            continue
        lines.append(line(node, "  "))
        lines += [line(c, "    ") for c in node.get("children", ()) if c["seconds"] >= min_s]
    return lines


# name -> jax.stages.Compiled, for as long as the step that built it lives
_PROGRAMS: "weakref.WeakValueDictionary[str, object]" = weakref.WeakValueDictionary()


# the dicts of the ``keeping_programs()`` blocks that are open
_KEPT: list[dict] = []


def note_program(name: str, compiled) -> None:
    """Record a compiled step program under ``name`` (the latest wins)."""
    _PROGRAMS[name] = compiled
    for kept in _KEPT:
        kept[name] = compiled


def programs() -> dict:
    """``{name: compiled}`` of the step programs built in this process and
    still alive; ``compiled.as_text()`` carries the ``op_name`` of every
    instruction a device trace shows."""
    return dict(_PROGRAMS)


@contextmanager
def keeping_programs():
    """Yields a ``{name: compiled}`` that holds every program noted inside
    the block: for a caller that reads a program's text after the loop that
    built it has returned (``programs()`` forgets a step with its builder)."""
    kept: dict = {}
    _KEPT.append(kept)
    try:
        yield kept
    finally:
        _KEPT.remove(kept)


@contextmanager
def trace(log_dir: str | None):
    """Capture an XLA device trace into ``log_dir`` (no-op when None). The
    program's spans entered meanwhile are in it, on its host lines."""
    if not log_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
