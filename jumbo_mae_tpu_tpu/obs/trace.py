"""The program's own names in a profiler trace: host spans, device scopes,
and the compiled step programs a trace is joined with.

- :func:`span` / :class:`span_timer` — ``with span("data_wait"):`` times a
  host-side stage into the registry's ``span_seconds{name=...}`` histogram
  (what an operator reads at ``/metrics``) and, for the same interval,
  enters a ``jax.profiler.TraceAnnotation``: while a profiler runs
  (``run.profile_dir``, the benchmark's ``--trace 1``) the span sits on a
  host line of the ``.xplane.pb`` on the same clock as the device's
  operations. With no profiler running the annotation is a no-op TraceMe.
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

import time
import weakref
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

from jumbo_mae_tpu_tpu.obs.metrics import get_registry

_SPAN_HELP = "host-side span durations by stage"

# Host spans placed by the library (cli/train.py's loop adds data_wait,
# train_step and checkpoint_save). PERF.md §3 says which metric reads each.
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
SCOPE_LM_HEAD = "lm_head"  # final norm, logits over the rows held, cross-entropy
# ... and in its linear-attention (Kimi delta attention) layers. A block of
# that kind has no mla_latent / rope / attn_core / attn_out. An MLA block's
# head-wise gate lies under attn_out, the expert groups' choice under router.
SCOPE_KDA_PROJ = "kda_proj"  # the six projections of x: q, k, v, decay gate, beta, output gate
SCOPE_KDA_CONV = "kda_conv"  # the causal depthwise convolutions of q, k, v and their SiLU
SCOPE_KDA_GATE = "kda_gate"  # L2 norms, log-decay, beta; under kda_out: output norm and head gate
SCOPE_KDA_CORE = "kda_core"  # (q, k, v, g, beta) -> o: the chunked gated delta rule
SCOPE_KDA_OUT = "kda_out"  # output norm and head-wise gate (also under kda_gate), W_o
# ... and in its grouped-query layers (full or sliding-window softmax attention
# over shared key/value heads). A block of that kind has rope, attn_out and one
# of the two cores: a full layer's is attn_core, as every causal core's.
SCOPE_GQA_PROJ = "gqa_proj"  # the q, k, v and head-gate projections of x
SCOPE_SWA_CORE = "swa_core"  # a sliding-window layer's causal kernels and what feeds them


def _span_hist(name: str, registry):
    reg = registry if registry is not None else get_registry()
    return reg.histogram("span_seconds", _SPAN_HELP, labels=("name",)).labels(name)


@contextmanager
def span(name: str, registry=None):
    """Time a host-side stage into ``span_seconds{name=...}`` and mark it in
    a running profiler's trace. The histogram handle is resolved per entry —
    for per-step hot loops, hoist with :func:`span_timer`."""
    hist = _span_hist(name, registry)
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name):
            yield
    finally:
        hist.observe(time.perf_counter() - t0)


class span_timer:  # noqa: N801 - context-manager factory, used like span()
    """Pre-resolved reusable span: same contract as :func:`span` but the
    histogram lookup happens once at construction — the shape for per-step
    loops (train step, data wait)."""

    __slots__ = ("name", "_hist", "_t0", "_mark", "last_s")

    def __init__(self, name: str, registry=None):
        self.name = name
        self._hist = _span_hist(name, registry)
        self._t0 = 0.0
        self._mark = None
        self.last_s = 0.0  # duration of the most recent exit (loop bookkeeping)

    def __enter__(self) -> "span_timer":
        self._mark = TraceAnnotation(self.name)
        self._mark.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dur = time.perf_counter() - self._t0
        self._mark.__exit__(*exc)
        self.last_s = dur
        self._hist.observe(dur)

    def observe(self, dur_s: float) -> None:
        """Record an externally measured duration under this span's name
        (histogram only: an interval that is over cannot be annotated)."""
        self._hist.observe(dur_s)


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
