"""Retrace sentinel: zero-recompiles-after-warmup, enforced at runtime.

A steady-state training or serving loop must not compile. Every XLA
compile after warmup is either a bucket-config bug, a shape leak (a batch
that missed padding), or a weak-type/dtype drift — all of which silently
multiply step latency by 100-1000× when they land, and none of which the
test suite sees because tests run two steps and stop.

This sentinel hooks JAX's own compile telemetry
(``jax.monitoring`` event ``/jax/core/compile/backend_compile_duration``,
which fires on *every* backend compile, first trace and retrace alike —
and never in a compile-free steady state). Protocol:

* ``note(tag, tree)`` — record the abstract signature (leaf shapes +
  dtypes) of what is about to be dispatched; cheap, no device access.
* ``arm()`` — warmup is over: from here every compile is a violation
  unless inside an ``expected()`` block (checkpoint restore, a fault
  injection building its alternate executable, a one-off eval).
* on a violation the sentinel journals a ``retrace`` event carrying the
  most recent signature change it saw (tag, previous and new signature,
  the per-leaf diff) — the attribution that turns "something recompiled"
  into "batch 7 arrived as (96, 224, 224, 3) where warmup saw 128".

Metrics: ``retrace_compiles_total`` (every compile seen while active),
``retrace_events_total`` (violations), ``retrace_armed`` gauge.

JAX has no per-listener unregister, so the program has one listener for the
compile event: ``obs.trace``'s, registered when that module is imported, which
also writes the event into the span log. It calls the live sentinels through
a WeakSet (``obs.trace.compile_watchers``) — creating/dropping sentinels
(tests do this a lot) never accumulates listeners.
"""

from __future__ import annotations

import threading
import warnings
from contextlib import contextmanager

from jumbo_mae_tpu_tpu.obs.trace import COMPILE_EVENT, compile_watchers

__all__ = ["RetraceSentinel", "COMPILE_EVENT"]


def _signature(tree) -> tuple:
    """Abstract signature of a pytree: ((shape, dtype), ...) per leaf."""
    import jax

    sig = []
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        sig.append((shape, dtype))
    return tuple(sig)


def _sig_diff(prev: tuple, new: tuple) -> list[dict]:
    """Per-leaf differences between two signatures."""
    diff = []
    for i in range(max(len(prev), len(new))):
        p = prev[i] if i < len(prev) else None
        n = new[i] if i < len(new) else None
        if p != n:
            diff.append(
                {
                    "leaf": i,
                    "prev_shape": list(p[0]) if p else None,
                    "prev_dtype": p[1] if p else None,
                    "new_shape": list(n[0]) if n else None,
                    "new_dtype": n[1] if n else None,
                }
            )
    return diff


class RetraceSentinel:
    """One armed watcher over a loop's dispatch signatures."""

    def __init__(self, name: str = "train", *, journal=None, registry=None):
        from jumbo_mae_tpu_tpu.obs.metrics import get_registry

        self.name = name
        self._journal = journal
        self._lock = threading.Lock()
        self._armed = False
        self._expected_depth = 0
        self._sigs: dict[str, tuple] = {}
        self._last_change: dict | None = None
        self.compiles = 0          # every backend compile seen while live
        self.expected_compiles = 0
        self.violations: list[dict] = []
        reg = registry if registry is not None else get_registry()
        self._m_compiles = reg.counter(
            "retrace_compiles_total",
            "backend compiles observed by the retrace sentinel",
            labels=("loop",),
        )
        self._m_events = reg.counter(
            "retrace_events_total",
            "unexpected recompiles after warmup (each journals a "
            "`retrace` event)",
            labels=("loop",),
        )
        self._m_armed = reg.gauge(
            "retrace_armed",
            "1 once warmup ended and the zero-recompile contract is live",
            labels=("loop",),
        )
        self._m_armed.labels(loop=name).set(0)
        compile_watchers.add(self)

    # -- protocol --------------------------------------------------------

    def note(self, tag: str, tree) -> None:
        """Record the signature about to be dispatched under ``tag``."""
        sig = _signature(tree)
        with self._lock:
            prev = self._sigs.get(tag)
            if prev is not None and prev != sig:
                self._last_change = {
                    "tag": tag,
                    "prev": prev,
                    "new": sig,
                    "diff": _sig_diff(prev, sig),
                }
            self._sigs[tag] = sig

    def arm(self) -> None:
        with self._lock:
            self._armed = True
            self._last_change = None
        self._m_armed.labels(loop=self.name).set(1)

    def disarm(self) -> None:
        with self._lock:
            self._armed = False
        self._m_armed.labels(loop=self.name).set(0)

    @contextmanager
    def expected(self, reason: str = ""):
        """Compiles inside this block are legitimate (fault-injection
        alternate executables, one-off evals, checkpoint paths)."""
        with self._lock:
            self._expected_depth += 1
        try:
            yield
        finally:
            with self._lock:
                self._expected_depth -= 1

    # -- listener side ---------------------------------------------------

    def _on_compile(self, duration: float) -> None:
        with self._lock:
            self.compiles += 1
            armed = self._armed and self._expected_depth == 0
            change = self._last_change
            self._last_change = None
            if armed:
                record = {
                    "loop": self.name,
                    "compile_seconds": round(float(duration), 4),
                    "tag": change["tag"] if change else None,
                    "prev_sig": (
                        [list(s) for s in change["prev"]] if change else None
                    ),
                    "new_sig": (
                        [list(s) for s in change["new"]] if change else None
                    ),
                    "diff": change["diff"] if change else None,
                }
                self.violations.append(record)
            elif not self._armed or self._expected_depth:
                self.expected_compiles += 1
        self._m_compiles.labels(loop=self.name).inc()
        if not armed:
            return
        self._m_events.labels(loop=self.name).inc()
        attribution = (
            f"last signature change: `{record['tag']}` {record['diff']}"
            if change
            else "no noted signature changed — host-side jit or weak-type "
            "promotion; check scalar dtypes"
        )
        warnings.warn(
            f"retrace sentinel[{self.name}]: unexpected XLA compile after "
            f"warmup ({record['compile_seconds']}s). {attribution}",
            RuntimeWarning,
            stacklevel=2,
        )
        journal = self._journal
        if journal is not None:
            try:
                journal.event("retrace", **record)
            except Exception:  # noqa: BLE001 — observability must not kill the loop
                pass

    # -- readout ---------------------------------------------------------

    def summary(self) -> dict:
        with self._lock:
            return {
                "loop": self.name,
                "compiles": self.compiles,
                "expected": self.expected_compiles,
                "violations": len(self.violations),
            }

    def assert_steady(self) -> None:
        """Raise if any unexpected recompile happened after ``arm()``."""
        if self.violations:
            first = self.violations[0]
            raise AssertionError(
                f"retrace sentinel[{self.name}]: "
                f"{len(self.violations)} unexpected recompile(s) after "
                f"warmup; first: tag={first['tag']} diff={first['diff']}"
            )

    def close(self) -> None:
        self.disarm()
        compile_watchers.discard(self)
