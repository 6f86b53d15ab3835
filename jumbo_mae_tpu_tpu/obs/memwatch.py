"""Memory observability: live device/host telemetry, byte accounting, leaks.

The repo already knows memory *statically*: `obs/perfmodel.py` predicts
``peak_hbm_bytes`` and `obs/costmodel.py` extracts XLA's compile-time
``memory_analysis()``. Nothing measured it live, so a leaking cache or an
under-predicted activation footprint stayed invisible until the OOM. This
module closes the loop from prediction to measurement, the same
predict-vs-measured discipline `perf_predict_vs_measured` applies to step
time:

- :class:`MemoryWatcher` samples per-device memory via
  ``device.memory_stats()`` (``bytes_in_use`` / ``peak_bytes_in_use``)
  plus host RSS and the Python allocator's live block count, publishing
  ``mem_device_bytes{device=}``, ``mem_device_peak_bytes{device=}``,
  ``mem_host_rss_bytes`` and ``mem_py_alloc_blocks``. Backends without
  memory stats (XLA:CPU) degrade gracefully: the device/drift gauges are
  *never registered* (absent from the scrape, not zero) and the first
  degraded sample carries a one-shot ``note`` the caller can journal.
- The watcher cross-checks the capacity model: feed it the predicted peak
  for each active executable (``record_predicted_peak``, from
  ``ProgramCost.peak_bytes`` / ``PerfPrediction.peak_hbm_bytes``) and
  every sample publishes ``mem_hbm_predict_vs_measured{program=}`` =
  measured device peak / predicted peak. A ratio drifting above 1 means
  the model under-predicts (OOM risk); far below 1 means capacity planning
  is leaving batch size on the table.
- :class:`MemAccountant` is one registry for byte-level accounting of
  every in-process cache and buffer (engine executable cache, encoder
  LRU, warmcache disk dir, MicroBatcher queue, journal/flightrec rings)
  publishing ``mem_component_bytes{component=}`` — so "RSS grew 2 GiB"
  decomposes into *which* cache grew.
- :class:`LeakSentinel` fits a robust (Theil–Sen) slope over a rolling
  window of RSS + per-component samples; sustained growth names the
  fastest-growing component, and the caller journals ``mem_leak_suspect``,
  dumps the flight recorder, and latches ``/healthz`` degraded. Chaos
  coverage comes from the ``host.leak`` fault site (`faults/inject.py`).
- `tools/mem_doctor.py` turns the journaled ``mem_sample`` rows into the
  offline diagnosis (peak timeline, component attribution, leak verdict,
  OOM-risk vs the ChipSpec HBM capacity).

Sampling is log-boundary / scrape-rate work, never per-step: one
``/proc/self/status`` read, one ``memory_stats()`` call per device, and
one cheap probe per registered component (PERF_ARCHIVE.md §Memwatch overhead).
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque
from typing import Callable

from jumbo_mae_tpu_tpu.obs.metrics import get_registry

MB = 1024 * 1024


# --------------------------------------------------------------- host probes


def host_rss_bytes() -> int | None:
    """Current resident set size from ``/proc/self/status`` (Linux).

    Falls back to ``ru_maxrss`` (the *peak* RSS — still monotone under a
    leak, so the sentinel keeps working) where /proc is missing; ``None``
    when neither source exists.
    """
    try:
        with open("/proc/self/status", "rb") as f:
            for line in f:
                if line.startswith(b"VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return None


def host_available_bytes() -> int | None:
    """``MemAvailable`` from ``/proc/meminfo`` — the kernel's estimate of
    how much can be allocated without swapping; ``None`` off-Linux."""
    try:
        with open("/proc/meminfo", "rb") as f:
            for line in f:
                if line.startswith(b"MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return None


def tree_nbytes(tree) -> int:
    """Total array bytes of a pytree (params/opt-state size on host).

    Counts anything with ``.nbytes`` (numpy and jax arrays alike); other
    leaves (scalars, None) count zero.
    """
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


def _device_memory_stats() -> list[tuple[str, int | None, int | None]] | None:
    """``[(label, bytes_in_use, peak_bytes_in_use)]`` per local device.

    ``None`` when the backend has no usable memory stats (XLA:CPU raises
    or returns an empty/useless dict) — the caller must degrade to
    host-only telemetry, not publish zeros.
    """
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return None
    out: list[tuple[str, int | None, int | None]] = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            return None
        if not stats or "bytes_in_use" not in stats:
            return None
        out.append(
            (
                f"{d.platform}:{d.id}",
                stats.get("bytes_in_use"),
                stats.get("peak_bytes_in_use"),
            )
        )
    return out or None


def _theil_sen_slope(values) -> float:
    """Median pairwise slope per *sample index* — robust to one-off jumps
    (an eval allocating a temp buffer) that would swing a least-squares
    fit; O(n²) pairs on a ≤ window-sized input."""
    n = len(values)
    if n < 2:
        return 0.0
    slopes = [
        (values[j] - values[i]) / (j - i)
        for i in range(n)
        for j in range(i + 1, n)
    ]
    slopes.sort()
    m = len(slopes)
    mid = m // 2
    if m % 2:
        return float(slopes[mid])
    return float(slopes[mid - 1] + slopes[mid]) / 2.0


# ----------------------------------------------------------- MemAccountant


class MemAccountant:
    """One registry for byte accounting of every in-process cache/buffer.

    Components register a zero-arg probe returning their current byte
    footprint (or ``None`` while unknowable); :meth:`sample` polls every
    probe and publishes ``mem_component_bytes{component=}``. Probes must
    be cheap (a counter read, a ``stat()``) — they run per log window and
    per scrape. A probe that raises is skipped for that sample, never
    fatal: accounting must not take down the thing it accounts.
    """

    def __init__(self, registry=None):
        reg = registry if registry is not None else get_registry()
        self._g = reg.gauge(
            "mem_component_bytes",
            "live byte accounting per in-process cache/buffer",
            labels=("component",),
        )
        self._probes: dict[str, Callable[[], float | None]] = {}
        self._lock = threading.Lock()

    def register(self, component: str, probe: Callable[[], float | None]):
        with self._lock:
            self._probes[component] = probe

    def unregister(self, component: str):
        with self._lock:
            self._probes.pop(component, None)

    def components(self) -> list[str]:
        with self._lock:
            return sorted(self._probes)

    def sample(self) -> dict[str, int]:
        with self._lock:
            probes = list(self._probes.items())
        out: dict[str, int] = {}
        for name, probe in probes:
            try:
                v = probe()
            except Exception:
                continue
            if v is None:
                continue
            out[name] = int(v)
            self._g.labels(component=name).set(float(v))
        return out


# ---------------------------------------------------------- MemoryWatcher


class MemoryWatcher:
    """Samples device + host memory and validates the HBM prediction.

    Host gauges (``mem_host_rss_bytes``, ``mem_py_alloc_blocks``) register
    eagerly — they exist on every backend. Device gauges
    (``mem_device_bytes``, ``mem_device_peak_bytes``) and the drift gauge
    (``mem_hbm_predict_vs_measured``) register lazily on the first
    *successful* ``memory_stats()`` read, so a CPU scrape simply doesn't
    carry them. The first degraded sample sets a one-shot ``note`` field
    in the snapshot — the caller journals it once, then the watcher stays
    quiet about it.
    """

    def __init__(self, *, accountant: MemAccountant | None = None,
                 registry=None, chip=None):
        reg = registry if registry is not None else get_registry()
        self._reg = reg
        self.accountant = accountant
        # chip: obs.perfmodel.ChipSpec | None — carries the HBM capacity
        # the doctor's OOM-risk estimate divides by (0 on generic CPU)
        self.chip = chip
        self._g_rss = reg.gauge(
            "mem_host_rss_bytes", "host resident set size of this process"
        )
        self._g_blocks = reg.gauge(
            "mem_py_alloc_blocks",
            "live Python allocator blocks (sys.getallocatedblocks) — a "
            "unit-free heap-growth signal",
        )
        self._g_dev = None
        self._g_dev_peak = None
        self._g_drift = None
        self._predicted: dict[str, float] = {}
        self._lock = threading.Lock()
        self._device_degraded = False
        self._degrade_noted = False
        self._last: dict = {}

    # -- prediction side of the drift gauge ------------------------------

    def record_predicted_peak(self, program: str, peak_bytes) -> None:
        """Attach the capacity-model peak for ``program`` (train step, an
        engine ``task/bucket`` executable); every subsequent sample
        publishes measured/predicted for it. Zero/None predictions are
        ignored — no division theater."""
        try:
            v = float(peak_bytes or 0)
        except (TypeError, ValueError):
            return
        if v > 0:
            with self._lock:
                self._predicted[program] = v

    def predicted_peaks(self) -> dict[str, float]:
        with self._lock:
            return dict(self._predicted)

    # -- sampling ---------------------------------------------------------

    def sample(self) -> dict:
        """One telemetry sample; publishes gauges, returns the snapshot
        dict the caller can journal as a ``mem_sample`` event. Usable
        directly as a ``TelemetryServer.add_pre_scrape`` hook."""
        snap: dict = {"ts": time.time()}
        rss = host_rss_bytes()
        if rss is not None:
            self._g_rss.set(float(rss))
            snap["rss_bytes"] = int(rss)
        blocks = sys.getallocatedblocks()
        self._g_blocks.set(float(blocks))
        snap["py_alloc_blocks"] = int(blocks)

        dev = _device_memory_stats()
        if dev is None:
            self._device_degraded = True
            if not self._degrade_noted:
                self._degrade_noted = True
                snap["note"] = (
                    "device memory_stats() unavailable on this backend — "
                    "HBM gauges degraded to host-only telemetry"
                )
        else:
            self._device_degraded = False
            if self._g_dev is None:
                self._g_dev = self._reg.gauge(
                    "mem_device_bytes",
                    "live device (HBM) bytes in use",
                    labels=("device",),
                )
                self._g_dev_peak = self._reg.gauge(
                    "mem_device_peak_bytes",
                    "high-water device (HBM) bytes since process start",
                    labels=("device",),
                )
            peak_max = 0
            in_use_total = 0
            for label, in_use, peak in dev:
                if in_use is not None:
                    self._g_dev.labels(device=label).set(float(in_use))
                    in_use_total += int(in_use)
                if peak is not None:
                    self._g_dev_peak.labels(device=label).set(float(peak))
                    peak_max = max(peak_max, int(peak))
            snap["device_bytes"] = int(in_use_total)
            snap["device_peak_bytes"] = int(peak_max)
            drift = self._publish_drift(peak_max)
            if drift:
                snap["hbm_drift"] = drift
        if self.chip is not None and getattr(self.chip, "hbm_bytes", 0):
            snap["hbm_capacity_bytes"] = int(self.chip.hbm_bytes)
        if self.accountant is not None:
            comps = self.accountant.sample()
            if comps:
                snap["components"] = comps
        self._last = snap
        return snap

    def _publish_drift(self, measured_peak: int) -> dict[str, float]:
        if measured_peak <= 0:
            return {}
        with self._lock:
            predicted = dict(self._predicted)
        if not predicted:
            return {}
        if self._g_drift is None:
            self._g_drift = self._reg.gauge(
                "mem_hbm_predict_vs_measured",
                "measured device peak bytes / capacity-model predicted "
                "peak, per active executable (>1 = model under-predicts)",
                labels=("program",),
            )
        out: dict[str, float] = {}
        for program, pred in predicted.items():
            ratio = round(measured_peak / pred, 4)
            self._g_drift.labels(program=program).set(ratio)
            out[program] = ratio
        return out

    # -- readouts ---------------------------------------------------------

    @property
    def device_stats_degraded(self) -> bool:
        return self._device_degraded

    def last_sample(self) -> dict:
        """Most recent snapshot — shaped for ``HealthState.probe()``."""
        return self._last

    def headroom_check(
        self, need_bytes: int, *, margin_frac: float = 0.10
    ) -> str | None:
        """``None`` when ``need_bytes`` fits inside the host's available
        memory with ``margin_frac`` slack; otherwise the refusal reason.
        Unknowable headroom (no /proc/meminfo) is *not* a refusal — the
        check exists to stop a predictable OOM, not to block platforms
        it can't read."""
        avail = host_available_bytes()
        if avail is None:
            return None
        budget = int(avail * (1.0 - margin_frac))
        if int(need_bytes) > budget:
            return (
                f"needs {int(need_bytes) // MB} MiB but only "
                f"{budget // MB} MiB of host memory is safely available "
                f"(MemAvailable {avail // MB} MiB, {margin_frac:.0%} margin)"
            )
        return None


# ----------------------------------------------------------- LeakSentinel


class LeakSentinel:
    """Names the fastest-growing component under sustained RSS growth.

    Feed it every :meth:`MemoryWatcher.sample` snapshot. Over a rolling
    window it fits a Theil–Sen slope to RSS *per sample*; when the robust
    growth across the window exceeds ``min_growth_mb`` it fires **once**
    (latched — `/healthz` stays degraded for the rest of the run, exactly
    like an SLO breach) and returns the suspect dict for the caller to
    journal as ``mem_leak_suspect`` and hand to the flight recorder. The
    suspect is the registered component with the largest robust slope; if
    no component explains the growth the verdict is ``unaccounted`` —
    pointing at native/JAX allocations outside the accountant's reach.

    The robust fit is the stable-workload guard: a one-sample spike (an
    eval window, a compile) moves the median pairwise slope very little,
    while a real leak grows every sample and moves it fully.
    """

    def __init__(self, *, window: int = 12, min_samples: int = 4,
                 min_growth_mb: float = 32.0, registry=None):
        if window < 2:
            raise ValueError(f"window must be >= 2, got {window}")
        self.window = int(window)
        self.min_samples = max(2, int(min_samples))
        self.min_growth_bytes = float(min_growth_mb) * MB
        self._reg = registry if registry is not None else get_registry()
        self._g_suspect = None
        self._samples: deque = deque(maxlen=self.window)
        self._fired: dict | None = None

    def degraded(self) -> bool:
        """Latched verdict — compose into ``HealthState.degraded_when``."""
        return self._fired is not None

    @property
    def suspect(self) -> dict | None:
        return self._fired

    def observe(self, snap: dict) -> dict | None:
        """Account one snapshot; returns the suspect dict on the single
        firing transition, ``None`` otherwise (including while latched)."""
        rss = snap.get("rss_bytes")
        if rss is None:
            return None
        self._samples.append(
            (float(snap.get("ts", 0.0)), int(rss),
             dict(snap.get("components") or {}))
        )
        if self._fired is not None or len(self._samples) < self.min_samples:
            return None
        rss_series = [s[1] for s in self._samples]
        slope = _theil_sen_slope(rss_series)
        n = len(rss_series)
        robust_growth = slope * (n - 1)
        if robust_growth < self.min_growth_bytes:
            return None
        suspect, comp_slope = "unaccounted", 0.0
        names = set()
        for _, _, comps in self._samples:
            names.update(comps)
        for name in sorted(names):
            series = [s[2].get(name, 0) for s in self._samples]
            s = _theil_sen_slope(series)
            if s > comp_slope:
                suspect, comp_slope = name, s
        # a component only takes the blame when its growth is a real share
        # of the RSS growth — a mildly warming cache must not eat the
        # verdict for a native leak it didn't cause
        if suspect != "unaccounted" and comp_slope < 0.2 * slope:
            suspect, comp_slope = "unaccounted", 0.0
        span_s = self._samples[-1][0] - self._samples[0][0]
        self._fired = {
            "component": suspect,
            "rss_growth_bytes": int(rss_series[-1] - rss_series[0]),
            "robust_growth_bytes": int(robust_growth),
            "slope_bytes_per_sample": int(slope),
            "component_slope_bytes_per_sample": int(comp_slope),
            "window": n,
            "window_span_s": round(max(span_s, 0.0), 3),
        }
        if self._g_suspect is None:
            self._g_suspect = self._reg.gauge(
                "mem_leak_suspect",
                "1 once the leak sentinel latched, naming the "
                "fastest-growing component",
                labels=("component",),
            )
        self._g_suspect.labels(component=suspect).set(1.0)
        return dict(self._fired)
