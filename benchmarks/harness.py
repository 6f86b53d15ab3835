"""One run of one cell: set-up, warm-up, the measured window, an optional
traced window, the comparison with the reference, and the result line.

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json``, its configuration in ``benchmarks/configs/<config>.json``,
its traffic in ``benchmarks/traffic/<traffic>.json``, the driver named there in
``benchmarks/drivers/<driver>.py`` and each metric's reader in
``benchmarks/metrics/<name up to the first dot>.py``. Adding a cell, a
configuration, a driver or a metric adds files and entries and edits none.

A driver module has ``build(cell, *, devices, seed)`` and may name its host
spans in ``SPANS``. What ``build`` returns has ``warm()`` (every shape of the
window, counted as set-up), ``window(seconds, seed)`` (the record the metric
readers take their numbers from, with ``window_s``, ``attempted``, ``failed``
and ``samples``), ``program_bytes()`` (the compiler's footprint of the timed
program), ``check()`` (``[(name, value, limit), ...]``) and ``close()``. A
reader is ``read(record)``: the number, or None where it finds nothing to
read; in a traced run ``record["trace"]`` is ``trace_reduce``'s reduction.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

class BenchmarkError(RuntimeError):
    """The run cannot produce a result (wrong machine, a compile inside the
    window, a missing file): non-zero exit and no result line."""


def _load(path: Path, what: str) -> dict:
    if not path.is_file():
        raise BenchmarkError(f"{what}: no file {path}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str, root: Path = ROOT):
    """``benchmarks/<kind>/<name>.py`` as a module, found by name."""
    path = root / "benchmarks" / kind / f"{name}.py"
    if not path.is_file():
        raise BenchmarkError(f"no {kind[:-1]} file {path.relative_to(root)}")
    spec = importlib.util.spec_from_file_location(f"benchmarks.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic and metric lists."""
    bench = _load(root / "BENCHMARK.json", "benchmark")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchmarkError(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load(root / configs[cell["config"]]["file"], "configuration")
    traffic = _load(root / "benchmarks" / "traffic" / f"{cell['traffic']}.json", "traffic mix")

    def mine(metric):
        return name in metric.get("workloads", [name])

    end_to_end = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in end_to_end}
    return {
        "name": name,
        "root": root,
        "chips": int(cell["chips"]),
        "config": config,
        "traffic": traffic,
        "end_to_end": end_to_end,
        # a per-layer metric belongs to the cells that report what it moves
        "per_layer": [m for m in bench["per_layer"]
                      if mine(m) and m["moves"] in reported],
    }


class CompileWatch:
    """Backend compiles of this process, from ``jax.monitoring``: seconds
    and count, and persistent-cache misses."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.seconds += float(duration)

    def _event(self, event, **_):
        if event == CACHE_MISS_EVENT:
            self.misses += 1


def span(name: str):
    """A host span in the profiler's own trace (a no-op when none runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def open_devices(chips: int, *, require_tpu: bool = True, compile_cache: bool = True):
    """Point the compile cache at its fixed place inside the checkout (the
    program's own ``enable_compile_cache``) and return the first ``chips``
    devices, or refuse: no accelerator, or fewer chips than the cell needs."""
    import jax

    if compile_cache:
        from jumbo_mae_tpu_tpu.utils.procenv import enable_compile_cache

        enable_compile_cache()
        # small programs are worth caching too: every run is a new process
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # and no program may push another out: a machine that caps the cache
        # (the chip tool's exports JAX_COMPILATION_CACHE_MAX_SIZE = 192 MiB,
        # one L/16 step program is 152 MB) would make every run compile
        jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchmarkError(
            f"needs a TPU, found platform {devices[0].platform!r}"
        )
    if len(devices) < chips:
        raise BenchmarkError(f"cell needs {chips} chip(s), found {len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def _metric_values(entries, record, root: Path) -> dict:
    out = {}
    for m in entries:
        reader = load_module("metrics", m["name"].split(".", 1)[0], root)
        value = reader.read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _traced(driver, seconds: float, seed: int, out_dir: Path, span_names) -> dict:
    """A further short window under the profiler, reduced to busy time, the
    window and the breakdown: ``trace_reduce.reduce_planes``. The measured
    window is never traced."""
    import jax

    from benchmarks import trace_reduce

    out_dir.mkdir(parents=True, exist_ok=True)
    for old in glob.glob(str(out_dir / "plugins" / "profile" / "*" / "*")):
        os.remove(old)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # tracing every Python call slows the host path
    options.enable_hlo_proto = False
    options.host_tracer_level = 1  # the harness spans, not every runtime call
    jax.profiler.start_trace(str(out_dir), profiler_options=options)
    try:
        driver.window(seconds, seed + 1)
    finally:
        jax.profiler.stop_trace()
    files = sorted(glob.glob(str(out_dir / "plugins" / "profile" / "*" / "*.xplane.pb")))
    if not files:
        raise BenchmarkError("the profiler wrote no trace")
    return trace_reduce.reduce_file(files[-1], span_names)


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool, t0: float,
             require_tpu: bool = True, compile_cache: bool = True,
             scratch: Path | None = None) -> dict:
    """Run one cell and return the result object of the contract."""
    devices = open_devices(cell["chips"], require_tpu=require_tpu,
                           compile_cache=compile_cache)
    watch = CompileWatch()
    driver_mod = load_module("drivers", cell["traffic"]["driver"], cell["root"])
    clock = lambda what: print(f"[{time.perf_counter() - t0:8.2f} s] {what}", flush=True)
    clock("devices found")
    driver = driver_mod.build(cell, devices=devices, seed=seed)
    clock("driver built")
    try:
        driver.warm()
        compiles_before, compile_s = watch.compiles, watch.seconds
        setup_s = time.perf_counter() - t0
        clock("warm: window starts")
        record = driver.window(seconds, seed)
        if watch.compiles != compiles_before:
            raise BenchmarkError(
                f"{watch.compiles - compiles_before} compile(s) inside the window"
            )
        clock("window closed")
        record |= {"setup_s": setup_s, "compile_s": compile_s, "chips": len(devices),
                   "device_kind": devices[0].device_kind}
        # memory_peak_bytes is the runtime's own counter and nothing else;
        # what the compiler says the timed program holds goes beside it
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices), "memory_peak_bytes": memory_peak(devices),
                  "program_bytes": int(driver.program_bytes())}
        result = {}
        if trace:
            scratch = scratch or ROOT / ".bench_scratch"
            reduced = _traced(driver, cell["traffic"]["trace_seconds"], seed,
                              scratch / "trace" / cell["name"],
                              getattr(driver_mod, "SPANS", ()))
            if watch.compiles != compiles_before:
                raise BenchmarkError("compile(s) inside the traced window")
            record["trace"] = reduced
            device |= {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
            result["breakdown"] = {k: reduced[k] for k in ("device_ops", "idle_gaps")}
        clock("comparing with the reference")
        checks = driver.check()
        clock("compared")
    finally:
        driver.close()
    for name, value, limit in checks:
        print(f"check {name}: {value:.6g} (limit {limit:.6g}) "
              f"{'ok' if value <= limit else 'FAILED'}", flush=True)
    print(f"samples: {record.get('samples', 0)}; compiles in set-up: "
          f"{compiles_before} ({compile_s:.1f} s, {watch.misses} cache misses)", flush=True)
    entries = cell["per_layer"] if trace else cell["end_to_end"]
    return {
        "correct": all(value <= limit for _, value, limit in checks),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": _metric_values(entries, record, cell["root"]),
        "device": device,
        **result,
    }


def main(argv, *, t0: float, require_tpu: bool = True, compile_cache: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "jumbo_mae_tpu_tpu").is_dir():
            raise BenchmarkError("no program beside the benchmark: nothing to measure")
        cell = load_cell(args.workload)
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t0=t0, require_tpu=require_tpu,
                          compile_cache=compile_cache)
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0
