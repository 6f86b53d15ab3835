"""Observability subsystem: one registry across serve / train / data.

- ``obs.metrics``  — thread-safe counters/gauges/histograms with labels,
  Prometheus text rendering, process default registry (+ null registry for
  telemetry-off A/B runs); hosts ``AverageMeter``.
- ``obs.exporter`` — stdlib HTTP server for ``/metrics`` and ``/healthz``.
- ``obs.trace``    — host-side spans (registry histogram + profiler
  annotation + the always-on span log, with JAX's trace / lower / compile
  events as child records), the step program's scope vocabulary, the record
  of compiled step programs, and the XLA device-trace capture helper.
- ``obs.mfu``      — analytic FLOPs + MFU reporting (fed into the registry
  by the train loop), with one device_kind normalizer for the peak-TFLOPS
  tables.
- ``obs.costmodel`` — XLA ``cost_analysis``/``memory_analysis`` extraction
  for every compiled program (``xla_*`` gauges, journal events, MFU vs HFU
  split).
- ``obs.perfmodel`` — analytic roofline capacity model (predicted step time
  / throughput / peak HBM; FSDP/DP comm terms) + the live
  predict-vs-measured drift gauge.
- ``obs.perfledger`` — schema-versioned BENCH_HISTORY.jsonl writer/reader
  ``tools/bench_infer.py`` and ``tools/loadgen.py`` append to and
  ``tools/perf_doctor.py`` diagnoses.
- ``obs.modelstats`` — per-layer-group grad/param/update statistics computed
  inside the jitted train step (``run.diag_every``).
- ``obs.journal``  — append-only crash-safe JSONL run journal (per-host
  segments under multi-process runs) + single and merged multi-host readers.
- ``obs.flightrec`` — crash flight recorder (ring buffer + black-box dumps,
  host-tagged filenames on non-zero hosts).
- ``obs.fleet``    — file-based fleet-health protocol: per-host beacons +
  the host-0 aggregator (straggler/lost detection, ``fleet_*`` gauges).
- ``obs.reqtrace`` — per-request trace context for the serving path + the
  crash-safe JSONL access log (``tools/serve_doctor.py`` reads it offline).
- ``obs.memwatch`` — live memory observability: device/host sampling with
  the HBM predict-vs-measured drift gauge, per-component byte accounting,
  and the robust-slope leak sentinel (``tools/mem_doctor.py`` reads the
  journaled samples offline).
- ``obs.lockwatch`` — opt-in instrumented locks (``GRAFT_LOCKWATCH=1``):
  runtime lock-order inversion + long-hold detection, ``lock_*`` metrics,
  ``lock_order_violation`` journal events.
- ``obs.goodput``  — goodput accounting: wall-clock attribution ledger
  (``goodput_*`` gauges, ``goodput_report`` journal events), cross-
  generation journal stitching, and the checkpoint-interval advisor.
- ``obs.hangwatch`` — step-deadline hang watchdog: converts a wedged
  collective into a fast ``EXIT_HANG`` death the elastic supervisor can
  restart (``hang_detected`` journal event, bounded checkpoint drain).
- ``obs.retrace``  — retrace sentinel: hooks JAX compile telemetry and
  turns any post-warmup recompile into a ``retrace`` journal event with
  shape/dtype-diff attribution.
- ``obs.slo``      — declarative SLO objectives, rolling-window burn rates,
  and the latched degraded flag surfaced in ``/healthz``.
- ``obs.doctor_common`` — markdown/window helpers shared by the offline
  doctors (``tools/run_doctor.py``, ``tools/serve_doctor.py``).

The former ``utils/meters.py`` / ``utils/mfu.py`` / ``utils/profiling.py``
modules remain as import-compatible shims over this package.
"""

from jumbo_mae_tpu_tpu.obs.exporter import HealthState, TelemetryServer
from jumbo_mae_tpu_tpu.obs.fleet import FleetAggregator, HostBeacon, read_beacons
from jumbo_mae_tpu_tpu.obs.flightrec import FlightRecorder
from jumbo_mae_tpu_tpu.obs.goodput import (
    GOODPUT_BUCKETS,
    GoodputLedger,
    advise_ckpt_interval,
    bucket_display,
    stitch_generations,
)
from jumbo_mae_tpu_tpu.obs.hangwatch import HangWatchdog
from jumbo_mae_tpu_tpu.obs.journal import (
    JOURNAL_EVENTS,
    RunJournal,
    env_fingerprint,
    journal_dir,
    read_journal,
    read_merged_journal,
)
from jumbo_mae_tpu_tpu.obs.lockwatch import WatchedLock
from jumbo_mae_tpu_tpu.obs.memwatch import (
    LeakSentinel,
    MemAccountant,
    MemoryWatcher,
    host_available_bytes,
    host_rss_bytes,
    tree_nbytes,
)
from jumbo_mae_tpu_tpu.obs.retrace import RetraceSentinel
from jumbo_mae_tpu_tpu.obs.modelstats import (
    STAT_NAMES,
    first_nonfinite_group,
    group_layout,
    group_of,
    group_stats,
    publish_group_stats,
    stats_dict,
)
from jumbo_mae_tpu_tpu.obs.metrics import (
    LATENCY_BUCKETS,
    NULL_REGISTRY,
    RATIO_BUCKETS,
    AverageMeter,
    Counter,
    Family,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    get_registry,
    set_registry,
)
from jumbo_mae_tpu_tpu.obs.costmodel import (
    COST_SCHEMA_VERSION,
    ProgramCost,
    UtilizationReport,
    cost_asdict,
    extract_cost,
    publish_cost,
    utilization_report,
)
from jumbo_mae_tpu_tpu.obs.mfu import (
    PEAK_TFLOPS,
    MfuReport,
    classify_flops_per_image,
    detect_peak_tflops,
    encoder_flops_per_image,
    lookup_peak_tflops,
    mfu_report,
    normalize_device_kind,
    pretrain_flops_per_image,
)
from jumbo_mae_tpu_tpu.obs.perfledger import (
    LEDGER_SCHEMA,
    append_row,
    comparable_env,
    make_row,
    read_ledger,
    resolve_history_path,
)
from jumbo_mae_tpu_tpu.obs.perfmodel import (
    ChipSpec,
    PerfPrediction,
    chip_spec,
    detect_chip,
    dp_comm_bytes,
    fsdp_comm_bytes,
    predict_train_step,
    publish_drift,
    roofline,
)
from jumbo_mae_tpu_tpu.obs.reqtrace import (
    OUTCOMES,
    AccessLog,
    RequestTrace,
    RequestTracer,
)
from jumbo_mae_tpu_tpu.obs.slo import SLOObjective, SLOTracker, parse_slo
from jumbo_mae_tpu_tpu.obs.trace import (
    note_program,
    programs,
    span,
    span_timer,
    trace,
)

__all__ = [
    "AccessLog",
    "AverageMeter",
    "COST_SCHEMA_VERSION",
    "ChipSpec",
    "Counter",
    "Family",
    "FleetAggregator",
    "FlightRecorder",
    "GOODPUT_BUCKETS",
    "Gauge",
    "GoodputLedger",
    "HangWatchdog",
    "HostBeacon",
    "HealthState",
    "Histogram",
    "LATENCY_BUCKETS",
    "LEDGER_SCHEMA",
    "LeakSentinel",
    "MemAccountant",
    "MemoryWatcher",
    "MetricsRegistry",
    "MfuReport",
    "NULL_REGISTRY",
    "NullRegistry",
    "OUTCOMES",
    "PEAK_TFLOPS",
    "PerfPrediction",
    "ProgramCost",
    "RATIO_BUCKETS",
    "RequestTrace",
    "RequestTracer",
    "JOURNAL_EVENTS",
    "RetraceSentinel",
    "RunJournal",
    "WatchedLock",
    "SLOObjective",
    "SLOTracker",
    "STAT_NAMES",
    "TelemetryServer",
    "UtilizationReport",
    "advise_ckpt_interval",
    "append_row",
    "bucket_display",
    "chip_spec",
    "classify_flops_per_image",
    "comparable_env",
    "cost_asdict",
    "detect_chip",
    "detect_peak_tflops",
    "dp_comm_bytes",
    "encoder_flops_per_image",
    "env_fingerprint",
    "extract_cost",
    "first_nonfinite_group",
    "fsdp_comm_bytes",
    "get_registry",
    "group_layout",
    "group_of",
    "group_stats",
    "host_available_bytes",
    "host_rss_bytes",
    "journal_dir",
    "lookup_peak_tflops",
    "make_row",
    "mfu_report",
    "normalize_device_kind",
    "note_program",
    "parse_slo",
    "predict_train_step",
    "pretrain_flops_per_image",
    "programs",
    "publish_cost",
    "publish_drift",
    "publish_group_stats",
    "read_beacons",
    "read_journal",
    "read_ledger",
    "read_merged_journal",
    "resolve_history_path",
    "roofline",
    "set_registry",
    "span",
    "span_timer",
    "stats_dict",
    "stitch_generations",
    "trace",
    "tree_nbytes",
    "utilization_report",
]
