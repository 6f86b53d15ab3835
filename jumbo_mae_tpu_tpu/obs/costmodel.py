"""Compiled-cost extraction: what XLA says each program costs.

The analytic FLOP counts in ``obs/mfu.py`` are what the *model* should cost;
this module records what the *compiled program* actually costs, straight from
XLA's own accounting (``Compiled.cost_analysis()`` / ``memory_analysis()``):
flops, bytes accessed, and the argument/output/temp HBM footprint. Three
consumers hang off one extraction:

- gauges: every compiled program publishes ``xla_flops`` / ``xla_bytes_*`` /
  ``xla_peak_bytes`` with ``(program, bucket, dtype)`` labels — the train
  step via ``cli/train.py``, every engine bucket executable via
  ``infer/engine.py``;
- the journal: one ``compiled_program`` event per program at compile time,
  so the cost basis of a run survives the process;
- the MFU split: analytic flops / measured time = *model* flops utilization
  (MFU), XLA-counted flops / measured time = *hardware* flops utilization
  (HFU; includes remat recompute and fusion overhead). HFU ≥ MFU, and the
  gap is the recompute bill.

Extraction must never cost a compile: both analyses are free readouts of an
already-compiled executable, and every path here degrades to ``None`` when a
backend reports nothing (PJRT plugins may legally return empty analyses).
"""

from __future__ import annotations

from dataclasses import asdict, dataclass

# Bump when the ProgramCost field set changes: journal events and ledger rows
# carry it so offline readers can tell schemas apart.
COST_SCHEMA_VERSION = 1


@dataclass
class ProgramCost:
    """XLA's accounting for one compiled executable.

    ``source`` records how much the backend gave us: ``"compiled"`` (cost +
    memory analysis), ``"lowered"`` (cost analysis only — no memory stats),
    or the instance is absent entirely (extraction returned ``None``).
    """

    program: str
    flops: float = 0.0
    bytes_accessed: float = 0.0
    argument_bytes: float = 0.0
    output_bytes: float = 0.0
    temp_bytes: float = 0.0
    peak_bytes: float = 0.0
    generated_code_bytes: float = 0.0
    source: str = "compiled"


def _cost_dict(executable) -> dict | None:
    """Normalize ``cost_analysis()`` across jax versions: 0.4.x returns a
    list with one dict per partition, newer versions a plain dict."""
    ca = executable.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return ca if isinstance(ca, dict) and ca else None


def extract_cost(executable, program: str) -> ProgramCost | None:
    """Read XLA's cost/memory analysis off a ``Compiled`` (or ``Lowered``)
    executable. Never compiles, never raises: a backend that reports nothing
    yields ``None`` and the caller publishes nothing."""
    try:
        ca = _cost_dict(executable)
    except Exception:  # noqa: BLE001 - optional per PJRT contract
        ca = None
    if ca is None:
        return None
    cost = ProgramCost(
        program=program,
        flops=max(0.0, float(ca.get("flops", 0.0) or 0.0)),
        bytes_accessed=max(0.0, float(ca.get("bytes accessed", 0.0) or 0.0)),
    )
    try:
        mem = executable.memory_analysis()
    except Exception:  # noqa: BLE001
        mem = None
    if mem is None:
        cost.source = "lowered"
        return cost
    get = lambda attr: float(getattr(mem, attr, 0) or 0)  # noqa: E731
    cost.argument_bytes = get("argument_size_in_bytes")
    cost.output_bytes = get("output_size_in_bytes")
    cost.temp_bytes = get("temp_size_in_bytes")
    cost.generated_code_bytes = get("generated_code_size_in_bytes")
    # live-at-once upper bound: args + outputs + scratch, minus donated
    # aliases (counted in both argument and output sizes)
    cost.peak_bytes = max(
        0.0,
        cost.argument_bytes
        + cost.output_bytes
        + cost.temp_bytes
        - get("alias_size_in_bytes"),
    )
    return cost


def cost_asdict(cost: ProgramCost) -> dict:
    """Journal/ledger payload shape for one program's cost."""
    return {"cost_schema": COST_SCHEMA_VERSION, **asdict(cost)}


def lookup_cost(cost_reports, task: str, bucket: int) -> ProgramCost | None:
    """Resolve the ``ProgramCost`` for one dispatched ``(task, bucket)``.

    Engine cost tables are keyed ``(task_key, bucket)`` where ``task_key``
    may be pool-suffixed (``"features/mean"``); the dispatcher only knows
    the plain task name. Resolution order: exact key, then any key at the
    same bucket whose task component equals or extends ``task``, then any
    key at that bucket (single-task engines). ``None`` when the table is
    empty or the bucket was never compiled — the meter then bills
    device-time only."""
    if not cost_reports:
        return None
    exact = cost_reports.get((task, int(bucket)))
    if exact is not None:
        return exact
    fallback = None
    for (key_task, key_bucket), cost in cost_reports.items():
        if int(key_bucket) != int(bucket):
            continue
        if key_task == task or str(key_task).startswith(f"{task}/"):
            return cost
        if fallback is None:
            fallback = cost
    return fallback


_GAUGES = (
    ("xla_flops", "flops", "XLA-counted flops per execution"),
    ("xla_bytes_accessed", "bytes_accessed", "XLA-counted bytes accessed per execution"),
    ("xla_peak_bytes", "peak_bytes", "estimated live-at-once memory (args+out+temp-aliased)"),
    ("xla_argument_bytes", "argument_bytes", "argument buffer bytes"),
    ("xla_output_bytes", "output_bytes", "output buffer bytes"),
    ("xla_temp_bytes", "temp_bytes", "scratch/temp buffer bytes"),
)


def publish_cost(
    cost: ProgramCost, *, bucket: str = "", dtype: str = "", registry=None
) -> None:
    """Set the ``xla_*{program,bucket,dtype}`` gauge family for one program.

    Called once per compile — gauge handles are resolved here, not on the
    hot path."""
    if cost is None:
        return
    if registry is None:
        from jumbo_mae_tpu_tpu.obs.metrics import get_registry

        registry = get_registry()
    labels = (cost.program, str(bucket), str(dtype))
    for name, field, help_ in _GAUGES:
        fam = registry.gauge(name, help_, labels=("program", "bucket", "dtype"))
        fam.labels(*labels).set(getattr(cost, field))


@dataclass
class UtilizationReport:
    """The MFU/HFU split over one measured steady-state window."""

    model_flops_utilization: float
    hardware_flops_utilization: float
    achieved_model_tflops: float
    achieved_hardware_tflops: float
    peak_tflops: float


def utilization_report(
    analytic_flops_per_step: float,
    xla_flops_per_step: float | None,
    steps_per_sec: float,
    *,
    n_chips: int = 1,
    peak_tflops: float,
) -> UtilizationReport:
    """MFU (analytic model flops) vs HFU (XLA-counted flops, remat included)
    over one throughput measurement. ``xla_flops_per_step`` is the whole
    program's count; both are divided across ``n_chips``. ``peak_tflops``
    is the chip's table entry (``obs.mfu.detect_peak_tflops``)."""
    peak = max(float(peak_tflops), 1e-12)
    model_t = analytic_flops_per_step / max(n_chips, 1) * steps_per_sec / 1e12
    hw_t = (
        (xla_flops_per_step or 0.0) / max(n_chips, 1) * steps_per_sec / 1e12
    )
    return UtilizationReport(
        model_flops_utilization=model_t / peak,
        hardware_flops_utilization=hw_t / peak,
        achieved_model_tflops=model_t,
        achieved_hardware_tflops=hw_t,
        peak_tflops=peak,
    )
