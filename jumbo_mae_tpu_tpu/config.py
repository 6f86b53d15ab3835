"""Run configuration: a dataclass tree with YAML recipes + CLI overrides.

Replaces the reference's config story — bash scripts passing ~50 argparse
flags per entry point (``/root/reference/src/main_pretrain.py:98-167``,
``/root/reference/config/*.sh``) — with typed recipe files. Epoch→step
arithmetic the reference did in shell (``$((1281167 * EPOCHS / BATCH))``,
``/root/reference/config/ft.sh:40-43``) is a config-time helper here
(``epochs:`` keys), and seeds default to fixed values, not ``random.randint``
(defect #7).

Override grammar: ``--set optim.learning_rate=1e-3 data.workers=0`` — dotted
paths into the tree, values parsed as YAML scalars.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Literal

import yaml

from jumbo_mae_tpu_tpu.data.loader import DataConfig
from jumbo_mae_tpu_tpu.parallel.mesh import MeshConfig
from jumbo_mae_tpu_tpu.train.checkpoint import CheckpointConfig
from jumbo_mae_tpu_tpu.train.modes import MODES, STEP_MODE
from jumbo_mae_tpu_tpu.train.optim import OptimConfig

IMAGENET_TRAIN_SIZE = 1_281_167

Mode = Literal["pretrain", "finetune", "linear", "lm"]


@dataclass(frozen=True)
class ModelConfig:
    """Encoder/decoder selection: a preset name plus field overrides."""

    preset: str = "vit_b16"
    overrides: dict[str, Any] = field(default_factory=dict)
    # decoder (pretrain only). The common knobs are first-class fields; every
    # other DecoderConfig field (dropout/droppath/layerscale/grad_ckpt/
    # remat_policy) is reachable via ``dec_overrides``, mirroring the
    # encoder's ``overrides`` (parity: the reference's
    # --dec-dropout/--dec-droppath/--dec-layerscale flags,
    # /root/reference/src/main_pretrain.py).
    dec_layers: int = 8
    dec_dim: int = 512
    dec_heads: int = 16
    dec_dtype: str = "bfloat16"
    dec_overrides: dict[str, Any] = field(default_factory=dict)
    norm_pix_loss: bool = True
    # mode lm: fields of models/lm.MlaMoeConfig (preset and the fields
    # above are the vision models' and are not read)
    lm: dict[str, Any] = field(default_factory=dict)
    # classifier head (finetune/linear only)
    mixup: float = 0.0
    cutmix: float = 0.0
    label_smoothing: float = 0.0
    criterion: str = "ce"


@dataclass(frozen=True)
class RunConfig:
    mode: Mode = "pretrain"
    name: str = "run"
    output_dir: str = "runs"
    seed: int = 0
    init_seed: int = 0

    training_steps: int = 100
    log_interval: int = 50
    eval_interval: int = 1000
    # checkpoint cadence decoupled from eval: ckpt_every > 0 also saves a
    # checkpoint every N steps (no eval pass attached). 0 keeps the legacy
    # behavior — checkpoints ride eval boundaries only. tools/goodput_doctor
    # recommends a concrete value from measured save cost and failure rate.
    ckpt_every: int = 0

    train_batch_size: int = 256  # GLOBAL batch
    valid_batch_size: int = 256
    grad_accum: int = 1

    synthetic_data: bool = False
    sanity_eval: bool = True
    # evaluate-and-exit: restore weights (run.pretrained_ckpt or run.resume)
    # and run one full validation pass — no training. Beyond the reference
    # (its eval only ever runs inline in the train loop). eval_which picks
    # the checkpoint slot restored under run.resume: the rolling "last"
    # (resume semantics) or the metric-best "best".
    eval_only: bool = False
    eval_which: str = "last"
    resume: bool = False
    pretrained_ckpt: str = ""
    profile_dir: str = ""
    # resilience (jumbo_mae_tpu_tpu/faults): the divergence sentinel skips
    # non-finite steps on device and, after sentinel_patience consecutive
    # bad steps (skips or loss spikes above sentinel_spike_factor x EMA),
    # rolls back to the last checkpoint with the data cursor restored —
    # giving up after sentinel_max_rollbacks. `faults` holds a fault-
    # injection plan (GRAFT_FAULTS grammar, see faults/inject.py) — chaos
    # testing only; empty means the env var (if any) stays in charge.
    sentinel: bool = True
    sentinel_patience: int = 3
    sentinel_spike_factor: float = 10.0
    sentinel_ema_beta: float = 0.98
    sentinel_max_rollbacks: int = 3
    faults: str = ""
    # diagnostics (obs/modelstats, obs/journal, obs/flightrec):
    # diag_every > 0 compiles per-layer-group grad/param/update-ratio stats
    # + the loss batch's finite fraction into the train step (one extra
    # (groups, 3) array out; the base program is untouched at 0) and
    # fetches/publishes them every diag_every steps. `journal` writes the
    # append-only crash-safe run journal under <output_dir>/<name>/journal/.
    # flightrec_steps sizes the crash flight recorder's per-step ring
    # buffer (0 disables black-box dumps entirely).
    diag_every: int = 0
    journal: bool = True
    flightrec_steps: int = 256
    # retrace sentinel (obs/retrace.py): after warmup, any XLA recompile
    # journals a `retrace` event with shape/dtype-diff attribution and
    # warns. Costs one jax.monitoring listener + a dict lookup per step.
    retrace: bool = True
    # telemetry (jumbo_mae_tpu_tpu/obs): metrics are always *recorded*; the
    # exporter serving them over HTTP (/metrics Prometheus text, /healthz)
    # is opt-in. Port 0 binds any free port (the chosen one is printed).
    telemetry: bool = False
    telemetry_port: int = 9100
    telemetry_host: str = "0.0.0.0"
    # fleet health (obs/fleet.py): every process atomically rewrites a
    # per-host beacon under <run_dir>/fleet/ (step, step-time EMA, data-wait
    # fraction, shard retries/quarantines, sentinel bad steps, heartbeat);
    # host 0 aggregates the beacon dir into fleet_*{host=} gauges, journals
    # fleet_straggler / fleet_host_lost / fleet_host_rejoined transitions,
    # and feeds /healthz (degraded is soft — never a 503). A host is a
    # straggler when it trails the fleet-max step by fleet_lag_steps or its
    # step-time EMA exceeds fleet_ratio x the fleet median; lost when its
    # heartbeat is older than fleet_dead_after_s.
    fleet: bool = True
    fleet_lag_steps: int = 2
    fleet_ratio: float = 1.5
    fleet_dead_after_s: float = 60.0
    # elastic fleet training (train/elastic.py + obs/hangwatch.py): the hang
    # watchdog kills a process whose step makes no progress for
    # hangwatch_deadline_s seconds (0 = disabled; compile/eval/restore pause
    # it via expected() windows) with EXIT_HANG so the supervisor restarts
    # it. The supervisor (cli/train.py --elastic N) restarts a broken fleet
    # from the last committed checkpoint at the surviving world size, under
    # a budget of elastic_max_restarts with exponential backoff
    # (elastic_backoff_s doubling to elastic_backoff_cap_s); a host whose
    # beacon goes stale for elastic_wedge_after_s while its process lives is
    # treated as wedged supervisor-side; after a down-size, a graceful
    # restart back to full world size is attempted every
    # elastic_rejoin_after_s seconds.
    hangwatch_deadline_s: float = 0.0
    elastic_max_restarts: int = 8
    elastic_backoff_s: float = 1.0
    elastic_backoff_cap_s: float = 60.0
    elastic_wedge_after_s: float = 0.0
    elastic_rejoin_after_s: float = 30.0
    # memory observability (obs/memwatch.py): sample device/host memory per
    # log window (and per /metrics scrape when serving), journal mem_sample
    # snapshots, publish mem_* gauges, and run the leak sentinel — a robust
    # RSS slope over memwatch_leak_window samples exceeding memwatch_leak_mb
    # journals mem_leak_suspect naming the fastest-growing component, dumps
    # the flight recorder, and latches /healthz degraded.
    memwatch: bool = True
    memwatch_leak_window: int = 12
    memwatch_leak_mb: float = 32.0
    # serving SLOs (jumbo_mae_tpu_tpu/obs/slo.py): objectives like
    # "p99_latency_ms<=250;success_rate>=0.99" evaluated over a rolling
    # slow window with a fast confirmation window (0 = window_s / 12);
    # breaches above burn_threshold latch the degraded flag in /healthz
    # and publish the slo_* gauges. Empty = no SLO tracking.
    slo: str = ""
    slo_window_s: float = 60.0
    slo_fast_window_s: float = 0.0
    slo_burn_threshold: float = 1.0
    # continuous deployment (serve/publisher.py): publish_dir non-empty
    # turns on the gated train→serve weights publisher — every checkpoint
    # that passes the gates (finite-loss window since the last save,
    # sentinel-clean, at least publish_min_interval_steps since the last
    # publish, and — when publish_metric_key is set — the eval metric
    # above/below publish_metric_floor per publish_metric_sense) is
    # exported as an inference-ready artifact into publish_dir (the
    # directory `predict --swap-watch` polls). publish_quant "int8"
    # quantizes matmul weights at publish time (infer/quant.py);
    # "none" ships f32. Deltas ride against the last published tree;
    # a full tree is forced every publish_full_every artifacts.
    publish_dir: str = ""
    publish_quant: str = "int8"
    publish_min_interval_steps: int = 0
    publish_full_every: int = 8
    publish_metric_key: str = ""
    publish_metric_floor: float = 0.0
    publish_metric_sense: str = "below"
    use_wandb: bool = True
    wandb_project: str = ""
    wandb_entity: str = ""
    wandb_tags: tuple = ()
    wandb_id: str = ""  # stable id → resume the same wandb run on restart


@dataclass(frozen=True)
class TrainConfig:
    run: RunConfig = field(default_factory=RunConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    data: DataConfig = field(default_factory=DataConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)

    def checkpoint_config(self) -> CheckpointConfig:
        spec = MODES[STEP_MODE[self.run.mode]]
        return CheckpointConfig(
            directory=str(Path(self.run.output_dir) / self.run.name / "ckpt"),
            best_mode=spec.best_mode,
            metric_key=spec.best_metric,
        )


def steps_from_epochs(
    epochs: float, global_batch: int, dataset_size: int = IMAGENET_TRAIN_SIZE
) -> int:
    return int(dataset_size * epochs / global_batch)


_SECTIONS = {
    "run": RunConfig,
    "model": ModelConfig,
    "optim": OptimConfig,
    "data": DataConfig,
    "mesh": MeshConfig,
}


def _coerce(cls, raw: dict) -> Any:
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = set(raw) - names
    if unknown:
        raise ValueError(f"unknown {cls.__name__} keys: {sorted(unknown)}")
    return cls(**raw)


def _resolve_epochs(doc: dict) -> dict:
    """Allow ``epochs`` / ``warmup_epochs`` in run/optim sections; converted
    against the global train batch size."""
    doc = {k: dict(v) if isinstance(v, dict) else v for k, v in doc.items()}
    run = doc.get("run", {})
    batch = run.get("train_batch_size", RunConfig.train_batch_size)
    # One source of truth for the dataset size: data.dataset_size wins, a
    # top-level dataset_size is accepted as shorthand, then the ImageNet
    # constant. The resolved value feeds BOTH the epochs→steps conversion
    # and the resume data cursor (cli/train.py).
    top_level = doc.pop("dataset_size", None)
    data_sec = doc.setdefault("data", {})
    dataset = data_sec.get("dataset_size", top_level)
    if dataset is None:
        dataset = IMAGENET_TRAIN_SIZE
    elif not isinstance(dataset, int) or isinstance(dataset, bool) or dataset <= 0:
        # it feeds both epochs→steps and the resume cursor — fail loudly
        raise ValueError(f"dataset_size must be a positive int, got {dataset!r}")
    data_sec["dataset_size"] = dataset
    if "epochs" in run:
        run["training_steps"] = steps_from_epochs(run.pop("epochs"), batch, dataset)
    optim = doc.get("optim", {})
    if "warmup_epochs" in optim:
        optim["warmup_steps"] = steps_from_epochs(
            optim.pop("warmup_epochs"), batch, dataset
        )
    optim.setdefault("training_steps", run.get("training_steps", RunConfig.training_steps))
    doc["run"], doc["optim"] = run, optim
    return doc


def config_from_dict(doc: dict) -> TrainConfig:
    doc = _resolve_epochs(doc or {})
    unknown = set(doc) - set(_SECTIONS)
    if unknown:
        raise ValueError(f"unknown config sections: {sorted(unknown)}")
    return TrainConfig(
        **{sec: _coerce(cls, doc.get(sec, {})) for sec, cls in _SECTIONS.items()}
    )


def _parse_value(text: str) -> Any:
    value = yaml.safe_load(text)
    if isinstance(value, str):
        # YAML 1.1 doesn't recognize dot-less scientific notation ("1e-3")
        try:
            return float(value)
        except ValueError:
            return value
    return value


def apply_overrides(doc: dict, overrides: list[str]) -> dict:
    doc = {k: dict(v) if isinstance(v, dict) else v for k, v in doc.items()}
    for item in overrides:
        if "=" not in item:
            raise ValueError(f"override must be key.path=value, got {item!r}")
        path, value = item.split("=", 1)
        keys = path.split(".")
        node = doc
        for k in keys[:-1]:
            node = node.setdefault(k, {})
            if not isinstance(node, dict):
                raise ValueError(f"cannot override through scalar at {k!r}")
        node[keys[-1]] = _parse_value(value)
    return doc


def load_config(
    path: str | Path | None = None, overrides: list[str] | None = None
) -> TrainConfig:
    doc: dict = {}
    if path is not None:
        doc = yaml.safe_load(Path(path).read_text()) or {}
    doc = apply_overrides(doc, overrides or [])
    return config_from_dict(doc)


def config_to_dict(cfg: TrainConfig) -> dict:
    return dataclasses.asdict(cfg)
