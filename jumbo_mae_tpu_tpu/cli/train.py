"""Unified training entry point: pretrain / finetune / linear probe.

The reference shipped two near-identical entry scripts
(``/root/reference/src/main_pretrain.py:48-96``,
``/root/reference/src/main_finetune.py:48-96``) driven by bash flag files;
here one loop covers all three modes, driven by YAML recipes
(``recipes/``). Structure parity with the reference loop: sanity eval before
step 1, step loop with metric meters, periodic eval + best/last
checkpointing — plus what it lacked: true resume, MFU/throughput reporting,
deterministic seeds, profiler capture.

Run:
    python -m jumbo_mae_tpu_tpu.cli.train --config recipes/pretrain_vit_b16_in1k_1600ep.yaml
    python -m jumbo_mae_tpu_tpu.cli.train --config ... --set run.training_steps=10 data.workers=0
"""

from __future__ import annotations

import argparse
import contextlib
import math
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from jumbo_mae_tpu_tpu.config import (
    IMAGENET_TRAIN_SIZE,
    TrainConfig,
    config_to_dict,
    load_config,
)
from jumbo_mae_tpu_tpu.data import (
    TrainLoader,
    epoch_shard_order,
    merge_shard_states,
    prefetch_to_device,
    resize_assignment,
    split_for_accum,
    synthetic_batches,
    valid_loader,
)
from jumbo_mae_tpu_tpu.data.synthetic import token_batches
from jumbo_mae_tpu_tpu.data.tario import QUARANTINE
from jumbo_mae_tpu_tpu.faults import (
    DivergenceError,
    DivergenceSentinel,
    SentinelConfig,
    fault_point,
    faults_active,
    host_leak_tick,
    install_plan,
    leak_ballast_bytes,
    set_host_index,
)
from jumbo_mae_tpu_tpu.models import (
    ClassificationModel,
    DecoderConfig,
    MAEPretrainModel,
    preset,
)
from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig, MlaMoeLM
from jumbo_mae_tpu_tpu.obs.mfu import lm_flops_per_token
from jumbo_mae_tpu_tpu.ops.head_loss import head_tile
from jumbo_mae_tpu_tpu.parallel import batch_sharding, create_mesh
from jumbo_mae_tpu_tpu.train import (
    EXIT_FATAL,
    EXIT_HANG,
    EXIT_OK,
    Checkpointer,
    RunEngine,
    create_sharded_state,
    exit_code_for,
    load_pretrained_params,
    make_eval_step,
    make_optimizer,
    make_train_step,
)
from jumbo_mae_tpu_tpu.obs import (
    FleetAggregator,
    FlightRecorder,
    GoodputLedger,
    HangWatchdog,
    HealthState,
    HostBeacon,
    RunJournal,
    TelemetryServer,
    env_fingerprint,
    first_nonfinite_group,
    get_registry,
    group_layout,
    publish_group_stats,
    span_timer,
    stats_dict,
    trace,
)
from jumbo_mae_tpu_tpu.obs.trace import (
    SPAN_MODEL_BUILD,
    format_setup_report,
    setup_report,
    spanned,
)
from jumbo_mae_tpu_tpu.obs.costmodel import (
    cost_asdict,
    extract_cost,
    publish_cost,
    utilization_report,
)
from jumbo_mae_tpu_tpu.obs.memwatch import (
    LeakSentinel,
    MemAccountant,
    MemoryWatcher,
)
from jumbo_mae_tpu_tpu.obs.perfmodel import detect_chip, publish_drift, roofline
from jumbo_mae_tpu_tpu.utils import (
    AverageMeter,
    MetricLogger,
    StepTimer,
    classify_flops_per_image,
    detect_peak_tflops,
    mfu_report,
    param_summary,
    pretrain_flops_per_image,
)
from jumbo_mae_tpu_tpu.train.modes import MODES, STEP_MODE
from jumbo_mae_tpu_tpu.utils.procenv import enable_compile_cache


@spanned(SPAN_MODEL_BUILD)
def build_model(cfg: TrainConfig):
    """Construct the mode's flax module and its per-sample train FLOPs (a
    sample is an image, or in mode ``lm`` a sequence of ``data.seq_len``
    tokens)."""
    m = cfg.model
    mode = cfg.run.mode
    if mode == "lm":
        lm = MlaMoeConfig(**m.lm)
        if cfg.data.seq_len <= 0:
            raise ValueError("run.mode=lm needs data.seq_len > 0")
        flops = cfg.data.seq_len * lm_flops_per_token(lm, cfg.data.seq_len)
        return MlaMoeLM(lm), lm, flops
    if mode == "pretrain":
        enc = preset(m.preset, labels=None, **{"mask_ratio": 0.75, **m.overrides})
        dec = DecoderConfig(
            **{
                "layers": m.dec_layers,
                "dim": m.dec_dim,
                "heads": m.dec_heads,
                "dtype": m.dec_dtype,
                **m.dec_overrides,
            }
        )
        model = MAEPretrainModel(enc, dec, norm_pix_loss=m.norm_pix_loss)
        flops = pretrain_flops_per_image(enc, dec)
        return model, enc, flops
    linear = mode == "linear"
    enc = preset(
        m.preset,
        **{
            "mask_ratio": None,
            "linear_probing": linear,
            "batch_norm": linear,
            **m.overrides,
        },
    )
    model = ClassificationModel(
        enc,
        mixup_alpha=m.mixup,
        cutmix_alpha=m.cutmix,
        label_smoothing=m.label_smoothing,
        criterion=m.criterion,
    )
    return model, enc, classify_flops_per_image(enc)


def _mode_inputs(cfg: TrainConfig) -> tuple[str, ...]:
    return MODES[STEP_MODE[cfg.run.mode]].inputs


def _token_row(cfg: TrainConfig) -> int:
    """Ids a batch row holds in mode ``lm``: the trained tokens, the next
    one, and one more per multi-token-prediction module; a block-diffusion
    model's, the clean tokens alone (``MlaMoeConfig.token_row``)."""
    return MlaMoeConfig(**cfg.model.lm).token_row(cfg.data.seq_len)


def _zero_batch(cfg: TrainConfig, rows: int) -> dict:
    """An all-zero batch of the mode's input leaves."""
    size = cfg.data.image_size
    leaves = {
        "images": lambda: np.zeros((rows, size, size, 3), np.uint8),
        "labels": lambda: np.zeros((rows,), np.int32),
        "tokens": lambda: np.zeros((rows, _token_row(cfg)), np.int32),
    }
    return {name: leaves[name]() for name in _mode_inputs(cfg)}


def _example_batch(cfg: TrainConfig, per_process: int) -> dict:
    return split_for_accum(_zero_batch(cfg, per_process), cfg.run.grad_accum)


def _strip_for_model(cfg: TrainConfig, batch: dict) -> dict:
    return {k: batch[k] for k in _mode_inputs(cfg) if k in batch}


def _synthetic(cfg: TrainConfig, per_process: int, num_labels: int, *, seed: int,
               grad_accum: int = 1):
    """The mode's seeded synthetic batches."""
    if cfg.run.mode == "lm":
        lm = MlaMoeConfig(**cfg.model.lm)
        first, rows = lm.rows
        # a block-diffusion model's last row held is its mask id: no document holds it
        return token_batches(
            per_process, _token_row(cfg),
            vocab_rows=(first, rows - 1) if lm.diffusion_block else (first, rows),
            grad_accum=grad_accum, seed=seed,
        )
    return synthetic_batches(
        per_process,
        cfg.data.image_size,
        # the MODEL's class count — labels >= cfg.labels one-hot to
        # all-zero rows, silently zeroing CE loss and pinning acc at 1
        labels=num_labels if "labels" in _mode_inputs(cfg) else None,
        grad_accum=grad_accum,
        seed=seed,
    )


def make_train_iterator(
    cfg: TrainConfig,
    mesh,
    per_process: int,
    start_step: int = 0,
    data_cursor: dict | None = None,
    num_labels: int = 1000,
    shard_override: list | None = None,
    shard_preconsumed: dict | None = None,
):
    """Build the device-prefetched train iterator.

    Resume: with a checkpointed ``data_cursor`` the loader continues the
    deterministic stream sample-exactly (per-worker epoch/offset + the
    round-robin phase). Without one (old checkpoint, changed worker count)
    it falls back to the coarse epoch cursor: restart the stream at the
    epoch the resumed step falls in — per-epoch shard order and shuffles are
    keyed on (seed, epoch), so no sample skipping is needed. One stream
    epoch yields dataset_size × repeats samples (repeated augmentation
    clones count toward the batch).

    ``shard_override`` is the resize-consistent resume path: explicit
    ``(global_index, url)`` pairs for this process's share of the resume
    epoch (computed by :func:`_resize_shard_override` from the journaled
    shard cursors), replacing the topology-derived stripe for that epoch
    only. ``shard_preconsumed`` rides with it — the merged consumed set
    the override was derived from, seeded into the new generation's shard
    ledgers so their ``shard_cursor`` snapshots stay CUMULATIVE across
    generations (a second resize must subtract everything ever consumed,
    not just this generation's reads).

    Returns ``(iterator, source, cursor_log, shard_log)`` — ``cursor_log``
    maps each absolute step to the loader snapshot after that step's batch
    left the loader (prefetch-safe: recorded at loader exit, consumed by
    step index); ``shard_log`` likewise maps steps to the merged
    consumed-shard ledger snapshot, journaled as ``shard_cursor`` at each
    checkpoint so a future resized resume can reconstruct the assignment.
    """
    start_epoch = (start_step * cfg.run.train_batch_size) // max(
        1, cfg.data.dataset_size * max(1, cfg.data.repeats)
    )
    if start_step > 0 and data_cursor is None:
        if (
            cfg.data.dataset_size == IMAGENET_TRAIN_SIZE
            and cfg.data.train_shards
            and "imagenet" not in str(cfg.data.train_shards).lower()
        ):
            print(
                "[train] WARNING: resuming with the default (ImageNet) "
                "data.dataset_size but custom train_shards — if the real "
                "dataset is smaller, the resume epoch below is wrong; set "
                "data.dataset_size explicitly"
            )
        print(f"[train] data cursor: resuming stream at epoch {start_epoch}")
    cursor_log: dict[int, dict] = {}
    shard_log: dict[int, dict] = {}
    if cfg.run.synthetic_data:
        it = _synthetic(cfg, per_process, num_labels, seed=cfg.run.seed,
                        grad_accum=cfg.run.grad_accum)
        source = None
    elif cfg.run.mode == "lm":
        raise ValueError("run.mode=lm reads run.synthetic_data only: there is "
                         "no token loader yet")
    else:
        data_cursor = _pick_process_cursor(data_cursor)
        loader_kwargs = dict(
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            start_epoch=start_epoch,
        )
        try:
            source = TrainLoader(
                cfg.data,
                per_process,
                cursor=data_cursor,
                epoch_shard_override=shard_override,
                shard_preconsumed=shard_preconsumed,
                **loader_kwargs,
            )
            if data_cursor is not None:
                print(
                    "[train] data cursor: sample-exact resume at epoch/offset "
                    f"{data_cursor['workers']}"
                )
        except ValueError as e:
            if data_cursor is None:
                raise
            print(f"[train] WARNING: {e}; falling back to epoch-{start_epoch} resume")
            source = TrainLoader(
                cfg.data,
                per_process,
                epoch_shard_override=shard_override,
                shard_preconsumed=shard_preconsumed,
                **loader_kwargs,
            )

        def tracked():
            step = start_step
            for b in source:
                step += 1
                cursor_log[step] = source.snapshot()
                shards = source.shard_snapshot()
                if shards is not None:
                    shard_log[step] = shards
                yield b

        it = (split_for_accum(b, cfg.run.grad_accum) for b in tracked())
    it = ({k: v for k, v in b.items() if k != "valid"} for b in it)
    it = (_strip_for_model(cfg, b) for b in it)
    sharding = batch_sharding(mesh, accum=cfg.run.grad_accum > 1)
    return prefetch_to_device(it, sharding), source, cursor_log, shard_log


def make_valid_iterator(
    cfg: TrainConfig, mesh, per_process: int, num_labels: int = 1000
):
    sharding = batch_sharding(mesh, accum=False)
    if cfg.run.synthetic_data:
        def gen():
            it = _synthetic(cfg, per_process, num_labels, seed=cfg.run.seed + 1)
            for _, batch in zip(range(4), it):
                batch["valid"] = np.ones((per_process,), bool)
                yield batch

        return lambda: prefetch_to_device(gen(), sharding)
    if not cfg.data.valid_shards:
        return None
    return lambda: prefetch_to_device(
        valid_loader(
            cfg.data,
            per_process,
            process_index=jax.process_index(),
            process_count=jax.process_count(),
        ),
        sharding,
    )


class PreemptionGuard:
    """SIGTERM-safe training: TPU pods get preempted with a grace window, so
    a termination signal flips a flag and the step loop checkpoints at the
    next step boundary instead of dying mid-state (the reference had no
    resume at all, let alone a graceful-preemption path). SIGINT gets the
    same treatment so ^C on an interactive run saves before exiting."""

    def __init__(self):
        self.flagged = False

    def install(self) -> bool:
        import signal

        def handler(signum, frame):
            if self.flagged:
                # second signal: restore default behavior so a stuck run
                # (hung collective, long compile) stays force-killable
                signal.signal(signum, signal.SIG_DFL)
                signal.raise_signal(signum)
                return
            self.flagged = True
            print(
                f"[train] caught signal {signum}: will checkpoint and exit "
                "at the next step boundary (signal again to force-exit)"
            )

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, handler)
            except ValueError:  # not the main thread (e.g. under a runner)
                print(
                    "[train] WARNING: not on the main thread — graceful "
                    "preemption disabled; SIGTERM will kill the run "
                    "without a checkpoint"
                )
                return False
        return True


def _agree_on_preemption(preempt: "PreemptionGuard", process_count: int) -> bool:
    """Whether to take the preemption exit — all processes must agree (a
    checkpoint save is collective), so multi-host gathers every host's flag."""
    if process_count == 1:
        return preempt.flagged
    from jax.experimental import multihost_utils

    return bool(
        multihost_utils.process_allgather(np.asarray(preempt.flagged)).any()
    )


def _pick_process_cursor(data_cursor: dict | None) -> dict | None:
    """Restore-side counterpart of :func:`_gather_data_cursor`: select this
    process's cursor from the checkpointed payload. The checkpoint records
    every process's cursor plus the saving topology (the saved JSON is
    host-0's); sample-exact resume is only valid with the SAME process count
    — shard stripes and per-process batch sizes are topology-dependent — so
    any mismatch drops every process to epoch resume together (a mixed
    schedule would be globally inconsistent)."""
    if data_cursor is None:
        return None
    saved_pc = int(data_cursor.get("process_count", 1))
    if saved_pc != jax.process_count():
        print(
            f"[train] WARNING: checkpoint data cursor was saved with "
            f"{saved_pc} processes but this run has "
            f"{jax.process_count()}; falling back to epoch resume"
        )
        return None
    if "per_process" in data_cursor:
        picked = {
            "workers": data_cursor["per_process"][jax.process_index()],
            "batches": data_cursor["batches"],
        }
        if data_cursor.get("native_threads") is not None:
            picked["native_threads"] = data_cursor["native_threads"]
        return picked
    return data_cursor


def _gather_data_cursor(snap: dict | None) -> dict | None:
    """Make a loader snapshot checkpoint-safe under multi-host: Orbax's JSON
    payload is host-0's, so every process's cursor is all-gathered into it
    (``per_process``); restore picks the entry for ``jax.process_index()``.
    Collective — every process must call this at the same step."""
    if snap is None:
        return None
    if jax.process_count() == 1:
        return {**snap, "process_count": 1}
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(
        np.asarray(snap["workers"], np.int64)
    )
    # override marker: any-host semantics — if even one host's streams are
    # still inside an epoch_shard_override epoch, its offsets are measured
    # against the override stripe and the whole fleet must take the
    # journal-derived resume path (a mixed schedule would be inconsistent)
    ov = multihost_utils.process_allgather(
        np.asarray(
            -1
            if snap.get("override_epoch") is None
            else int(snap["override_epoch"]),
            np.int64,
        )
    )
    out = {
        "per_process": gathered.tolist(),
        "batches": snap["batches"],
        "process_count": jax.process_count(),
    }
    # substrate marker must survive the gather or a native-IO cursor can
    # never restore on a pod (and would mis-resume on the worker path)
    if snap.get("native_threads") is not None:
        out["native_threads"] = snap["native_threads"]
    if int(ov.max()) >= 0:
        out["override_epoch"] = int(ov.max())
    return out


def _resize_shard_override(
    cfg: TrainConfig,
    run_dir: Path,
    start_step: int,
    old_world: int,
    *,
    world: int,
    host: int,
) -> tuple[list, dict, dict]:
    """Resize-consistent resume (data/resize.py): reconstruct this process's
    shard assignment for the resume epoch from the journaled cursors.

    Reads the run's merged journal, takes each old host's ``shard_cursor``
    at the restored step, unions the consumed sets, and stripes the
    epoch's remainder across the NEW world — a pure function of
    ``(world, host, journal)``, no collective, so every process computes a
    disjoint, exhaustive assignment independently. Raises when no cursor
    exists for the step (pre-elastic checkpoint, journal disabled) — the
    caller falls back to plain epoch resume.

    Returns ``(pairs, preconsumed, info)``: ``preconsumed`` is the merged
    consumed-set snapshot the assignment subtracted, in
    :meth:`~jumbo_mae_tpu_tpu.data.resize.ShardLedger.snapshot` shape —
    the caller seeds it into the new generation's ledgers so the next
    ``shard_cursor`` events stay cumulative across generations.
    """
    from jumbo_mae_tpu_tpu.obs.journal import read_merged_journal

    latest: dict[int, dict] = {}
    for e in read_merged_journal(run_dir):
        if (
            e.get("type") == "shard_cursor"
            and int(e.get("step", -1)) == start_step
        ):
            latest[int(e.get("host", 0))] = e
    if not latest:
        raise FileNotFoundError(
            f"no shard_cursor journal events at step {start_step} "
            f"under {run_dir}"
        )
    merged = merge_shard_states(
        [{"epochs": e.get("epochs") or {}} for e in latest.values()]
    )
    start_epoch = (start_step * cfg.run.train_batch_size) // max(
        1, cfg.data.dataset_size * max(1, cfg.data.repeats)
    )
    order = epoch_shard_order(
        cfg.data.train_shards, seed=cfg.run.seed, epoch=start_epoch
    )
    consumed = merged.get(start_epoch, set())
    pairs = resize_assignment(order, consumed, world_size=world, process_id=host)
    preconsumed = {
        "epochs": {str(e): sorted(v) for e, v in merged.items()}
    }
    info = {
        "step": start_step,
        "epoch": start_epoch,
        "old_world": old_world,
        "new_world": world,
        "shards_total": len(order),
        "shards_consumed": len(consumed),
        "shards_remaining": len(order) - len(consumed),
        "cursor_hosts": sorted(latest),
    }
    return pairs, preconsumed, info


def _apply_override_resume(
    cfg: TrainConfig,
    run_dir: Path,
    data_cursor: dict | None,
    start_step: int,
    *,
    process_count: int,
    host_index: int,
    emit,
) -> tuple[dict | None, list | None, dict | None]:
    """Decide the data-resume mode: sample-exact cursor vs journal-derived
    shard override. The override path is taken when the cursor was saved
    under a DIFFERENT world size (its per-worker offsets describe streams
    striped for the old topology), or when it carries ``override_epoch`` —
    the saving generation was itself running on an ``epoch_shard_override``,
    so the offsets were measured on the override stripe and replaying them
    against the topology stripe would silently yield different samples even
    at the SAME world size (crash/preemption restart mid-override).

    Returns ``(data_cursor, shard_override, shard_preconsumed)``. On the
    override path the sample cursor is voided (resume is shard-granular);
    when the journal cannot reconstruct the assignment, the cursor is also
    voided — its offsets are meaningless for this generation's stripes —
    and the run falls back to plain epoch resume.
    """
    if (
        data_cursor is None
        or cfg.run.synthetic_data
        or not cfg.data.train_shards
    ):
        return data_cursor, None, None
    old_world = int(data_cursor.get("process_count", 1))
    if old_world == process_count and data_cursor.get("override_epoch") is None:
        return data_cursor, None, None
    try:
        pairs, preconsumed, rinfo = _resize_shard_override(
            cfg,
            run_dir,
            start_step,
            old_world,
            world=process_count,
            host=host_index,
        )
    except (OSError, ValueError, KeyError) as e:
        # no usable shard cursors in the journal (pre-elastic checkpoint,
        # journal disabled or unreadable): epoch resume still works
        print(
            f"[train] WARNING: resize-consistent resume unavailable "
            f"({e}); falling back to epoch resume"
        )
        return None, None, None
    cause = "resize" if old_world != process_count else "override_restart"
    emit("elastic_resize", cause=cause, **rinfo)
    print(
        f"[train] elastic resize ({cause}): world {old_world} -> "
        f"{process_count}; epoch {rinfo['epoch']} resumes with "
        f"{rinfo['shards_remaining']}/{rinfo['shards_total']} "
        "shards unconsumed"
    )
    return None, pairs, preconsumed


def evaluate(eval_step, state, batches, pad_batch: dict | None = None) -> dict[str, float]:
    """Weighted-exact eval aggregation (sums / num_samples — fixes the
    reference's pretrain val-loss normalization, SURVEY defect #2).

    Multi-host: the jitted eval step contains collectives, so every process
    must issue the SAME number of calls even when shard striping gives them
    different batch counts. Processes that run out of data keep feeding
    ``pad_batch`` (all rows ``valid=False``) until every process is done —
    agreement reached with a tiny host-level all-gather per round.
    """
    multi = jax.process_count() > 1
    if multi:
        from jax.experimental import multihost_utils

    totals: dict[str, float] = {}
    it = iter(batches)
    i = 0
    pending: list = []
    while True:
        batch = next(it, None)
        if multi:
            anyone_has_data = bool(
                multihost_utils.process_allgather(
                    np.asarray(batch is not None)
                ).any()
            )
            if not anyone_has_data:
                break
            if batch is None:
                if pad_batch is None:
                    raise ValueError(
                        "multi-host eval needs pad_batch for exhausted processes"
                    )
                batch = pad_batch
        elif batch is None:
            break
        # accumulate device scalars; fetch ONCE after the loop — a per-batch
        # device_get would serialize host dispatch against device compute,
        # exactly what the train loop avoids at its log boundaries
        pending.append(eval_step(state, batch, i))
        i += 1
    for sums in jax.device_get(pending):
        for k, v in sums.items():
            totals[k] = totals.get(k, 0.0) + float(v)
    n = max(totals.pop("num_samples", 0.0), 1.0)
    return {f"val/{k}": v / n for k, v in totals.items()}


def train(cfg: TrainConfig) -> dict:
    """Run the configured job; returns the final summary metrics."""
    run = cfg.run
    if run.faults:
        # recipe-driven chaos: the plan outlives this call on purpose (the
        # GRAFT_FAULTS env path behaves the same) — tests clear it
        plan = install_plan(run.faults)
        print(f"[faults] injection plan active: sites={plan.sites()}")
    process_count = jax.process_count()
    host_index = jax.process_index()
    # pin the fault layer's host identity (the `@host=` selector) before any
    # site can fire; mirrored into GRAFT_HOST so data workers inherit it
    set_host_index(host_index)
    # elastic generation: stamped into the environment by the supervisor's
    # launch() so scrapes, beacons and merged journals can tell pre- from
    # post-restart processes (0 = first launch / no supervisor)
    generation = int(os.environ.get("GRAFT_GENERATION", "0") or 0)
    # goodput ledger (obs/goodput.py): the clock starts HERE, at the top of
    # train(), so state build, compile and restore are on the books — every
    # second of this process's wall-clock lands in exactly one bucket
    ledger = GoodputLedger(generation=generation)
    if run.train_batch_size % (process_count * run.grad_accum):
        raise ValueError(
            f"process_count * grad_accum ({process_count} * {run.grad_accum}) "
            f"must divide the global batch size ({run.train_batch_size})"
        )
    per_process = run.train_batch_size // process_count
    per_process_valid = max(1, run.valid_batch_size // process_count)

    if run.eval_only and not (cfg.data.valid_shards or run.synthetic_data):
        # fail before any device/state work
        raise ValueError(
            "run.eval_only requires validation data "
            "(data.valid_shards or run.synthetic_data)"
        )
    if run.eval_which not in ("last", "best"):
        raise ValueError(
            f"run.eval_which must be 'last' or 'best', got {run.eval_which!r}"
        )
    if run.eval_which != "last" and not (run.eval_only and run.resume):
        # never silently drop a knob: slot selection only has an effect on
        # the eval_only+resume restore (pretrained_ckpt goes through the
        # warm-start merge, training resume is defined as 'last')
        raise ValueError(
            "run.eval_which=best requires run.eval_only=true AND "
            "run.resume=true (other paths would silently ignore it)"
        )

    cfg.mesh.validate_pipe()
    pipe_microbatches = 0
    if cfg.mesh.pipe > 1:
        from jumbo_mae_tpu_tpu.parallel import create_pipeline_mesh

        n_dev = len(jax.devices())
        pipe_data = cfg.mesh.data
        if pipe_data in (1, -1):
            # untouched default (or explicit fill): cover every device —
            # and say so, because a gpipe microbatch-divisibility error
            # downstream would otherwise reference a data axis the user
            # never wrote (data=1 cannot opt out: a pipe mesh that strands
            # devices is rejected below, so 1 could only ever mean
            # n_dev == pipe, which the fill reproduces)
            pipe_data = max(1, n_dev // cfg.mesh.pipe)
            if pipe_data > 1:
                print(
                    f"[mesh] data axis auto-filled to {pipe_data} "
                    f"(pipe={cfg.mesh.pipe} over {n_dev} devices); set "
                    "mesh.data explicitly to override"
                )
        if pipe_data * cfg.mesh.pipe < n_dev:
            # silently training on a subset is an easy way to waste a pod
            raise ValueError(
                f"mesh data={pipe_data} x pipe={cfg.mesh.pipe} covers only "
                f"{pipe_data * cfg.mesh.pipe} of {n_dev} devices; choose "
                "mesh.pipe to divide the device count (mesh.data=-1 "
                "auto-fills the data axis), or expose fewer devices to "
                "the process"
            )
        mesh = create_pipeline_mesh(data=pipe_data, pipe=cfg.mesh.pipe)
        pipe_microbatches = cfg.mesh.pipe_microbatches or cfg.mesh.pipe
    else:
        mesh = create_mesh(cfg.mesh)
    if cfg.mesh.pipe_decoder and (run.mode != "pretrain" or not pipe_microbatches):
        # never silently drop a parallelism knob
        raise ValueError(
            "mesh.pipe_decoder requires run.mode=pretrain and mesh.pipe>1"
        )
    model, enc_cfg, flops_per_image = build_model(cfg)

    # after config/mesh validation (so invalid runs never create checkpoint
    # directories) but before the expensive sharded-state build, so an
    # unsatisfiable eval_only restore fails fast. A non-resume eval_only run
    # never saves — skip the Checkpointer (and its eager dir creation).
    ckpt = (
        None
        if run.eval_only and not run.resume
        else Checkpointer(cfg.checkpoint_config())
    )
    # the top-of-train guard pins eval_which to "last" outside eval_only
    eval_which = run.eval_which
    resuming = (
        run.resume
        and ckpt is not None
        and ckpt.latest_step(eval_which) is not None
    )
    if run.eval_only and run.resume and not resuming:
        # an explicit restore request that can't be satisfied must not fall
        # through to plausible-looking random-init metrics
        ckpt.close()
        raise FileNotFoundError(
            f"run.eval_only with run.resume=true but no '{eval_which}' "
            f"checkpoint under {cfg.checkpoint_config().directory}"
        )

    if run.eval_only:
        # evaluation never steps the optimizer — a no-op tx keeps AdamW's
        # ~2x-params moment buffers off the device entirely
        import optax

        tx = optax.identity()
    else:
        tx = make_optimizer(
            cfg.optim, run.train_batch_size, num_layers=enc_cfg.layers
        )

    example = _example_batch(cfg, per_process)
    state, state_sharding = create_sharded_state(
        model,
        tx,
        example,
        mesh,
        mode=STEP_MODE[run.mode],
        init_seed=run.init_seed,
        rng_seed=run.seed,
        param_dtype=cfg.optim.param_dtype,
    )

    if run.pretrained_ckpt and not resuming:
        # (skipped on resume: the checkpoint restore below overwrites params
        # AND opt_state anyway — re-doing the merge + a full jitted tx.init
        # would only cost startup time and a transient opt-state allocation)
        # With low-precision param storage, merge into an f32 template so
        # the master copy keeps the checkpoint's full precision (merging
        # straight into bf16 params would quantize the master at init);
        # stored params are then the downcast, per the master-weights
        # contract.
        low_precision = cfg.optim.param_dtype and jnp.dtype(
            cfg.optim.param_dtype
        ) != jnp.float32
        template = (
            jax.tree_util.tree_map(
                lambda p: p.astype(jnp.float32), state.params
            )
            if low_precision
            else state.params
        )
        merged = load_pretrained_params(run.pretrained_ckpt, template)
        # Optimizer state derives from the params at tx.init time — re-init
        # so anything param-coupled follows the merge (critical with
        # optim.param_dtype: the f32 master copy in opt_state would
        # otherwise still hold the random init and the first step would
        # overwrite the warm start with master-derived values).
        opt_state = jax.jit(
            state.tx.init, out_shardings=state_sharding.opt_state
        )(merged)
        if low_precision:
            merged = jax.tree_util.tree_map(
                lambda m, p: m.astype(p.dtype), merged, state.params
            )
        state = state.replace(params=merged, opt_state=opt_state)

    start_step = 0
    data_cursor = None
    ckpt_fallbacks: list[dict] = []  # journaled once the journal exists
    if resuming:
        if run.eval_only:
            # params/batch_stats/rng only — the saved opt_state never
            # touches the device (tx is a no-op identity here)
            state, extra = ckpt.restore_eval(
                state, sharding=state_sharding, which=eval_which
            )
        else:
            # a corrupt/torn latest step (host died mid-commit, fs
            # hiccup) walks back to the previous committed step instead
            # of killing the resume — bounded, and journaled below so
            # the replayed window is auditable
            def _note_fallback(from_step, to_step, err):
                ckpt_fallbacks.append(
                    {
                        "from_step": int(from_step),
                        "to_step": int(to_step),
                        "error": f"{type(err).__name__}: {err}",
                    }
                )

            state, extra = ckpt.restore(
                state,
                sharding=state_sharding,
                fallback_steps=2,
                on_fallback=_note_fallback,
            )
        start_step = int(state.step)
        data_cursor = extra.get("data_cursor")
        if not run.eval_only:
            ledger.add("ckpt_restore", ckpt.last_restore_s or 0.0)
        print(f"[train] resumed from step {start_step}")

    mode_key = STEP_MODE[run.mode]
    # mesh.pipe_decoder additionally depth-shards the MAE decoder stack
    # (pretrain only; mesh.pipe must divide dec_layers)
    dec_cfg = model.decoder_cfg if cfg.mesh.pipe_decoder else None
    # per-layer-group diagnostics (obs/modelstats): a STATIC flag — with
    # diag_every=0 the compiled step program is byte-identical to pre-diag
    diag_on = run.diag_every > 0 and not run.eval_only
    diag_names = group_layout(state.params) if diag_on else ()
    train_step = (
        None
        if run.eval_only  # dead work in an eval-and-exit run
        else make_train_step(
            mesh,
            state_sharding,
            mode=mode_key,
            grad_accum=run.grad_accum,
            pipe_microbatches=pipe_microbatches,
            encoder_cfg=enc_cfg if pipe_microbatches else None,
            decoder_cfg=dec_cfg,
            guard_nonfinite=run.sentinel,
            diag=diag_on,
        )
    )
    eval_step = make_eval_step(mesh, state_sharding, mode=mode_key)

    is_main = host_index == 0
    if is_main:
        # startup parameter table (parity: the reference's module.tabulate
        # pre-flight print, /root/reference/src/pretraining.py:214)
        print(param_summary(state.params))
    preempt = PreemptionGuard()
    if not run.eval_only:
        # eval_only has no step loop to honor the flag and nothing to
        # checkpoint — default signal behavior (exit now) is the honest one
        preempt.install()
    # telemetry: metrics always record into the process registry; the HTTP
    # exporter (/metrics + /healthz) is opt-in per recipe. State is built and
    # (if requested) restored by this point, so readiness is honest.
    health = HealthState()
    health.set_ready(True, detail=f"mode={run.mode} start_step={start_step}")
    # data-layer resilience surfaced to the operator: shard URLs the retry
    # layer gave up on this process (worker subprocesses keep their own —
    # the inline and native-IO substrates report here)
    health.probe("quarantined_shards", lambda: sorted(QUARANTINE.snapshot()))
    telemetry = None
    if run.telemetry and is_main:
        telemetry = TelemetryServer(
            health=health, host=run.telemetry_host, port=run.telemetry_port
        ).start()
        print(
            f"[obs] exporter on {run.telemetry_host}:{telemetry.port} "
            "(/metrics, /healthz)"
        )
    logger = MetricLogger(
        Path(run.output_dir) / run.name,
        name=run.name,
        config=config_to_dict(cfg),
        enabled=is_main,
        use_wandb=run.use_wandb,
        wandb_project=run.wandb_project,
        wandb_entity=run.wandb_entity,
        wandb_tags=tuple(run.wandb_tags),
        wandb_id=run.wandb_id,
    )
    if run.mode == "lm":
        # static, from the causal kernels' block tables and their plan of a
        # masked pair's sub-tiles: the score entries a (head, sequence) of each
        # attention kind computes, and those its mask keeps; logged once,
        # beside the steps' train/moe_* counters
        pairs = {
            f"train/attn_pairs_{what}_{kind}": count
            for kind, counts in enc_cfg.attn_pairs(cfg.data.seq_len).items()
            for what, count in zip(("visited", "needed"), counts)
        }
        if pairs:
            logger.log(pairs, step=start_step)
            if is_main:
                print(f"[train] attention pairs a head and sequence: {pairs}")
        # static too: the heads this chip holds of each attention kind, and the
        # model's own count (they differ where a layer's heads are divided)
        heads = {
            f"train/attn_heads_{what}_{kind}": count
            for kind, counts in enc_cfg.attn_heads().items()
            for what, count in zip(("held", "published"), counts)
        }
        # and the trunk blocks of each mixer kind (a conv block has no heads)
        layers = {f"train/layers_{kind}": n for kind, n in enc_cfg.layers_by_kind.items()}
        logger.log(heads | layers, step=start_step)
        if is_main:
            print(f"[train] attention heads a kind: {heads}")
            print(f"[train] blocks a mixer kind: {layers}; qk_norm {enc_cfg.qk_norm}, "
                  f"tie_embeddings {enc_cfg.tie_embeddings}")
            # static too: the expert layers' variants
            print(f"[train] expert layers: the router reads {enc_cfg.router_input} and scores "
                  f"{enc_cfg.router_scoring}; an expert's gate is {enc_cfg.expert_act}")
            # static too: the tile of tokens the head's loss walks (ops/head_loss.py)
            tokens = run.train_batch_size // run.grad_accum * cfg.data.seq_len
            tile = head_tile(tokens, enc_cfg.rows[1])
            print(f"[train] head loss: {tokens} tokens a program over {enc_cfg.rows[1]} rows "
                  f"in {-(-tokens // tile)} tile(s) of {tile}")
    valid_factory = make_valid_iterator(
        cfg, mesh, per_process_valid, num_labels=getattr(enc_cfg, "labels", None) or 1000
    )
    # all-padding eval batch, pre-sharded by EVERY process at setup so
    # exhausted hosts can keep stepping the collective eval program
    pad_batch = None
    if valid_factory is not None and process_count > 1:
        host_pad = _zero_batch(cfg, per_process_valid)
        host_pad["valid"] = np.zeros((per_process_valid,), bool)
        if "labels" in host_pad:
            host_pad["labels"] -= 1
        pad_batch = next(
            prefetch_to_device(iter([host_pad]), batch_sharding(mesh, accum=False))
        )

    if run.eval_only:
        assert valid_factory is not None  # guaranteed by the top-of-train check
        if is_main and not (resuming or run.pretrained_ckpt):
            print(
                "[eval] WARNING: eval_only on a fresh random init — set "
                "run.pretrained_ckpt or run.resume=true to restore weights"
            )
        val = evaluate(eval_step, state, valid_factory(), pad_batch)
        logger.log(val, step=start_step)
        if is_main:
            print(f"[eval] step {start_step}: {val}")
        if ckpt is not None:
            ckpt.close()
        logger.close()
        if telemetry is not None:
            telemetry.close()
        return val

    if run.sanity_eval and valid_factory is not None:
        print(
            "[train] sanity eval:",
            evaluate(eval_step, state, valid_factory(), pad_batch),
        )

    # run-history diagnostics (EVERY host, unlike the logger): the crash-safe
    # journal — host 0 under <run_dir>/journal/, host i under
    # <run_dir>/journal-host<i>/, every row host-tagged, merged offline by
    # read_merged_journal — and the black-box flight recorder dumping into
    # <run_dir>/ (host-tagged filenames off host 0) on non-finite steps,
    # rollbacks, SIGTERM, or an escaping exception. Installed AFTER the
    # preemption guard so its SIGTERM handler dumps first, then chains into
    # graceful checkpointing.
    run_dir = Path(run.output_dir) / run.name
    journal = None
    if run.journal:
        jdir = run_dir / ("journal" if is_main else f"journal-host{host_index}")
        journal = RunJournal(jdir, host=host_index)
    flightrec = (
        FlightRecorder(run_dir, capacity=run.flightrec_steps, host=host_index)
        if run.flightrec_steps > 0
        else None
    )
    if flightrec is not None:
        flightrec.install()

    def _emit(etype: str, **fields) -> None:
        """One diagnostic event → journal (durable) + flight ring (memory)."""
        rec = {"ts": round(time.time(), 3), "type": etype, **fields}
        if journal is not None:
            try:
                rec = journal.event(etype, **fields)
            except OSError as e:  # a full disk must not kill the run
                print(f"[obs] WARNING: journal write failed: {e}")
        if flightrec is not None:
            flightrec.record_event(rec)

    def _black_box(reason: str, **extra) -> None:
        if flightrec is None:
            return
        path = flightrec.dump(reason, extra=extra or None)
        _emit("flight_record", reason=reason, path=str(path))
        print(f"[obs] flight record ({reason}) -> {path}")

    # retrace sentinel (obs/retrace.py): armed after the first step, every
    # further XLA compile journals a `retrace` event with shape/dtype-diff
    # attribution — unless it's expected (eval, fault-inject executables)
    retrace_sentinel = None
    if run.retrace:
        from jumbo_mae_tpu_tpu.obs.retrace import RetraceSentinel

        retrace_sentinel = RetraceSentinel("train", journal=journal)

    def _rt_expected(reason: str):
        """Compiles inside are legitimate: a one-off eval, and the small
        slice programs a checkpoint save of a sharded state compiles."""
        return (
            retrace_sentinel.expected(reason)
            if retrace_sentinel is not None
            else contextlib.nullcontext()
        )

    if journal is not None:
        health.probe("journal", lambda: str(journal.path))
    _emit(
        "run_start",
        config=config_to_dict(cfg),
        env=env_fingerprint(),
        start_step=start_step,
        resumed=bool(resuming),
        generation=generation,
        diag_every=run.diag_every,
        diag_groups=list(diag_names),
    )
    for fb in ckpt_fallbacks:
        _emit("ckpt_fallback", **fb)

    # fleet health (obs/fleet.py): every host rewrites its beacon each step;
    # host 0 additionally aggregates the beacon dir into fleet_* gauges (on
    # the exporter's scrape, so idle scans cost nothing), journals straggler/
    # lost/rejoined transitions via _emit, and feeds /healthz (soft degraded)
    beacon = None
    fleet_agg = None
    beacon_stats: dict = {"generation": generation}
    if run.fleet:
        beacon = HostBeacon(run_dir / "fleet", host=host_index)
        if is_main:
            fleet_agg = FleetAggregator(
                run_dir / "fleet",
                expected_hosts=process_count,
                lag_steps=run.fleet_lag_steps,
                ratio=run.fleet_ratio,
                dead_after_s=run.fleet_dead_after_s,
                on_event=_emit,
            )
            health.probe("fleet", fleet_agg.summary)
            health.degraded_when(fleet_agg.degraded)
            if telemetry is not None:
                telemetry.add_pre_scrape(fleet_agg.scan)

    def _beacon_write(step_now: int) -> None:
        if beacon is None:
            return
        try:
            beacon.write(step=step_now, **beacon_stats)
        except OSError:  # a shared-fs hiccup must not kill the run
            pass

    # hang watchdog (obs/hangwatch.py): beats ride the pre-step hook; a
    # wedged collective stops them, and at run.hangwatch_deadline_s the
    # watchdog journals the stall, drains the async checkpoint writer
    # (bounded), and exits EXIT_HANG — the elastic supervisor converts
    # that into a restart instead of an indefinite stall
    hangwatch = None
    if run.hangwatch_deadline_s > 0:
        hangwatch = HangWatchdog(
            run.hangwatch_deadline_s,
            exit_code=EXIT_HANG,
            drain=ckpt.wait,
        )

        @hangwatch.on_fire
        def _hang_fired(info):
            _emit("hang_detected", host=host_index, **info)
            # the stall the watchdog sat through is pure detection latency;
            # a final cumulative report makes it to the journal before the
            # os._exit — offline stitching reads it as this generation's
            # last word
            ledger.add("hang_latency", float(info.get("stalled_s") or 0.0))
            _emit(
                "goodput_report",
                **ledger.report(step=int(info.get("step") or 0), reason="hang"),
            )
            _beacon_write(int(info.get("step") or 0))
            if flightrec is not None:
                try:
                    flightrec.dump("hang_detected", extra=info)
                except Exception:  # noqa: BLE001 - already dying loudly
                    pass
            print(
                f"[train] HANG: no step progress for "
                f"{info['stalled_s']:.0f}s (deadline "
                f"{info['deadline_s']:.0f}s) — exiting {EXIT_HANG}"
            )

        hangwatch.start()
        print(
            f"[train] hang watchdog armed after step 1: deadline "
            f"{run.hangwatch_deadline_s:.0f}s -> exit {EXIT_HANG}"
        )

    def _hw_expected(reason: str):
        """Legitimately-slow phases (eval, rollback restore, checkpoint
        waits) suspend the step-deadline clock; the fleet.wedge fault and
        real collective stalls sit OUTSIDE every such window."""
        return (
            hangwatch.expected(reason)
            if hangwatch is not None
            else contextlib.nullcontext()
        )

    # resize-consistent resume: a checkpoint saved under a different world
    # size — or mid-override at the SAME world size — voids the sample-exact
    # cursor, but the journaled shard cursors reconstruct a shard-exact
    # assignment for this topology (no shard double-counted, none skipped —
    # tests/test_elastic.py)
    data_cursor, shard_override, shard_preconsumed = _apply_override_resume(
        cfg,
        run_dir,
        data_cursor,
        start_step,
        process_count=process_count,
        host_index=host_index,
        emit=_emit,
    )

    train_iter, source, cursor_log, shard_log = make_train_iterator(
        cfg, mesh, per_process, start_step, data_cursor,
        num_labels=getattr(enc_cfg, "labels", None) or 1000,
        shard_override=shard_override,
        shard_preconsumed=shard_preconsumed,
    )
    meter = AverageMeter()
    timer = StepTimer(warmup_steps=min(2, max(1, run.training_steps - 1)))
    # the chips this run computes on: a sub-mesh (mesh.data=1 mesh.fsdp=1 on
    # a four-chip host) leaves the other devices idle, and they must not
    # dilute the per-chip rates
    n_chips = mesh.devices.size
    last_metrics: dict[str, float] = {}
    # divergence sentinel (faults/sentinel.py): the device guard inside the
    # step skips non-finite updates; this host half watches the fetched
    # metrics for bad streaks and drives rollback-to-last-checkpoint
    sentinel = (
        DivergenceSentinel(
            SentinelConfig(
                patience=run.sentinel_patience,
                spike_factor=run.sentinel_spike_factor,
                ema_beta=run.sentinel_ema_beta,
                max_rollbacks=run.sentinel_max_rollbacks,
            )
        )
        if run.sentinel
        else None
    )
    if sentinel is not None:
        # per-step sentinel verdicts into the journal with exact step
        # indices; the loop emits the richer rollback event itself
        sentinel.on_event = lambda kind, payload: (
            _emit(f"sentinel_{kind}", **payload)
            if kind != "rollback"
            else None
        )

    # step-loop telemetry: spans aggregate into span_seconds{name=...}; the
    # gauges publish the log-window derived numbers the logger prints.
    # train_step spans measure DISPATCH (the loop syncs only at log
    # boundaries); true step wall time is the steps_per_sec the MFU uses.
    reg = get_registry()
    g_mfu = reg.gauge("train_mfu", "model FLOP utilization (log-window)")
    g_ips = reg.gauge("train_images_per_sec", "global throughput (log-window)")
    g_wait_frac = reg.gauge(
        "train_data_wait_fraction", "share of wall time waiting on data"
    )
    g_step = reg.gauge("train_step", "current absolute step")
    g_grad_norm = reg.gauge(
        "train_grad_norm", "global gradient norm of the last fetched step"
    )
    c_steps = reg.counter("train_steps_total", "optimizer steps this process")
    g_moe = reg.gauge(
        "train_moe",
        "expert-layer counters of the last fetched step (mode lm): rows per "
        "held expert, imbalance, held share, dropped, per layer and overall",
        labels=("counter",),
    )
    g_kda = reg.gauge(
        "train_kda",
        "linear-attention counters of the last fetched step (mode lm): "
        "largest |state| at the end of the sequences and mean decay a step, "
        "per layer and overall",
        labels=("counter",),
    )
    g_hfu = reg.gauge(
        "train_hardware_flops_utilization",
        "XLA-counted flops (remat recompute included) / peak (log-window)",
    )
    g_gen = reg.gauge(
        "run_generation",
        "elastic supervisor generation of this process (0 = first launch)",
    )
    g_gen.set(generation)
    # compiled-cost observability: the AOT dispatch in train/steps exposes
    # the step's executable, so XLA's cost/memory analysis is a free readout
    # — no second compile. Extracted once at the first log boundary,
    # journaled, and folded into the MFU/HFU split + drift gauge below.
    step_cost = None  # None = not yet extracted, False = gave up
    chip = detect_chip()
    # None on the CPU backend: a CPU count is not a device rate, so no
    # perf/mfu, perf/*_utilization or perf/tflops_per_chip is published there
    peak_tflops = detect_peak_tflops()
    # memory observability (obs/memwatch.py): log-boundary device/host
    # samples + per-component byte accounting + the leak sentinel. The
    # fault ballast probe makes the injected host.leak chaos site show up
    # as a *named* component in the verdict, closing the loop the CI
    # mem-smoke asserts.
    memwatch = None
    leak_sentinel = None
    if run.memwatch:
        accountant = MemAccountant()
        accountant.register("fault_ballast", leak_ballast_bytes)
        if flightrec is not None:
            accountant.register("flightrec_ring", flightrec.ring_bytes)
        if journal is not None:
            accountant.register(
                "journal_file", lambda: journal.path.stat().st_size
            )
        memwatch = MemoryWatcher(accountant=accountant, chip=chip)
        leak_sentinel = LeakSentinel(
            window=run.memwatch_leak_window,
            min_growth_mb=run.memwatch_leak_mb,
        )
        health.probe("memory", memwatch.last_sample)
        health.degraded_when(leak_sentinel.degraded)
    sp_wait = span_timer("data_wait")
    sp_step = span_timer("train_step")
    sp_ckpt = span_timer("checkpoint_save")
    # liveness: a wedged collective / dead loader flips /healthz to 503 well
    # before an operator would spot a silent stall in the logs
    health.watch("train_step", max_age_s=3600.0)
    health.watch("data_batch", max_age_s=3600.0)
    window_t0, window_wait = time.perf_counter(), 0.0
    window_steps = 0  # dispatches this log window (beacon step-time EMA)
    bad_total = 0  # cumulative sentinel-bad steps (beacon field)
    step_ema_s: float | None = None

    diag_pending: list = []  # [(step, device (G,3) stats)] fetched at log time
    prev_window_bad = False  # edge-trigger for the non-finite black box
    seen_quarantine: set = set()

    # -- the run engine (train/engine.py): the driver owns the step loop,
    # -- log-boundary metric fetch, rollback/preemption control flow, and
    # -- the crash/shutdown ladder; everything below registers into it ----
    def _next_batch(step_now: int):
        nonlocal window_wait
        with sp_wait:
            batch = next(train_iter)
        window_wait += sp_wait.last_s
        ledger.add("data_wait", sp_wait.last_s)
        health.beat("data_batch")
        return batch

    def _dispatch(state_now, batch, step_now: int):
        # fault sites train.loss / train.grad: traced multipliers into
        # the step (NaN at chosen invocations, no recompile); the
        # branch costs nothing when no plan is active
        inject = None
        if faults_active():
            # host.leak chaos site: corrupt(n) retains n MB/step in
            # the module ballast (the leak sentinel's test fixture);
            # a raise action models "the leak got fixed" and clears
            host_leak_tick(key=str(step_now))
            # fleet.wedge chaos site: delay(s) past the hangwatch
            # deadline holds THIS host's step outside any expected()
            # window — the watchdog, not the data path, must catch it
            fault_point("fleet.wedge", key=str(step_now), data=None)
            lm = fault_point("train.loss", key=str(step_now), data=1.0)
            gm = fault_point("train.grad", key=str(step_now), data=1.0)
            if (lm, gm) != (1.0, 1.0):
                inject = np.asarray([lm, gm], np.float32)
        if retrace_sentinel is not None:
            retrace_sentinel.note("train_step", batch)
        with sp_step:
            if inject is None:
                state_now, metrics = train_step(state_now, batch)
            elif retrace_sentinel is not None:
                # the inject arm is a distinct (legitimate)
                # executable — its first compile is not a retrace
                with retrace_sentinel.expected("fault-inject"):
                    state_now, metrics = train_step(state_now, batch, inject)
            else:
                state_now, metrics = train_step(state_now, batch, inject)
        # dispatch span → productive / compile (first dispatch) / rollback
        # recompute; the ledger routes by step number and process history
        ledger.note_step(step_now, sp_step.last_s)
        return state_now, metrics

    engine = RunEngine(
        training_steps=run.training_steps,
        start_step=start_step,
        log_interval=run.log_interval,
        eval_interval=run.eval_interval,
        ckpt_interval=run.ckpt_every,
        process_count=process_count,
        next_batch=_next_batch,
        dispatch=_dispatch,
        should_stop=lambda: _agree_on_preemption(preempt, process_count),
    )

    @engine.pre_step
    def _fleet_component(eng, step_now):
        # beacon BEFORE the data wait: under synchronous SPMD the
        # fetched step counts stay lockstep, but a host stuck waiting
        # on data sits at this step's entry while its peers dispatch
        # ahead — that dispatch gap is exactly what fleet_step_lag sees
        nonlocal window_steps
        _beacon_write(step_now)
        window_steps += 1
        if hangwatch is not None:
            hangwatch.beat(step_now)

    @engine.on_step
    def _telemetry_component(eng, ev):
        c_steps.inc()
        g_step.set(ev.step)
        health.beat("train_step")
        if ev.step == start_step + 1:
            # warmup over (first step compiled + dispatched): steady state
            if retrace_sentinel is not None:
                retrace_sentinel.arm()
            if hangwatch is not None:
                hangwatch.arm()

    @engine.on_step
    def _diag_component(eng, ev):
        if not diag_on:
            return
        # keep the (G,3) stats array OUT of the scalar pending list
        # (the meter/sentinel consume scalars); fetch it only at the
        # diag cadence — off-cadence arrays are dropped on device
        metrics = dict(ev.metrics)
        diag_dev = metrics.pop("diag")
        if ev.step % run.diag_every == 0 or ev.step == run.training_steps:
            diag_pending.append((ev.step, diag_dev))
        ev.metrics = metrics

    @engine.on_step
    def _pacing_component(eng, ev):
        timer.tick()
        # only cursor_log[step] (and prefetched future steps) are ever
        # read — prune dead entries every iteration, not just at save
        # time, or sparse checkpointing grows host memory without bound
        for k in [k for k in cursor_log if k < ev.step]:
            del cursor_log[k]
        for k in [k for k in shard_log if k < ev.step]:
            del shard_log[k]

    @engine.on_log_window
    def _log_window(eng, win):
        nonlocal step_cost, window_t0, window_wait, window_steps
        nonlocal bad_total, step_ema_s, prev_window_bad, last_metrics
        nonlocal seen_quarantine
        step = win.step
        window_bad: list[int] = []
        for (s, m) in win.fetched:
            skipped = float(m.get("skipped", 0.0)) >= 0.5
            loss_v = float(m.get("loss", math.nan))
            if skipped or not math.isfinite(loss_v):
                window_bad.append(s)
            gn = m.get("grad_norm")
            if gn is not None:
                g_grad_norm.set(float(gn))
            for key, value in m.items():
                if key.startswith("moe_"):
                    g_moe.labels(key[len("moe_"):]).set(float(value))
                elif key.startswith("kda_"):
                    g_kda.labels(key[len("kda_"):]).set(float(value))
            if flightrec is not None:
                entry = {"loss": loss_v}
                if gn is not None:
                    entry["grad_norm"] = float(gn)
                if "finite_frac" in m:
                    entry["finite_frac"] = float(m["finite_frac"])
                if skipped:
                    entry["skipped"] = True
                flightrec.record_step(s, entry)
            if sentinel is not None and sentinel.observe(s, m):
                eng.request_rollback()
            if not skipped:
                # a skipped step's loss is the garbage the guard
                # refused to apply — keep it out of the log means
                meter.update(m)
        win.bad_steps = window_bad
        # per-layer-group diagnostics: one small stacked array per
        # diag step, published as model_*{group=...} gauges
        latest_diag = None
        if diag_pending:
            for (ds, _), arr in zip(
                diag_pending,
                jax.device_get([a for _, a in diag_pending]),
            ):
                publish_group_stats(diag_names, arr)
                latest_diag = (ds, stats_dict(diag_names, arr), arr)
                if flightrec is not None:
                    flightrec.record_step(ds, {"diag": latest_diag[1]})
            diag_pending.clear()
        summary = meter.summary("train/")
        if step_cost is None:
            # the first losses are on the host: set-up is over, and the span
            # log says where it went (obs/trace.py; README "Reading a trace")
            for line in format_setup_report(setup_report(), min_s=0.25):
                print(f"[setup] {line}")
            execs = getattr(train_step, "executables", None)
            if execs:
                cost = extract_cost(
                    next(iter(execs.values())), "train_step"
                )
                if cost is not None:
                    step_cost = cost
                    publish_cost(
                        cost,
                        bucket="",
                        dtype=cfg.model.overrides.get("dtype", ""),
                    )
                    _emit(
                        "compiled_program",
                        batch=run.train_batch_size,
                        **cost_asdict(cost),
                    )
                else:
                    step_cost = False  # backend reported nothing
        sps = timer.steps_per_sec
        if sps:
            imgs = sps * run.train_batch_size
            summary |= {
                "perf/images_per_sec": imgs,
                "perf/images_per_sec_per_chip": imgs / n_chips,
            }
            if run.mode == "lm":
                summary["perf/tokens_per_sec_per_chip"] = (
                    imgs / n_chips * cfg.data.seq_len
                )
            g_ips.set(imgs)
            if peak_tflops is not None:
                rep = mfu_report(
                    flops_per_image, imgs / n_chips, peak_tflops=peak_tflops
                )
                summary |= {
                    "perf/mfu": rep.mfu,
                    "perf/tflops_per_chip": rep.achieved_tflops,
                }
                g_mfu.set(rep.mfu)
            if step_cost:
                # roofline drift; on an accelerator also MFU (analytic
                # model flops) vs HFU (XLA-counted, remat recompute included)
                if peak_tflops is not None:
                    util = utilization_report(
                        flops_per_image * run.train_batch_size,
                        step_cost.flops,
                        sps,
                        n_chips=n_chips,
                        peak_tflops=peak_tflops,
                    )
                    summary |= {
                        "perf/model_flops_utilization": rep.mfu,
                        "perf/hardware_flops_utilization": (
                            util.hardware_flops_utilization
                        ),
                    }
                    g_hfu.set(util.hardware_flops_utilization)
                pred = roofline(
                    step_cost.flops,
                    step_cost.bytes_accessed,
                    chip,
                    peak_hbm_bytes=step_cost.peak_bytes,
                )
                drift = publish_drift(
                    pred.step_time_s, 1.0 / sps, program="train_step"
                )
                summary |= {
                    "perf/predicted_step_ms": pred.step_time_s * 1e3,
                    "perf/predict_vs_measured": drift,
                }
        now = time.perf_counter()
        wait_frac = window_wait / max(now - window_t0, 1e-9)
        g_wait_frac.set(wait_frac)
        ledger.publish()  # goodput_* gauges follow the log-window cadence
        # memory sample BEFORE the beacon write so this window's
        # rss/device-peak ride out in this window's beacon
        msnap = None
        if memwatch is not None:
            if step_cost:
                memwatch.record_predicted_peak(
                    "train_step", step_cost.peak_bytes
                )
            msnap = memwatch.sample()
            if "rss_bytes" in msnap:
                beacon_stats["rss_bytes"] = int(msnap["rss_bytes"])
            if "device_peak_bytes" in msnap:
                beacon_stats["device_peak_bytes"] = int(
                    msnap["device_peak_bytes"]
                )
            if "note" in msnap:
                print(f"[obs] {msnap['note']}")
        if beacon is not None:
            st = (now - window_t0) / max(window_steps, 1)
            step_ema_s = (
                st
                if step_ema_s is None
                else 0.5 * step_ema_s + 0.5 * st
            )
            bad_total += len(window_bad)
            beacon_stats.update(
                step_time_ema_s=round(step_ema_s, 4),
                data_wait_fraction=round(wait_frac, 4),
                shard_retries=int(
                    reg.counter(
                        "data_shard_retries_total",
                        "shard reads retried after a "
                        "transient failure",
                    ).value
                ),
                shard_quarantines=len(QUARANTINE.snapshot()),
                sentinel_bad_steps=bad_total,
                goodput_fraction=round(ledger.fraction(), 4),
            )
            _beacon_write(step)
            if fleet_agg is not None:
                fsum = None
                try:
                    fsum = fleet_agg.scan()
                except OSError:
                    pass
                if fsum and fsum.get("lost"):
                    # a peer's beacon went stale past dead_after_s: the
                    # next collective would block on it forever — exit
                    # EXIT_ELASTIC at the stop-safe boundary and let the
                    # supervisor relaunch at the surviving world size
                    eng.notify_host_lost(
                        {"hosts": fsum["lost"], "detected_by": "beacon"}
                    )
        window_t0, window_wait, window_steps = now, 0.0, 0
        logger.log(summary, step=step)
        last_metrics = summary
        win.summary = summary

        # durable step snapshot + newly quarantined shards
        if journal is not None or flightrec is not None:
            snap_ev = {
                "step": step,
                "metrics": summary,
                "data_wait_fraction": round(wait_frac, 4),
            }
            if window_bad:
                snap_ev["bad_steps"] = window_bad
            if latest_diag is not None:
                snap_ev["diag_step"] = latest_diag[0]
                snap_ev["diag"] = latest_diag[1]
            _emit("step", **snap_ev)
            new_q = set(QUARANTINE.snapshot()) - seen_quarantine
            if new_q:
                seen_quarantine |= new_q
                _emit("quarantine", shards=sorted(new_q))
        if msnap is not None:
            _emit(
                "mem_sample",
                step=step,
                **{k: v for k, v in msnap.items() if k != "ts"},
            )
            fired = (
                leak_sentinel.observe(msnap)
                if leak_sentinel is not None
                else None
            )
            if fired is not None:
                _emit("mem_leak_suspect", step=step, **fired)
                print(
                    "[obs] WARNING: leak sentinel fired — "
                    f"suspect {fired['component']} "
                    f"(+{fired['robust_growth_bytes'] // (1024 * 1024)}"
                    f" MiB robust growth over {fired['window']} "
                    "samples); /healthz degraded"
                )
                _black_box("mem_leak", **fired)
        # black box on the first bad window (edge-triggered: a long
        # NaN streak is one incident, not a dump per log boundary)
        if window_bad:
            if flightrec is not None:
                flightrec.mark_abnormal()
            if not prev_window_bad:
                grp = (
                    first_nonfinite_group(diag_names, latest_diag[2])
                    if latest_diag is not None
                    else None
                )
                _black_box(
                    "nonfinite_step",
                    bad_steps=window_bad,
                    first_nonfinite_group=grp,
                )
        prev_window_bad = bool(window_bad)

    @engine.on_rollback
    def _rollback(eng, step, win):
        # persistent divergence: restore the last checkpoint
        # (params + optimizer + RNG + data cursor) and continue
        # from there. Skipping alone can't fix a state that is
        # already bad — rewinding to a known-good one can.
        nonlocal train_iter, source, cursor_log, shard_log, prev_window_bad
        if ckpt.latest_step("last") is None:
            raise DivergenceError(
                f"training diverged at step {step} with no "
                "checkpoint to roll back to — lower the LR or "
                "set run.eval_interval below the failure point"
            )
        sentinel.record_rollback()  # raises once budget is spent
        t0_restore = time.perf_counter()
        with _hw_expected("rollback"):
            ckpt.wait()  # a save may still be in flight
            eng.state, extra = ckpt.restore(
                eng.state, sharding=state_sharding
            )
        ledger.add("ckpt_restore", time.perf_counter() - t0_restore)
        rolled_from, new_step = step, int(eng.state.step)
        # every step re-dispatched up to rolled_from is recompute, not
        # progress — lost work the goodput report makes visible
        ledger.note_rollback(rolled_from, new_step)
        print(
            f"[train] sentinel rollback #{sentinel.rollbacks} → "
            f"resuming from step {new_step}"
        )
        _emit(
            "rollback",
            from_step=rolled_from,
            to_step=new_step,
            rollbacks=sentinel.rollbacks,
            bad_steps=win.bad_steps,
        )
        # every rollback leaves a black box: the per-step ring
        # around the divergence, not just the fact of it
        _black_box(
            "sentinel_rollback",
            from_step=rolled_from,
            to_step=new_step,
            rollbacks=sentinel.rollbacks,
        )
        prev_window_bad = False  # restored stream starts clean
        if source is not None:
            source.close()
        # a rollback checkpoint saved mid-override carries the same
        # override_epoch marker a crash restart would see — re-derive
        # the stripe from the journal instead of replaying its offsets
        rb_cursor, rb_override, rb_preconsumed = _apply_override_resume(
            cfg,
            run_dir,
            extra.get("data_cursor"),
            new_step,
            process_count=process_count,
            host_index=host_index,
            emit=_emit,
        )
        with _hw_expected("rollback-restart"):
            train_iter, source, cursor_log, shard_log = make_train_iterator(
                cfg, mesh, per_process, new_step,
                rb_cursor,
                num_labels=getattr(enc_cfg, "labels", None) or 1000,
                shard_override=rb_override,
                shard_preconsumed=rb_preconsumed,
            )
        return new_step

    @engine.on_eval
    def _eval_component(eng, step, state_now):
        nonlocal last_metrics
        if valid_factory is None:
            return None
        t0_eval = time.perf_counter()
        with _hw_expected("eval"), _rt_expected("eval"):
            val = evaluate(eval_step, state_now, valid_factory(), pad_batch)
        ledger.add("eval", time.perf_counter() - t0_eval)
        logger.log(val, step=step)
        last_metrics |= val
        return val

    def _emit_shard_cursor(step: int) -> None:
        # every host journals its consumed-shard ledger AT the
        # checkpointed step — the crash-safe, per-host cursor a future
        # resized resume merges (data/resize.py); no collective, so a
        # SIGKILL'd peer can't strand it
        shards = shard_log.get(step)
        if shards is not None:
            _emit("shard_cursor", step=step, world=process_count, **shards)

    @engine.on_checkpoint
    def _checkpoint_component(eng, cev):
        step = cev.step
        if cev.reason == "preemption":
            snap = _gather_data_cursor(cursor_log.get(step))
            with _hw_expected("checkpoint"), _rt_expected("checkpoint"), sp_ckpt:
                ckpt.save(
                    step,
                    eng.state,
                    extra={"data_cursor": snap} if snap is not None else None,
                )
            ledger.add("ckpt_save", sp_ckpt.last_s)
            _emit("checkpoint_save", step=step, preemption=True)
            _emit_shard_cursor(step)
            return
        snap = _gather_data_cursor(cursor_log.get(step))
        extra = {"data_cursor": snap} if snap is not None else None
        for k in [k for k in cursor_log if k <= step]:
            del cursor_log[k]
        with _hw_expected("checkpoint"), _rt_expected("checkpoint"), sp_ckpt:
            ckpt.save(step, eng.state, metrics=cev.metrics, extra=extra)
        cev.save_seconds = round(sp_ckpt.last_s, 3)
        ledger.add("ckpt_save", sp_ckpt.last_s)
        _emit(
            "checkpoint_save",
            step=step,
            eval_metrics=cev.metrics,
            save_seconds=cev.save_seconds,
        )
        # periodic cumulative attribution snapshot, one per committed
        # checkpoint — the offline stitcher keys lost work off these
        ledger.publish()
        _emit("goodput_report", **ledger.report(step=step))
        _emit_shard_cursor(step)
        for k in [k for k in shard_log if k <= step]:
            del shard_log[k]

    @engine.on_host_lost
    def _host_lost_component(eng, info):
        _emit("host_lost", step=eng.step, **info)
        _black_box("host_lost", step=eng.step, **info)

    @engine.on_crash
    def _crash_component(eng, exc):
        # the black box is most valuable exactly here: the run is dying and
        # the in-memory ring is about to vanish
        eng.exit_reason = (
            "diverged"
            if isinstance(exc, DivergenceError)
            else f"exception:{type(exc).__name__}"
        )
        if flightrec is not None:
            try:
                flightrec.dump(
                    "exception", extra={"error": f"{type(exc).__name__}: {exc}"}
                )
            except Exception:  # noqa: BLE001 - never mask the real failure
                pass

    @engine.on_shutdown
    def _drain_shutdown(eng, reason, step):
        # the watchdog stands down FIRST: a long final wait_until_finished
        # is a clean drain, not a hang. The drain itself runs on every
        # supervisor-visible exit path (SIGTERM preemption, host_lost,
        # crash) — an async Orbax save left in flight at process exit is
        # a torn step the next resume would have to walk back from.
        if hangwatch is not None:
            hangwatch.disarm()
            hangwatch.stop()
        try:
            ckpt.wait()
        except Exception as e:  # noqa: BLE001 - never mask the real failure
            print(f"[train] WARNING: checkpoint drain on shutdown failed: {e}")

    @engine.on_shutdown
    def _retrace_shutdown(eng, reason, step):
        if retrace_sentinel is not None:
            rsum = retrace_sentinel.summary()
            print(
                f"[train] retrace sentinel: {rsum['violations']} unexpected "
                f"recompile(s) after warmup "
                f"({rsum['compiles']} compiles seen, "
                f"{rsum['expected']} expected)"
            )
            retrace_sentinel.close()

    @engine.on_shutdown
    def _journal_shutdown(eng, reason, step):
        # final authoritative ledger word: covers the tail past the last
        # checkpoint and carries the exit reason
        ledger.publish()
        _emit("goodput_report", **ledger.report(step=step, reason=reason))
        _emit("shutdown", reason=reason, step=step)
        _beacon_write(step)  # final heartbeat: a clean exit is not a lost host
        if flightrec is not None:
            flightrec.uninstall()
        if journal is not None:
            journal.close()

    # continuous deployment (serve/publisher.py): gate-passing checkpoints
    # export int8/delta artifacts into the swap-watch dir the serving
    # tier polls; host 0 only (the export fetches the full tree to host)
    publisher = None
    if run.publish_dir and is_main:
        from jumbo_mae_tpu_tpu.serve.publisher import CheckpointPublisher

        publisher = CheckpointPublisher(
            run.publish_dir,
            quant=run.publish_quant,
            min_interval_steps=run.publish_min_interval_steps,
            full_every=run.publish_full_every,
            metric_key=run.publish_metric_key,
            metric_floor=run.publish_metric_floor,
            metric_sense=run.publish_metric_sense,
            emit=_emit,
        )
        publisher.register(engine)
        print(f"[publish] gated weights publisher -> {run.publish_dir}")

    try:
        with trace(run.profile_dir or None):
            engine.run(state)
    finally:
        state = engine.state

    ckpt.wait()
    ckpt.close()
    logger.close()
    if telemetry is not None:
        telemetry.close()
    if source is not None:
        source.close()
    # the exit reason rides the metrics dict so main() can map it onto the
    # supervisor exit-code protocol (host_lost -> EXIT_ELASTIC, ...)
    return {**last_metrics, "_exit_reason": engine.exit_reason}


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", type=str, default=None, help="YAML recipe path")
    parser.add_argument(
        "--set",
        dest="overrides",
        nargs="*",
        action="extend",
        default=[],
        help="dotted config overrides: optim.learning_rate=1e-3 "
        "(repeatable — `--set a=1 --set b=2` and `--set a=1 b=2` are "
        "equivalent; without extend, a repeated flag would silently "
        "drop the earlier overrides)",
    )
    parser.add_argument(
        "--distributed",
        action="store_true",
        help="call jax.distributed.initialize() (multi-host pods)",
    )
    parser.add_argument(
        "--coordinator",
        type=str,
        default=None,
        help="explicit coordinator address (host:port) for --distributed; "
        "needed off-TPU (e.g. the multi-process CPU fleet smoke) where "
        "auto-detection has no metadata server to ask",
    )
    parser.add_argument(
        "--num-processes",
        type=int,
        default=None,
        help="process count for --distributed with --coordinator",
    )
    parser.add_argument(
        "--process-id",
        type=int,
        default=None,
        help="this process's index for --distributed with --coordinator",
    )
    parser.add_argument(
        "--elastic",
        type=int,
        default=0,
        metavar="N",
        help="supervise N local training processes instead of training in "
        "this one: dead/wedged hosts trigger a budgeted relaunch from the "
        "last committed checkpoint at the surviving world size, with a "
        "rejoin back to N once the budget and timer allow "
        "(train/elastic.py; budgets under run.elastic_*)",
    )
    return parser


def _run_elastic(args) -> int:
    """``--elastic N``: run the :class:`ElasticSupervisor` over N child
    training processes on localhost. Each generation gets a fresh gloo
    coordinator port; every child is forced to ``run.resume=true`` so a
    relaunch continues from the last committed checkpoint (a fresh run
    simply finds no checkpoint). Returns the supervisor's exit code."""
    import socket
    import subprocess
    import sys

    from jumbo_mae_tpu_tpu.train.elastic import ElasticSupervisor

    cfg = load_config(args.config, args.overrides)
    run = cfg.run
    world = int(args.elastic)
    accum = max(1, run.grad_accum)

    def _world_ok(w: int) -> bool:
        # the child's own top-of-train validation: world * grad_accum must
        # divide the global batch size. The supervisor clamps any downsized
        # world through this, so a 4->3 resize can never relaunch children
        # that all die on the same config error until the budget is gone.
        return run.train_batch_size % (w * accum) == 0

    if not _world_ok(world):
        raise ValueError(
            f"--elastic {world} (x grad_accum {accum}) must divide "
            f"run.train_batch_size ({run.train_batch_size})"
        )
    run_dir = Path(run.output_dir) / run.name
    run_dir.mkdir(parents=True, exist_ok=True)
    # the supervisor shares host-0's journal DIRECTORY but owns a fresh
    # segment (RunJournal always opens max+1), so its role="supervisor"
    # rows interleave cleanly under read_merged_journal
    journal = RunJournal(run_dir / "journal") if run.journal else None

    def _free_port() -> int:
        s = socket.socket()
        try:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]
        finally:
            s.close()

    base = [sys.executable, "-m", "jumbo_mae_tpu_tpu.cli.train"]
    if args.config:
        base += ["--config", args.config]
    for ov in args.overrides or []:
        base += ["--set", ov]

    def launch(world_size: int, gen: int) -> list:
        port = _free_port()
        # children learn their generation from the environment (it is not
        # a config field): beacons, run_start events and the run_generation
        # gauge all stamp it, so merged journals distinguish pre- and
        # post-restart processes
        env = dict(os.environ, GRAFT_GENERATION=str(gen))
        procs = []
        for i in range(world_size):
            procs.append(
                subprocess.Popen(
                    base
                    + [
                        "--set",
                        "run.resume=true",
                        "--distributed",
                        "--coordinator",
                        f"127.0.0.1:{port}",
                        "--num-processes",
                        str(world_size),
                        "--process-id",
                        str(i),
                    ],
                    env=env,
                )
            )
        print(
            f"[elastic] generation {gen}: world={world_size} "
            f"on 127.0.0.1:{port} (pids {[p.pid for p in procs]})"
        )
        return procs

    sup = ElasticSupervisor(
        run_dir=run_dir,
        world_size=world,
        launch=launch,
        max_restarts=run.elastic_max_restarts,
        backoff_s=run.elastic_backoff_s,
        backoff_cap_s=run.elastic_backoff_cap_s,
        rejoin_after_s=run.elastic_rejoin_after_s,
        wedge_after_s=run.elastic_wedge_after_s,
        world_ok=_world_ok,
        journal=journal,
    )
    import signal

    def _stop(signum, frame):
        print(f"[elastic] caught signal {signum}: draining the fleet")
        sup.request_stop()

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _stop)
    try:
        rc = sup.run()
    finally:
        if journal is not None:
            journal.close()
    print(f"[elastic] supervisor exiting {rc}")
    return rc


def main(argv: list[str] | None = None):
    args = build_parser().parse_args(argv)
    if args.elastic:
        # the supervisor only starts children: it must never call into jax,
        # or it would hold the chip its children need
        raise SystemExit(_run_elastic(args))
    enable_compile_cache()
    if args.distributed:
        if os.environ.get("JAX_PLATFORMS", "") == "cpu":
            # multi-process CPU (the CI fleet smoke): cross-process
            # collectives need the gloo backend, and the flag must land
            # before the first backend touch or XLA raises "Multiprocess
            # computations aren't implemented on the CPU backend"
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        if args.coordinator:
            jax.distributed.initialize(
                coordinator_address=args.coordinator,
                num_processes=args.num_processes,
                process_id=args.process_id,
            )
        else:
            jax.distributed.initialize()
    cfg = load_config(args.config, args.overrides)
    try:
        metrics = train(cfg)
    except DivergenceError as e:
        # deterministic failure: exit EXIT_FATAL so a supervisor does not
        # burn its restart budget re-proving the divergence
        print(f"[train] FATAL: {e}")
        raise SystemExit(EXIT_FATAL)
    reason = "completed"
    if isinstance(metrics, dict):
        reason = str(metrics.pop("_exit_reason", "completed"))
    print("[train] done:", metrics)
    code = exit_code_for(reason)
    if code != EXIT_OK:
        print(f"[train] exit reason {reason!r} -> exit code {code}")
        raise SystemExit(code)


if __name__ == "__main__":
    main()
