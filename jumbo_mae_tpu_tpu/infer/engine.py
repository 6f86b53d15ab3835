"""The batched inference engine: shape-bucketed AOT executables.

Training drove the per-step roofline (PERF_ARCHIVE.md); this module is the serving
counterpart. The design moves every once-per-model cost out of the request
path:

- **Restore once.** The checkpoint is read a single time per process via
  :func:`~jumbo_mae_tpu_tpu.train.checkpoint.restore_inference_state`
  (params + BatchNorm stats only — the optimizer state's ~2x-params bytes
  are never read), then merged onto each task's serving module with the
  same overlap diagnostics the warm-start path prints.
- **Compile once per (task, bucket) — per HOST, not per process.** Request
  batches are padded up to a power-of-two bucket and run through an
  explicitly cached executable, lowered ahead-of-time with
  ``jax.jit(...).lower().compile()`` — the hot path never enters the jit
  tracing/cache machinery, and a compile can only happen where
  :meth:`InferenceEngine.warmup` or the first miss puts it.
  ``compile_counts`` / ``on_compile`` expose exactly when that was, and
  ``warm_hits`` counts the executables that were *loaded* instead: by
  default every compile is published to the persistent
  :class:`~jumbo_mae_tpu_tpu.infer.warmcache.WarmCache` and a restarted
  replica's warmup deserializes the ladder instead of recompiling it
  (``warm_cache=False`` opts out; the default root and its
  ``JUMBO_WARMCACHE=0`` switch are documented on
  ``utils/procenv.default_warmcache_dir``). Warmup runs the
  ladder from a small thread pool — XLA compiles release the GIL.
- **Weights can be int8.** ``quant="int8"`` quantizes each task's params
  tree (``infer/quant.py``: per-output-channel weight-only PTQ) and the
  jitted forward dequantizes on use — the executable's HBM-resident
  argument is the int8 tree, which halves the weight traffic that
  dominates small-batch serving. Parity is measured, not assumed
  (``quant.parity_report``); padding-inertness is preserved because
  dequantization is an exact per-weight ``q * scale``.
- **Padding is provably inert.** Every model op is row-independent in
  deterministic mode (per-token norms, within-sample attention, stored
  BatchNorm stats), so a padded row cannot perturb a valid row — the same
  ``valid``-mask convention the eval step uses, enforced bit-exactly by
  ``tests/test_infer_engine.py`` on the float32 path. The engine slices
  the valid rows out on the host; callers never see padding.

Three tasks cover the model zoo's heads:

- ``features`` — frozen-encoder embeddings (``pool`` ∈ cls/gap/tokens),
  the representation ``tools/extract_features.py`` / the kNN probe serve;
- ``logits``  — classification logits through the trained head
  (finetune or linear-probe checkpoints, BatchNorm stats grafted);
- ``reconstruct`` — MAE pixel reconstruction + mask (the demo-figure
  path), mask seed passed as a traced scalar so reseeding never recompiles.
  With ``encoder_cache=N`` the task splits into an encode executable
  (normalize → masked encoder → decoder projection) and a decode
  executable, with an N-entry LRU of encoder outputs keyed by
  (image bytes, seed) in between — repeated reconstructions of the same
  image run the deep encoder once and only the light decoder per request.

Single-process by design: serving replicas scale horizontally; the mesh
machinery stays in the training stack.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import threading
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from jumbo_mae_tpu_tpu.config import TrainConfig
from jumbo_mae_tpu_tpu.infer import packing
from jumbo_mae_tpu_tpu.infer import warmcache as wc
from jumbo_mae_tpu_tpu.infer.bucketing import ceil_pow2
from jumbo_mae_tpu_tpu.infer.quant import dequantize_tree, quantize_params
from jumbo_mae_tpu_tpu.obs import lockwatch
from jumbo_mae_tpu_tpu.obs.metrics import RATIO_BUCKETS, get_registry
from jumbo_mae_tpu_tpu.obs.perfmodel import detect_chip, roofline
from jumbo_mae_tpu_tpu.models import (
    DecoderConfig,
    JumboViT,
    MAEPretrainModel,
    pool_tokens,
    preset,
)
from jumbo_mae_tpu_tpu.ops.masking import unshuffle_with_mask_tokens
from jumbo_mae_tpu_tpu.ops.preprocess import normalize_images
from jumbo_mae_tpu_tpu.train.checkpoint import (
    _ENCODER_KEYS,
    merge_pretrained_params,
    require_loaded,
    restore_inference_state,
)
from jumbo_mae_tpu_tpu.utils.procenv import default_warmcache_dir

POOLS = ("cls", "gap", "tokens")

# bucket math lives in infer/bucketing.py (one definition, property-tested);
# re-exported here because this module was its historical home
from jumbo_mae_tpu_tpu.infer.bucketing import (  # noqa: E402,F401
    OversizedBatchError,
    bucket_for,
    pow2_rungs,
)


class ResolutionMismatchError(ValueError):
    """Input resolution differs from what the engine's image-bucket
    executables were compiled for. Typed (rather than a bare ValueError)
    so a scheduler/router can catch it and route the request to the
    token-packed path — which accepts any patch-aligned resolution —
    instead of failing the request. ``expected`` is the engine's native
    square size; ``got`` the offending (H, W)."""

    def __init__(self, expected: int, got: tuple[int, int]):
        self.expected = int(expected)
        self.got = (int(got[0]), int(got[1]))
        super().__init__(
            f"engine is compiled for {expected}px inputs, got "
            f"{got[0]}x{got[1]} — resize upstream or route to the "
            f"token-packed path (predict_packed)"
        )


def _to_state_dict(tree) -> dict:
    from flax import serialization

    return serialization.to_state_dict(tree)


# Encoder-once/decode-many split of MAEPretrainModel.__call__ (models/mae.py):
# the two halves, bound via ``apply(..., method=...)``, cover between them
# exactly the ops of the fused reconstruction forward — same modules, same
# order, same PRNG consumption — so the mask is bit-identical to the fused
# path and the reconstruction matches to fusion-level float tolerance.


@functools.partial(jax.jit, static_argnums=(0, 3))
def _init_variables(model, rngs, example, static_args: tuple = ()):
    """``model.init`` as one compiled program, not thousands of small ops
    dispatched (and compiled) one by one. The module is a static argument,
    so every engine of one architecture in a process — the replicas of a
    pool, the int8 twin — shares the compile."""
    return model.init(rngs, example, *static_args)


def _mae_encode(mdl, images, deterministic: bool = True):
    """normalize → masked encoder → decoder projection. Everything that
    depends only on (image, mask seed) — the cacheable prefix."""
    x = normalize_images(images, dtype=mdl.encoder_cfg.compute_dtype)
    tokens, mask, ids_restore = mdl.encoder(x, deterministic)
    return mdl.decoder_proj(tokens), mask, ids_restore


def _mae_decode(mdl, tokens, mask, ids_restore, deterministic: bool = True):
    """mask-token unshuffle → decoder stack → pixel head. Row-independent
    throughout (per-token norms, within-sample attention, per-sample
    gather), so zero-padded rows stay provably inert — the same contract
    the fused executable has."""
    k = mdl.encoder_cfg.num_cls_tokens
    cls, visible = tokens[:, :k, :], tokens[:, k:, :]
    full = unshuffle_with_mask_tokens(visible, mdl.mask_token, ids_restore)
    decoded = mdl.decoder(jnp.concatenate([cls, full], axis=1), deterministic)
    pred = mdl.pixel_proj(decoded[:, k:, :].astype(jnp.float32))
    return {"reconstruction": pred, "mask": mask}


class InferenceEngine:
    """Restore a checkpoint once; serve bucket-batched forwards forever.

    ``cfg`` is the training recipe (`TrainConfig`) whose model section
    defines the encoder/decoder; ``ckpt`` any
    :func:`restore_inference_state` carrier (omit for random init —
    benchmarking only, a loaded checkpoint is enforced through the same
    ``require_loaded`` guard the export tools use).

    ``dtype`` overrides the serving compute dtype (default: the recipe's
    encoder dtype — bf16 on the chip; pass ``"float32"`` for the exact
    path). ``max_batch`` caps the largest bucket; requests larger than it
    are chunked. All public predict methods are thread-safe (compiles are
    serialized behind per-executable locks; dispatches run concurrently).

    ``quant="int8"`` serves the weight-only-quantized forward
    (``infer/quant.py`` — measure parity with ``quant.parity_report``
    before rollout). ``warm_cache`` controls the persistent executable
    cache: ``True`` (default) resolves via
    ``procenv.default_warmcache_dir()`` (env-disableable), a path uses that
    directory unconditionally, ``False``/``None`` disables.
    ``encoder_cache=N`` keeps an N-entry LRU of reconstruction encoder
    outputs so repeated reconstructions of one image pay the encoder once.
    """

    def __init__(
        self,
        cfg: TrainConfig,
        *,
        ckpt: str = "",
        dtype: str | None = None,
        max_batch: int = 64,
        max_tokens: int = 4096,
        labels: int | None = None,
        batch_norm: bool | None = None,
        quant: str | None = None,
        warm_cache: str | os.PathLike | bool | None = True,
        encoder_cache: int = 0,
        encoder_cache_bytes: int = 0,
        on_compile: Callable[[str, int], None] | None = None,
        registry=None,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if quant not in (None, "int8"):
            raise ValueError(f"quant must be None or 'int8', got {quant!r}")
        # telemetry handles resolved once (obs/metrics.py): the hot path only
        # ever pays a counter inc / histogram observe, and a NullRegistry
        # default turns every site into a no-op with no branches here
        reg = registry if registry is not None else get_registry()
        self._m_predict = reg.histogram(
            "infer_predict_seconds",
            "engine predict() wall time per batched call",
            labels=("task",),
        )
        self._m_images = reg.counter(
            "infer_images_total", "images served", labels=("task",)
        )
        self._m_hits = reg.counter(
            "infer_bucket_cache_hits_total",
            "bucket-executable cache hits",
            labels=("task",),
        )
        self._m_misses = reg.counter(
            "infer_bucket_cache_misses_total",
            "bucket-executable cache misses (each one is a compile)",
            labels=("task",),
        )
        self._m_compile = reg.histogram(
            "infer_compile_seconds",
            "AOT lower+compile time per (task, bucket) executable",
            labels=("task",),
        )
        self._m_pad = reg.histogram(
            "infer_pad_fraction",
            "padding rows / bucket size per dispatched chunk",
            buckets=RATIO_BUCKETS,
        )
        self._m_warm_start = reg.gauge(
            "infer_warm_start_seconds",
            "wall time of the last warmup() ladder (compiles + cache loads)",
        )
        self._m_enc_cache = reg.counter(
            "infer_encoder_cache_events_total",
            "reconstruction encoder-output LRU events",
            labels=("event",),
        )
        self._m_enc_cache_bytes = reg.gauge(
            "infer_encoder_cache_bytes",
            "resident bytes of cached encoder-output rows (tokens+mask+ids)",
        )
        self._m_quant = reg.gauge(
            "infer_quant_compression",
            "params bytes_before / bytes_after per quantized task",
            labels=("task",),
        )
        self._m_bucket_compile = reg.gauge(
            "infer_bucket_compile_seconds",
            "lower+compile wall time of each (task, bucket) executable",
            labels=("task", "bucket"),
        )
        self._m_exec_bytes = reg.gauge(
            "infer_executable_bytes",
            "serialized executable size per (task, bucket)",
            labels=("task", "bucket"),
        )
        self._m_warm_saved = reg.counter(
            "infer_warmcache_saved_seconds_total",
            "compile seconds avoided by warmcache hits (from entry metadata)",
            labels=("task",),
        )
        self._m_pred_s = reg.gauge(
            "perf_predicted_step_seconds",
            "roofline-predicted execution seconds",
            labels=("program",),
        )
        self._m_drift = reg.gauge(
            "perf_predict_vs_measured",
            "measured / roofline-predicted execution time",
            labels=("program",),
        )
        # token-packed serving observability (see predict_packed)
        self._m_pack_pad = reg.histogram(
            "serve_pack_token_pad_fraction",
            "padding tokens / device tokens per packed dispatch "
            "(row bucketing included)",
            buckets=RATIO_BUCKETS,
        )
        self._m_pack_segments = reg.histogram(
            "serve_pack_segments_per_dispatch",
            "request segments packed into one dispatch",
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0),
        )
        self._m_pack_occ = reg.histogram(
            "serve_pack_budget_occupancy",
            "occupied tokens / (rows x token budget) per packed dispatch",
            buckets=RATIO_BUCKETS,
        )
        self._m_pack_dispatches = reg.counter(
            "serve_pack_dispatches_total",
            "token-packed dispatches served",
            labels=("task",),
        )
        self._m_pack_parity = reg.gauge(
            "serve_pack_parity_min",
            "min packed-vs-unpacked feature cosine of the last parity gate",
        )
        self._m_pack_parity_fail = reg.counter(
            "serve_pack_parity_failures_total",
            "packed-parity gate failures (cosine or top-1 below threshold)",
        )
        self._registry = reg
        # resolved here, not inside the best-effort cost publication: an
        # accelerator that is not in the spec tables is an error
        self._chip = detect_chip()
        self.cfg = cfg
        self.max_batch = int(max_batch)
        # packed-path token budget ceiling: the rung ladder tops out here
        # (4096 covers 896px/patch16 = 3136 patch tokens + CLS)
        self.max_tokens = int(max_tokens)
        self.on_compile = on_compile
        m = cfg.model
        overrides = dict(m.overrides)
        if dtype is not None:
            overrides["dtype"] = dtype
        # serving is always deterministic — stochastic knobs forced off,
        # LAST, so recipe overrides can't re-enable them. grad_ckpt too:
        # there are no gradients to checkpoint for, and the packed forward
        # passes a traced pytree positionally past the remat wrapper's
        # static deterministic flag.
        self._enc = preset(
            m.preset,
            **{
                **overrides,
                "labels": None,
                "mask_ratio": None,
                "dropout": 0.0,
                "droppath": 0.0,
                "grad_ckpt": False,
            },
        )
        self._labels = labels if labels is not None else overrides.get("labels")
        self._batch_norm = (
            batch_norm if batch_norm is not None else cfg.run.mode == "linear"
        )
        self._dec = DecoderConfig(
            **{
                "layers": m.dec_layers,
                "dim": m.dec_dim,
                "heads": m.dec_heads,
                "dtype": m.dec_overrides.get("dtype", m.dec_dtype)
                if dtype is None
                else dtype,
                **{
                    k: v
                    for k, v in m.dec_overrides.items()
                    if k not in ("dtype", "dropout", "droppath")
                },
            }
        )
        self.image_size = self._enc.image_size

        self._ckpt = str(ckpt)
        self._ckpt_tree: dict | None = None
        self._ckpt_stats: dict | None = None
        if self._ckpt:
            from jumbo_mae_tpu_tpu.serve.publisher import is_publish_artifact

            if is_publish_artifact(self._ckpt):
                # a published train→serve artifact (serve/publisher.py):
                # verify the manifest, resolve its delta chain to a full
                # host tree — a pool can cold-start straight from the
                # newest publish and absorb later ones via hot-swap
                from jumbo_mae_tpu_tpu.serve.publisher import resolve_chain

                tree, stats = resolve_chain(self._ckpt)[:2]
            else:
                # to_device: leaves land on device one at a time, host
                # buffers dropped as they go — replica-density restore
                # (peak one tree, not host + device copies of a full model)
                tree, stats = restore_inference_state(
                    self._ckpt, to_device=True
                )
            self._ckpt_tree = _to_state_dict(tree)
            self._ckpt_stats = (
                _to_state_dict(stats) if stats is not None else None
            )

        self.quant = quant
        if encoder_cache and self._enc.mask_mode != "shared":
            # per-sample masking draws (batch, length) noise: a row's mask
            # depends on its batch position, so a cached encoder output
            # would silently change results across batch compositions.
            # Shared mode draws (length,) noise — position-independent.
            raise ValueError(
                "encoder_cache requires mask_mode='shared' (per-sample "
                "masks are batch-position-dependent and cannot be cached "
                "per image)"
            )
        self._enc_cache_size = int(encoder_cache)
        # optional byte bound on top of the entry bound: whichever trips
        # first evicts. 0 = entries-only (historical behaviour). Only
        # meaningful when encoder_cache > 0 enables the cache at all.
        self._enc_cache_bytes_cap = int(encoder_cache_bytes)
        self._enc_cache_nbytes = 0
        self._enc_cache: OrderedDict[str, tuple] = OrderedDict()
        self._enc_cache_lock = lockwatch.lock("engine.enc_cache")
        self.encoder_cache_hits = 0
        self.encoder_cache_misses = 0

        if warm_cache is True:
            wc_root = default_warmcache_dir()
        elif warm_cache:
            wc_root = str(warm_cache)
        else:
            wc_root = None
        self.warmcache = (
            wc.WarmCache(wc_root, registry=reg) if wc_root else None
        )
        # executables loaded from the warmcache instead of compiled —
        # deliberately NOT folded into compile_counts: "restart performs
        # zero compiles" is asserted against compile_counts staying flat
        self.warm_hits: dict[tuple[str, int], int] = {}
        self._fingerprint = self._model_fingerprint()

        self.load_stats: dict[str, dict] = {}
        self._tasks: dict[str, dict] = {}  # task -> {model, variables, ...}
        self._exec: dict[tuple[str, int], Any] = {}
        # serialized size per resident executable (where known) — summed by
        # executable_cache_bytes() for the memory accountant
        self._exec_nbytes: dict[tuple[str, int], int] = {}
        self.compile_counts: dict[tuple[str, int], int] = {}
        # XLA cost analysis per (task_key, bucket) + its roofline-predicted
        # execution seconds — filled at compile/warm-load time, read by the
        # per-dispatch drift gauge and bench_infer's ledger row
        self.cost_reports: dict[tuple[str, int], Any] = {}
        self._pred_s: dict[tuple[str, int], float] = {}
        self._lock = lockwatch.lock("engine.master")
        # one lock per (task, bucket): warmup threads compile distinct
        # executables concurrently (XLA releases the GIL) while two racers
        # for the SAME key still serialize
        self._key_locks: dict[tuple[str, int], threading.Lock] = {}
        # per-thread breakdown of the most recent predict on that thread
        # (compute/fetch split, bucket, pad rows) — read back by
        # last_breakdown() for request tracing. Thread-local because
        # predicts run concurrently; a shared dict would interleave.
        self._tls = threading.local()

    # ---------------------------------------------------------------- tasks

    def _graft(self, task: str, init_params, *, subtree: str, whole: bool):
        """Merge the restored checkpoint tree onto a task's fresh init.
        ``whole=True`` merges the full tree (reconstruct needs the decoder);
        otherwise the checkpoint's encoder subtree (``encoder`` for
        pretrain trees, ``model`` for classification trees, else the bare
        root) lands on ``subtree`` of the init."""
        if self._ckpt_tree is None:
            return init_params
        from flax import serialization

        init_sd = _to_state_dict(init_params)
        stats: dict = {}
        if whole:
            merged = merge_pretrained_params(
                self._ckpt_tree, init_sd, stats=stats
            )
        else:
            src_key = next(
                (k for k in _ENCODER_KEYS if k in self._ckpt_tree), None
            )
            src = self._ckpt_tree[src_key] if src_key else self._ckpt_tree
            dst = init_sd[subtree] if subtree else init_sd
            sub_merged = merge_pretrained_params(src, dst, stats=stats)
            merged = (
                {**init_sd, subtree: sub_merged} if subtree else sub_merged
            )
        require_loaded(stats, self._ckpt, f"the {task} serving model")
        self.load_stats[task] = stats
        return serialization.from_state_dict(init_params, merged)

    def _finish_task(self, task: str, t: dict) -> dict:
        """Shared tail of task construction: weight-only quantization of
        the params subtree (BatchNorm statistics stay f32 — they are not
        matmul weights and the executable takes them as arguments, never
        as baked-in constants, so warmcache entries stay checkpoint-
        independent)."""
        if self.quant == "int8":
            qtree, report = quantize_params(t["variables"]["params"])
            t["variables"] = {**t["variables"], "params": qtree}
            t["quant_report"] = report
            self._m_quant.labels(task).set(report["compression"])
        return t

    def _build_task(self, task: str) -> dict:
        size = self.image_size
        example = jnp.zeros((1, size, size, 3), jnp.uint8)
        rngs = {"params": jax.random.key(self.cfg.run.init_seed)}
        if task == "features":
            model = JumboViT(self._enc)
            variables = _init_variables(
                model,
                rngs,
                normalize_images(example, dtype=self._enc.compute_dtype),
                (True,),
            )
            params = self._graft(task, variables["params"], subtree="", whole=False)
            return self._finish_task(
                task, {"model": model, "variables": {"params": params}}
            )
        if task == "logits":
            if not self._labels:
                raise ValueError(
                    "the logits task needs a label count — set "
                    "model.overrides.labels in the recipe or pass labels="
                )
            enc = self._enc.replace(
                labels=int(self._labels), batch_norm=self._batch_norm
            )
            model = JumboViT(enc)
            variables = _init_variables(
                model,
                rngs,
                normalize_images(example, dtype=enc.compute_dtype),
                (True,),
            )
            params = self._graft(task, variables["params"], subtree="", whole=False)
            batch_stats = variables.get("batch_stats")
            if batch_stats is not None and self._ckpt_stats is not None:
                from flax import serialization

                saved = self._ckpt_stats
                # classification trees keep the head's stats under "model"
                saved = saved.get("model", saved)
                batch_stats = serialization.from_state_dict(batch_stats, saved)
            v = {"params": params}
            if batch_stats is not None:
                v["batch_stats"] = batch_stats
            return self._finish_task(task, {"model": model, "variables": v})
        if task == "reconstruct":
            enc = self._enc.replace(
                mask_ratio=self.cfg.model.overrides.get("mask_ratio", 0.75)
            )
            model = MAEPretrainModel(
                enc, self._dec, norm_pix_loss=self.cfg.model.norm_pix_loss
            )
            variables = _init_variables(
                model, {**rngs, "noise": jax.random.key(0)}, example
            )
            params = self._graft(task, variables["params"], subtree="", whole=True)
            return self._finish_task(
                task,
                {
                    "model": model,
                    "variables": {"params": params},
                    "enc_cfg": enc,
                },
            )
        raise ValueError(f"unknown task {task!r}")

    def _task(self, task: str) -> dict:
        t = self._tasks.get(task)
        if t is None:
            with self._lock:
                t = self._tasks.get(task)
                if t is None:
                    t = self._build_task(task)
                    self._tasks[task] = t
        return t

    # ------------------------------------------------------------ hot swap

    def swap_weights(self, params, batch_stats=None, *, ckpt: str = "") -> dict:
        """Replace the live weights with a newly restored tree — zero
        compiles. Params/batch_stats are executable *arguments*, so every
        cached AOT executable serves the new weights unchanged; only the
        task variable trees are rebuilt (fresh init + graft + quant).

        Returns an opaque snapshot of the previous weights for
        :meth:`restore_snapshot` — the double buffer a hot-swap rollback
        needs. Raises (leaving the previous weights live) when the new tree
        does not graft onto this architecture; the swap controller treats
        that as a failed swap. In-flight predicts are per-request atomic:
        each dispatch reads one task dict, so a request serves entirely old
        or entirely new weights, never a mix.
        """
        new_tree = _to_state_dict(params)
        new_stats = (
            _to_state_dict(batch_stats) if batch_stats is not None else None
        )
        with self._lock:
            snap = {
                "ckpt": self._ckpt,
                "tree": self._ckpt_tree,
                "stats": self._ckpt_stats,
                "tasks": dict(self._tasks),
            }
            built = sorted(self._tasks)
            self._ckpt = str(ckpt)
            self._ckpt_tree = new_tree
            self._ckpt_stats = new_stats
        try:
            rebuilt = {task: self._build_task(task) for task in built}
        except BaseException:
            with self._lock:
                self._ckpt = snap["ckpt"]
                self._ckpt_tree = snap["tree"]
                self._ckpt_stats = snap["stats"]
            raise
        with self._lock:
            self._tasks.update(rebuilt)
        with self._enc_cache_lock:
            # cached encoder outputs are weight-dependent
            self._enc_cache.clear()
            self._enc_cache_nbytes = 0
            self._m_enc_cache_bytes.set(0)
        return snap

    def restore_snapshot(self, snap: dict) -> None:
        """Reinstate a :meth:`swap_weights` snapshot (rollback). Tasks
        first built *after* the swap are dropped so they lazily rebuild
        from the restored tree instead of keeping the rolled-back weights."""
        with self._lock:
            self._ckpt = snap["ckpt"]
            self._ckpt_tree = snap["tree"]
            self._ckpt_stats = snap["stats"]
            for task in list(self._tasks):
                if task in snap["tasks"]:
                    self._tasks[task] = snap["tasks"][task]
                else:
                    del self._tasks[task]
        with self._enc_cache_lock:
            self._enc_cache.clear()
            self._enc_cache_nbytes = 0
            self._m_enc_cache_bytes.set(0)

    # ---------------------------------------------------- executable cache

    def _task_key(self, task: str, pool: str | None) -> str:
        return f"{task}:{pool}" if pool else task

    @staticmethod
    def _base_task(task: str) -> str:
        """'reconstruct.enc' / 'reconstruct.dec' share the 'reconstruct'
        task state (model + grafted variables); everything else is 1:1."""
        return task.split(".", 1)[0]

    def _model_fingerprint(self) -> str:
        """Everything the traced serving programs depend on besides their
        runtime arguments. Params and BatchNorm stats are arguments, so
        checkpoints of one architecture share warmcache entries; jax/jaxlib
        versions are included because PjRt serialization is not stable
        across them, and the device: its kind on an accelerator, the host
        CPU's identity on the CPU backend (XLA:CPU executables embed machine
        features)."""
        import jaxlib

        def cfg_dict(c):
            return dataclasses.asdict(c) if dataclasses.is_dataclass(c) else str(c)

        return wc.fingerprint(
            {
                "enc": cfg_dict(self._enc),
                "dec": cfg_dict(self._dec),
                "labels": self._labels,
                "batch_norm": self._batch_norm,
                "norm_pix_loss": self.cfg.model.norm_pix_loss,
                "mask_ratio": self.cfg.model.overrides.get("mask_ratio", 0.75),
                "image_size": self.image_size,
                "jax": jax.__version__,
                "jaxlib": jaxlib.__version__,
                "backend": jax.default_backend(),
                "device": (
                    wc.host_fingerprint()
                    if jax.default_backend() == "cpu"
                    else jax.devices()[0].device_kind
                ),
            }
        )

    def _entry_name(self, task_key: str, bucket: int) -> str:
        return wc.entry_name(
            self._fingerprint, task_key, bucket, str(self._enc.dtype), self.quant
        )

    def _task_cfg(self, base: str):
        """The encoder config a base task's model was built with — what the
        packed path's per-resolution variants must replicate (same params
        tree, different image_size)."""
        if base == "logits":
            return self._enc.replace(
                labels=int(self._labels), batch_norm=self._batch_norm
            )
        return self._enc

    @staticmethod
    def _packed_dims(task: str) -> tuple[int, int]:
        """Parse (rows, max_segments) out of a packed task key
        (``<base>.packed:<pool>@r<rows>s<smax>``)."""
        spec = task.rsplit("@", 1)[1]
        r, s = spec[1:].split("s", 1)
        return int(r), int(s)

    def _fn(self, task: str, pool: str | None):
        t = self._task(self._base_task(task))
        model = t["model"]
        quantized = self.quant is not None

        def prep(variables):
            # dequant-on-use: the executable's argument stays int8; the f32
            # view is an on-chip intermediate fused into the consumers
            return dequantize_tree(variables) if quantized else variables

        if ".embed@" in task:
            # per-resolution patch embedding: the packed pipeline's stage 1.
            # Same variables tree as the base task — only the (traced)
            # image_size differs, and with sincos2d posemb the params are
            # resolution-independent, so the graft/quant state is shared.
            res = int(task.rsplit("@", 1)[1])
            model_r = JumboViT(
                self._task_cfg(self._base_task(task)).replace(image_size=res)
            )

            def fn(variables, images):
                v = prep(variables)
                x = normalize_images(images, dtype=self._enc.compute_dtype)
                toks = model_r.apply(
                    {"params": v["params"]}, x, method=JumboViT.patchify
                )
                return toks.astype(jnp.float32)

            return fn
        if ".full:" in task:
            # unpacked full forward at an arbitrary resolution — the packed
            # path's per-request parity oracle (same output contract as
            # serve_packed: {"pooled", "logits"?})
            pool_name = task.split(".full:", 1)[1].rsplit("@", 1)[0]
            res = int(task.rsplit("@", 1)[1])
            model_r = JumboViT(
                self._task_cfg(self._base_task(task)).replace(image_size=res)
            )

            def fn(variables, images):
                v = prep(variables)
                x = normalize_images(images, dtype=self._enc.compute_dtype)
                return model_r.apply(
                    v, x, True, pooling=pool_name, method=JumboViT.serve_full
                )

            return fn
        if ".packed:" in task:
            # token-packed forward: consumes pre-embedded token segments,
            # so one executable serves every resolution in the mix (and
            # both features + logits when the base task has a head)
            pool_name = task.split(".packed:", 1)[1].rsplit("@", 1)[0]

            def fn(variables, tokens, seg, cls_pos, cls_index):
                v = prep(variables)
                return model.apply(
                    v,
                    tokens,
                    seg,
                    cls_pos,
                    cls_index,
                    True,
                    pooling=pool_name,
                    method=JumboViT.serve_packed,
                )

            return fn
        if task == "features":
            k = self._enc.num_cls_tokens

            def fn(variables, images):
                v = prep(variables)
                x = normalize_images(images, dtype=self._enc.compute_dtype)
                tokens = model.apply({"params": v["params"]}, x, True)
                out = (
                    tokens if pool == "tokens" else pool_tokens(tokens, k, pool)
                )
                return out.astype(jnp.float32)

            return fn
        if task == "logits":

            def fn(variables, images):
                x = normalize_images(images, dtype=self._enc.compute_dtype)
                return model.apply(prep(variables), x, True).astype(jnp.float32)

            return fn
        if task == "reconstruct.enc":

            def fn(variables, images, seed):
                v = prep(variables)
                tokens, mask, ids = model.apply(
                    {"params": v["params"]},
                    images,
                    True,
                    method=_mae_encode,
                    rngs={"noise": jax.random.key(seed)},
                )
                if ids.ndim == 1:
                    # shared-mode ids_restore is one permutation for the
                    # whole batch; materialize it per row so cached rows
                    # are self-contained (the 2-D decode gather is exact)
                    ids = jnp.broadcast_to(ids, (images.shape[0], ids.shape[0]))
                return tokens, mask.astype(jnp.float32), ids.astype(jnp.int32)

            return fn
        if task == "reconstruct.dec":

            def fn(variables, tokens, mask, ids):
                v = prep(variables)
                out = model.apply(
                    {"params": v["params"]},
                    tokens,
                    mask,
                    ids,
                    True,
                    method=_mae_decode,
                )
                return {
                    "reconstruction": out["reconstruction"].astype(jnp.float32),
                    "mask": out["mask"].astype(jnp.float32),
                }

            return fn

        def fn(variables, images, seed):
            v = prep(variables)
            out = model.apply(
                {"params": v["params"]},
                images,
                True,
                True,
                rngs={"noise": jax.random.key(seed)},
            )
            return {
                "reconstruction": out["reconstruction"].astype(jnp.float32),
                "mask": out["mask"].astype(jnp.float32),
            }

        return fn

    def _abstract_args(self, task: str, bucket: int, t: dict) -> list:
        """Lowering arguments for one executable: the task's (possibly
        quantized) variables tree plus shape-only stand-ins for the data."""
        size = self.image_size
        if ".embed@" in task or ".full:" in task:
            res = int(task.rsplit("@", 1)[1])
            return [
                t["variables"],
                jax.ShapeDtypeStruct((bucket, res, res, 3), jnp.uint8),
            ]
        if ".packed:" in task:
            # packed executables key rows/segment-slots into the task name;
            # ``bucket`` is the token budget
            rows, smax = self._packed_dims(task)
            k = self._enc.num_cls_tokens
            return [
                t["variables"],
                jax.ShapeDtypeStruct((rows, bucket, self._enc.dim), jnp.float32),
                jax.ShapeDtypeStruct((rows, bucket), jnp.int32),
                jax.ShapeDtypeStruct((rows, bucket), jnp.int32),
                jax.ShapeDtypeStruct((rows, smax, k), jnp.int32),
            ]
        if task == "reconstruct.dec":
            enc = t["enc_cfg"]
            seq = enc.num_cls_tokens + enc.keep_len
            return [
                t["variables"],
                jax.ShapeDtypeStruct(
                    (bucket, seq, self._dec.dim), self._dec.compute_dtype
                ),
                jax.ShapeDtypeStruct((bucket, enc.num_patches), jnp.float32),
                jax.ShapeDtypeStruct((bucket, enc.num_patches), jnp.int32),
            ]
        args = [
            t["variables"],
            jax.ShapeDtypeStruct((bucket, size, size, 3), jnp.uint8),
        ]
        if task in ("reconstruct", "reconstruct.enc"):
            args.append(jax.ShapeDtypeStruct((), jnp.int32))
        return args

    def _compile_lock(self, key: tuple[str, int]) -> threading.Lock:
        with self._lock:
            lk = self._key_locks.get(key)
            if lk is None:
                lk = self._key_locks[key] = lockwatch.lock(
                    f"engine.compile[{key[0]}/{key[1]}]"
                )
            return lk

    def _executable(self, task: str, pool: str | None, bucket: int):
        key = (self._task_key(task, pool), bucket)
        ex = self._exec.get(key)
        if ex is not None:
            self._m_hits.labels(key[0]).inc()
            return ex
        # build the task OUTSIDE any compile lock: _task takes the master
        # lock on first build, so calling it under a held lock deadlocks
        # when the compile is the first touch (warmup-first)
        t = self._task(self._base_task(task))
        with self._compile_lock(key):
            ex = self._exec.get(key)
            if ex is not None:
                self._m_hits.labels(key[0]).inc()
                return ex
            if self.warmcache is not None:
                name = self._entry_name(key[0], bucket)
                ex = self.warmcache.get(name)
                if ex is not None:
                    # a warm-start load, not a compile: compile_counts must
                    # stay flat so "restart performs zero compiles" is a
                    # checkable invariant, and miss keeps meaning compile
                    self._exec[key] = ex
                    self.warm_hits[key] = self.warm_hits.get(key, 0) + 1
                    self._publish_cost(key, ex)
                    meta = self.warmcache.entry_meta(name)
                    if meta:
                        # quantify what the hit was worth: the compile
                        # seconds the first process paid for this entry
                        saved = float(meta.get("compile_seconds") or 0.0)
                        if saved > 0:
                            self._m_warm_saved.labels(key[0]).inc(saved)
                        size = float(meta.get("executable_bytes") or 0.0)
                        if size > 0:
                            self._m_exec_bytes.labels(*map(str, key)).set(size)
                            self._exec_nbytes[key] = int(size)
                    return ex
            self._m_misses.labels(key[0]).inc()
            t_compile = time.perf_counter()
            # donate the request buffers: their HBM is recycled for
            # intermediates the moment the first op reads them (no-op on
            # CPU, where jax would warn per program)
            if jax.default_backend() == "cpu":
                donate: tuple[int, ...] = ()
            elif task == "reconstruct.dec":
                donate = (1, 2, 3)
            else:
                donate = (1,)
            ex = (
                jax.jit(self._fn(task, pool), donate_argnums=donate)
                .lower(*self._abstract_args(task, bucket, t))
                .compile()
            )
            self._exec[key] = ex
            self.compile_counts[key] = self.compile_counts.get(key, 0) + 1
            compile_s = time.perf_counter() - t_compile
            self._m_compile.labels(key[0]).observe(compile_s)
            self._m_bucket_compile.labels(*map(str, key)).set(compile_s)
            if self.on_compile is not None:
                self.on_compile(key[0], bucket)
            cost = self._publish_cost(key, ex)
            if self.warmcache is not None:
                meta = {"compile_seconds": round(compile_s, 4)}
                if cost is not None:
                    from jumbo_mae_tpu_tpu.obs.costmodel import cost_asdict

                    meta["cost"] = cost_asdict(cost)
                size = self.warmcache.put(
                    self._entry_name(key[0], bucket), ex, meta=meta
                )
                if size:
                    self._m_exec_bytes.labels(*map(str, key)).set(size)
                    self._exec_nbytes[key] = int(size)
            return ex

    def _publish_cost(self, key: tuple[str, int], ex):
        """Extract + publish XLA's cost analysis for one executable (at
        compile or warm-load time, never per dispatch) and precompute its
        roofline prediction for the drift gauge. Best-effort throughout."""
        try:
            from jumbo_mae_tpu_tpu.obs.costmodel import extract_cost, publish_cost

            cost = extract_cost(ex, key[0])
            if cost is None:
                return None
            dtype = str(self._enc.dtype) + (f"+{self.quant}" if self.quant else "")
            publish_cost(
                cost, bucket=str(key[1]), dtype=dtype, registry=self._registry
            )
            self.cost_reports[key] = cost
            pred = roofline(
                cost.flops,
                cost.bytes_accessed,
                self._chip,
                batch=key[1],
                peak_hbm_bytes=cost.peak_bytes,
            )
            self._pred_s[key] = pred.step_time_s
            self._m_pred_s.labels(f"{key[0]}/b{key[1]}").set(pred.step_time_s)
            return cost
        except Exception:  # noqa: BLE001 — observability must not fail serving
            return None

    def warmup(
        self,
        tasks: tuple[str, ...] = ("features",),
        *,
        pool: str = "cls",
        buckets: tuple[int, ...] | None = None,
        workers: int | None = None,
    ) -> int:
        """Pre-build every (task, bucket) executable the workload will hit
        — afterwards the request path never compiles (asserted by the
        bench's zero-recompiles-after-warmup report). Default buckets:
        every power of two up to ``max_batch``, plus ``max_batch`` itself
        when it is not one. Returns the number of executables *compiled* —
        warmcache loads are free and counted in ``warm_hits`` instead.

        The ladder runs on a small thread pool (XLA compiles release the
        GIL; per-executable locks keep same-key racers serialized), each
        compile's wall time observed into ``infer_compile_seconds`` and the
        whole ladder into ``infer_warm_start_seconds``."""
        if buckets is None:
            buckets = tuple(
                b for b in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
                if b <= self.max_batch
            )
            if self.max_batch not in buckets:
                buckets += (self.max_batch,)
        else:
            bad = [b for b in buckets if b > self.max_batch]
            if bad:
                raise OversizedBatchError(
                    f"warmup buckets {bad} exceed max_batch={self.max_batch}"
                )
        jobs: list[tuple[str, str | None, int]] = []
        for task in tasks:
            p = pool if task == "features" else None
            execs = (
                ("reconstruct.enc", "reconstruct.dec")
                if task == "reconstruct" and self._enc_cache_size > 0
                else (task,)
            )
            for name in execs:
                jobs.extend((name, p, b) for b in buckets)
        before = sum(self.compile_counts.values())
        t0 = time.perf_counter()
        if workers is None:
            workers = min(4, len(jobs))
        if workers > 1 and len(jobs) > 1:
            with ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="warmup"
            ) as px:
                list(px.map(lambda j: self._executable(*j), jobs))
        else:
            for j in jobs:
                self._executable(*j)
        self._m_warm_start.set(time.perf_counter() - t0)
        return sum(self.compile_counts.values()) - before

    # -------------------------------------------------------------- predict

    def _dispatch(self, task: str, pool: str | None, bucket: int, args, n: int):
        """Run one padded bucket through its executable; slice valid rows
        and fold the compute/fetch split into the thread-local breakdown."""
        t = self._task(self._base_task(task))
        t_compute = time.perf_counter()
        out = self._executable(task, pool, bucket)(t["variables"], *args)
        # block here so compute vs fetch split cleanly: dispatch+execution
        # ends at block_until_ready; what follows is device→host copy
        jax.block_until_ready(out)
        t_fetch = time.perf_counter()
        out = jax.tree_util.tree_map(lambda a: np.asarray(a)[:n], out)
        bd = self._tls.bd
        bd["compute_s"] += t_fetch - t_compute
        bd["fetch_s"] += time.perf_counter() - t_fetch
        bd["bucket"] = max(bd["bucket"], bucket)
        bd["pad_rows"] += bucket - n
        bd["bucket_rows"] += bucket
        # predicted-vs-measured drift: prediction precomputed at compile
        # time, so the hot path pays one dict lookup + one gauge set
        pred = self._pred_s.get((self._task_key(task, pool), bucket))
        if pred:
            self._m_drift.labels(f"{self._task_key(task, pool)}/b{bucket}").set(
                (t_fetch - t_compute) / pred
            )
        return out

    @staticmethod
    def _pad_rows(arr: np.ndarray, bucket: int) -> np.ndarray:
        n = arr.shape[0]
        if n == bucket:
            return arr
        pad = np.zeros((bucket - n, *arr.shape[1:]), arr.dtype)
        return np.concatenate([arr, pad])

    def _run(self, task: str, pool: str | None, images: np.ndarray, extra=()):
        """Bucket-pad one image chunk (len <= max_batch), run, slice."""
        n = images.shape[0]
        bucket = bucket_for(n, self.max_batch)
        self._m_pad.observe((bucket - n) / bucket)
        return self._dispatch(
            task, pool, bucket, (self._pad_rows(images, bucket), *extra), n
        )

    def _run_decode(self, tokens, mask, ids):
        """Bucket-pad one decode chunk (cached encoder outputs) and run the
        decode executable. Zero-padded rows are inert: every decode op is
        row-independent (see ``_mae_decode``)."""
        n = tokens.shape[0]
        bucket = bucket_for(n, self.max_batch)
        self._m_pad.observe((bucket - n) / bucket)
        args = (
            self._pad_rows(tokens, bucket),
            self._pad_rows(mask, bucket),
            self._pad_rows(ids, bucket),
        )
        return self._dispatch("reconstruct.dec", None, bucket, args, n)

    def last_breakdown(self) -> dict | None:
        """The compute/fetch/bucket/pad breakdown of the most recent predict
        *on the calling thread* (``None`` before any). This is the
        ``RequestTracer(breakdown=...)`` feed: the micro-batcher's collector
        thread calls predict and reads this right after, so the value can't
        be clobbered by a concurrent caller."""
        bd = getattr(self._tls, "bd", None)
        if bd is None:
            return None
        rows = bd["bucket_rows"]
        return {
            "compute_s": bd["compute_s"],
            "fetch_s": bd["fetch_s"],
            "bucket": bd["bucket"],
            "pad_fraction": (bd["pad_rows"] / rows) if rows else 0.0,
        }

    def _check_images(self, images) -> np.ndarray:
        images = np.asarray(images)
        if images.ndim == 3:
            images = images[None]
        if images.ndim != 4 or images.shape[-1] != 3:
            raise ValueError(f"expected (n, H, W, 3) uint8 images, got {images.shape}")
        if images.shape[1] != self.image_size or images.shape[2] != self.image_size:
            raise ResolutionMismatchError(
                self.image_size, (images.shape[1], images.shape[2])
            )
        return images.astype(np.uint8, copy=False)

    def _reset_breakdown(self):
        self._tls.bd = {
            "compute_s": 0.0, "fetch_s": 0.0,
            "bucket": 0, "pad_rows": 0, "bucket_rows": 0,
        }

    def _predict(self, task: str, images, *, pool=None, extra=()):
        t0 = time.perf_counter()
        self._reset_breakdown()
        images = self._check_images(images)
        chunks = [
            self._run(task, pool, images[i : i + self.max_batch], extra)
            for i in range(0, images.shape[0], self.max_batch)
        ]
        out = (
            chunks[0]
            if len(chunks) == 1
            else jax.tree_util.tree_map(lambda *xs: np.concatenate(xs), *chunks)
        )
        self._m_predict.labels(task).observe(time.perf_counter() - t0)
        self._m_images.labels(task).inc(images.shape[0])
        return out

    def features(self, images, *, pool: str = "cls") -> np.ndarray:
        """Pooled (or full-token) float32 encoder features, one row per
        input image."""
        if pool not in POOLS:
            raise ValueError(f"pool must be one of {POOLS}, got {pool!r}")
        return self._predict("features", images, pool=pool)

    def logits(self, images) -> np.ndarray:
        """Float32 classification logits through the trained head."""
        return self._predict("logits", images)

    def reconstruct(self, images, *, seed: int = 0) -> dict[str, np.ndarray]:
        """MAE reconstruction: ``{"reconstruction": (n, N, p*p*3), "mask":
        (n, N)}`` in (possibly norm-pix) patch space — same contract as
        ``tools/reconstruct.py``. ``seed`` varies the mask without
        recompiling (traced scalar). With ``encoder_cache`` enabled the
        encoder runs once per distinct (image, seed); repeats pay only the
        light decoder."""
        if self._enc_cache_size > 0:
            return self._reconstruct_cached(images, int(seed))
        return self._predict(
            "reconstruct", images, extra=(jnp.asarray(seed, jnp.int32),)
        )

    @staticmethod
    def _row_nbytes(row: tuple) -> int:
        """Payload bytes of one cached (tokens, mask, ids) row."""
        return sum(int(getattr(a, "nbytes", 0)) for a in row)

    def encoder_cache_stats(self) -> dict:
        with self._enc_cache_lock:
            size = len(self._enc_cache)
            nbytes = self._enc_cache_nbytes
        return {
            "capacity": self._enc_cache_size,
            "capacity_bytes": self._enc_cache_bytes_cap,
            "size": size,
            "bytes": nbytes,
            "hits": self.encoder_cache_hits,
            "misses": self.encoder_cache_misses,
        }

    def encoder_cache_bytes(self) -> int:
        """Resident payload bytes of the encoder-output LRU — the memory
        accountant's ``engine_enc_cache`` component probe."""
        with self._enc_cache_lock:
            return self._enc_cache_nbytes

    def executable_cache_bytes(self) -> int:
        """Sum of known serialized sizes of resident executables — the
        accountant's ``engine_exec_cache`` probe. Sizes come from warmcache
        serialization; a compiled-but-never-serialized executable (warmcache
        off) contributes 0 rather than guessing."""
        return sum(self._exec_nbytes.values())

    def predicted_peak_hbm(self) -> dict[str, float]:
        """XLA-predicted peak HBM bytes per compiled program
        (``task/b<bucket>`` keys) — feeds the serving-side
        ``mem_hbm_predict_vs_measured`` drift gauge via
        ``MemoryWatcher.record_predicted_peak``."""
        return {
            f"{k[0]}/b{k[1]}": float(c.peak_bytes)
            for k, c in self.cost_reports.items()
            if getattr(c, "peak_bytes", 0)
        }

    def _reconstruct_cached(self, images, seed: int) -> dict[str, np.ndarray]:
        """Encoder-once/decode-many reconstruction. The LRU key is the raw
        image bytes + mask seed: the mask draw depends on exactly (seed,
        position-in-batch-independent PRNG), so a cached encoder output is
        bit-identical to recomputing it — the cache can never change a
        result, only skip work."""
        t0 = time.perf_counter()
        self._reset_breakdown()
        images = self._check_images(images)
        n = images.shape[0]
        keys = [
            hashlib.sha1(images[i].tobytes()).hexdigest() + f":{seed}"
            for i in range(n)
        ]
        rows: list[tuple | None] = [None] * n
        miss_idx: dict[str, list[int]] = {}
        with self._enc_cache_lock:
            for i, k in enumerate(keys):
                hit = self._enc_cache.get(k)
                if hit is not None:
                    self._enc_cache.move_to_end(k)
                    rows[i] = hit
                else:
                    # dedupe within the batch: one encode per distinct image
                    miss_idx.setdefault(k, []).append(i)
        hits = n - sum(len(v) for v in miss_idx.values())
        self.encoder_cache_hits += hits
        self.encoder_cache_misses += len(miss_idx)
        if hits:
            self._m_enc_cache.labels("hit").inc(hits)
        if miss_idx:
            self._m_enc_cache.labels("miss").inc(len(miss_idx))
            miss_images = np.stack(
                [images[idxs[0]] for idxs in miss_idx.values()]
            )
            extra = (jnp.asarray(seed, jnp.int32),)
            parts = [
                self._run(
                    "reconstruct.enc",
                    None,
                    miss_images[i : i + self.max_batch],
                    extra,
                )
                for i in range(0, miss_images.shape[0], self.max_batch)
            ]
            tokens, mask, ids = (
                parts[0]
                if len(parts) == 1
                else tuple(
                    np.concatenate([p[j] for p in parts]) for j in range(3)
                )
            )
            with self._enc_cache_lock:
                for j, (k, idxs) in enumerate(miss_idx.items()):
                    row = (tokens[j], mask[j], ids[j])
                    for i in idxs:
                        rows[i] = row
                    if k not in self._enc_cache:
                        self._enc_cache_nbytes += self._row_nbytes(row)
                    self._enc_cache[k] = row
                    self._enc_cache.move_to_end(k)
                # two bounds, one loop: entry count (historical) and, when
                # configured, resident bytes — whichever trips first evicts
                while self._enc_cache and (
                    len(self._enc_cache) > self._enc_cache_size
                    or (
                        self._enc_cache_bytes_cap > 0
                        and self._enc_cache_nbytes > self._enc_cache_bytes_cap
                    )
                ):
                    _, old = self._enc_cache.popitem(last=False)
                    self._enc_cache_nbytes -= self._row_nbytes(old)
                    self._m_enc_cache.labels("evict").inc()
                self._m_enc_cache_bytes.set(self._enc_cache_nbytes)
        tokens = np.stack([r[0] for r in rows])
        mask = np.stack([r[1] for r in rows])
        ids = np.stack([r[2] for r in rows])
        chunks = [
            self._run_decode(
                tokens[i : i + self.max_batch],
                mask[i : i + self.max_batch],
                ids[i : i + self.max_batch],
            )
            for i in range(0, n, self.max_batch)
        ]
        out = (
            chunks[0]
            if len(chunks) == 1
            else jax.tree_util.tree_map(lambda *xs: np.concatenate(xs), *chunks)
        )
        self._m_predict.labels("reconstruct").observe(time.perf_counter() - t0)
        self._m_images.labels("reconstruct").inc(n)
        return out

    def predict(self, images, task: str = "features", **kw):
        if task == "features":
            return self.features(images, **kw)
        if task == "logits":
            return self.logits(images, **kw)
        if task == "reconstruct":
            return self.reconstruct(images, **kw)
        raise ValueError(f"unknown task {task!r}")

    # ------------------------------------------------- token-packed serving

    def seq_len(self, size: int) -> int:
        """Token count of one packed request at a square resolution:
        ``num_cls_tokens + (size/patch)²``. Raises on non-patch-aligned
        sizes — packing plans in whole patch tokens."""
        p = self._enc.patch_size
        size = int(size)
        if size < p or size % p:
            raise ValueError(
                f"image size {size} is not a positive multiple of "
                f"patch_size={p} — packed serving needs patch-aligned inputs"
            )
        return self._enc.num_cls_tokens + (size // p) ** 2

    def _check_packed_request(self, imgs: list, task_list: list) -> list[int]:
        """Validate a packed request mix; returns per-request token counts."""
        lengths = []
        for i, im in enumerate(imgs):
            if im.ndim != 3 or im.shape[-1] != 3:
                raise ValueError(
                    f"packed request {i}: expected one (H, W, 3) uint8 "
                    f"image, got {im.shape}"
                )
            h, w = int(im.shape[0]), int(im.shape[1])
            if h != w:
                raise ValueError(
                    f"packed request {i}: expected a square image, got "
                    f"{h}x{w}"
                )
            if h != self.image_size and self._enc.posemb != "sincos2d":
                raise ValueError(
                    f"packed request {i} is {h}px but the engine's native "
                    f"size is {self.image_size}px and posemb="
                    f"{self._enc.posemb!r} is resolution-locked — serve "
                    f"mixed resolutions with posemb='sincos2d'"
                )
            lengths.append(self.seq_len(h))
        bad = sorted({t for t in task_list if t not in ("features", "logits")})
        if bad:
            raise ValueError(
                f"packed serving covers the encoder-sharing tasks "
                f"features/logits; got {bad}"
            )
        return lengths

    def _embed_requests(
        self, imgs: list, tree_task: str
    ) -> list[np.ndarray]:
        """Stage 1 of the packed pipeline: per-resolution patch embedding
        (image-count-bucketed executables), one (n_patches, dim) float32
        token array per request."""
        patch_tokens: list = [None] * len(imgs)
        by_res: dict[int, list[int]] = {}
        for i, im in enumerate(imgs):
            by_res.setdefault(int(im.shape[0]), []).append(i)
        for res, idxs in sorted(by_res.items()):
            stack = np.stack([imgs[i] for i in idxs]).astype(np.uint8, copy=False)
            for off in range(0, len(idxs), self.max_batch):
                out = self._run(
                    f"{tree_task}.embed@{res}",
                    None,
                    stack[off : off + self.max_batch],
                )
                for j, i_req in enumerate(idxs[off : off + self.max_batch]):
                    patch_tokens[i_req] = out[j]
        return patch_tokens

    def predict_packed(
        self,
        images,
        tasks="features",
        *,
        pool: str = "cls",
        max_tokens: int | None = None,
    ) -> list[np.ndarray]:
        """Serve a mixed-resolution, mixed-task request list through ONE
        token-packed dispatch instead of one padded image bucket per
        ``(task, shape)``.

        ``images`` is a list of square, patch-aligned ``(H, W, 3)`` uint8
        arrays (224–896px etc. — any patch multiple; non-native sizes need
        ``posemb='sincos2d'``). ``tasks`` is one task name or one per
        request, from ``features``/``logits`` — the encoder-sharing pair
        that can ride one executable (when any request wants logits, the
        whole pack runs on the logits task's tree, whose encoder is the
        same grafted checkpoint). Returns one float32 row per request, in
        request order.

        Pipeline: per-resolution patch embedding (stage 1, image-count
        buckets) → deterministic FFD pack of the token segments into a
        power-of-2 token-budget rung (``infer/packing.py``) → one packed
        executable keyed by (rows, max_segments, budget). Pad tokens are
        provably inert (block-diagonal segment attention), and
        ``last_breakdown().pad_fraction`` reports the *token*-level pad of
        the packed dispatch — the costmeter bills waste from it.
        """
        if pool not in ("cls", "gap"):
            raise ValueError(
                f"packed serving pools per segment: pool must be 'cls' or "
                f"'gap', got {pool!r}"
            )
        imgs = [np.asarray(im) for im in images]
        n = len(imgs)
        if n == 0:
            return []
        task_list = [tasks] * n if isinstance(tasks, str) else list(tasks)
        if len(task_list) != n:
            raise ValueError(
                f"{n} images but {len(task_list)} tasks — pass one task "
                f"name or one per request"
            )
        lengths = self._check_packed_request(imgs, task_list)
        tree_task = (
            "logits" if any(t == "logits" for t in task_list) else "features"
        )

        t0 = time.perf_counter()
        self._reset_breakdown()
        patch_tokens = self._embed_requests(imgs, tree_task)
        # stage-1 image buckets are tiny next to the packed dispatch; reset
        # the pad accounting so last_breakdown() reports the packed
        # dispatch's TOKEN pad fraction (compute/fetch keep accumulating)
        self._tls.bd["pad_rows"] = 0
        self._tls.bd["bucket_rows"] = 0

        k = self._enc.num_cls_tokens
        rungs = packing.budget_rungs(int(max_tokens or self.max_tokens))
        budget, plan = packing.choose_budget(lengths, rungs)
        rows_b = ceil_pow2(plan.rows)
        smax_b = ceil_pow2(plan.max_segments)
        arrays = packing.build_arrays(plan, k, rows=rows_b, max_segments=smax_b)
        buf = packing.place_tokens(plan, patch_tokens, k, rows=rows_b)

        task_key = f"{tree_task}.packed:{pool}@r{rows_b}s{smax_b}"
        ex = self._executable(task_key, None, budget)
        t = self._task(tree_task)
        t_compute = time.perf_counter()
        out = ex(
            t["variables"],
            buf,
            arrays["segment_ids"],
            arrays["cls_pos"],
            arrays["cls_index"],
        )
        jax.block_until_ready(out)
        t_fetch = time.perf_counter()
        out = jax.tree_util.tree_map(np.asarray, out)
        bd = self._tls.bd
        bd["compute_s"] += t_fetch - t_compute
        bd["fetch_s"] += time.perf_counter() - t_fetch
        device_tokens = rows_b * budget
        total_tokens = plan.total_tokens
        bd["bucket"] = max(bd["bucket"], budget)
        bd["pad_rows"] += device_tokens - total_tokens
        bd["bucket_rows"] += device_tokens
        pred = self._pred_s.get((task_key, budget))
        if pred:
            self._m_drift.labels(f"{task_key}/b{budget}").set(
                (t_fetch - t_compute) / pred
            )

        self._m_pack_pad.observe((device_tokens - total_tokens) / device_tokens)
        self._m_pack_segments.observe(len(plan.segments))
        self._m_pack_occ.observe(total_tokens / device_tokens)
        self._m_pack_dispatches.labels(tree_task).inc()
        self._m_predict.labels("packed").observe(time.perf_counter() - t0)
        self._m_images.labels("packed").inc(n)

        pooled = packing.unpack_rows(plan, out["pooled"])
        logits = (
            packing.unpack_rows(plan, out["logits"]) if "logits" in out else None
        )
        return [
            logits[i] if task_list[i] == "logits" else pooled[i]
            for i in range(n)
        ]

    def packed_parity(
        self,
        images,
        tasks="features",
        *,
        pool: str = "cls",
        max_tokens: int | None = None,
        feature_cos_min: float = 0.999,
        logits_top1_min: float = 0.98,
    ) -> dict:
        """Per-request numeric parity of the packed path against the
        unpacked forward on the SAME task tree — the packed rollout's
        correctness gate (same thresholds as the int8 quant gate:
        feature cosine >= 0.999, logits top-1 agreement >= 0.98)."""
        imgs = [np.asarray(im) for im in images]
        n = len(imgs)
        task_list = [tasks] * n if isinstance(tasks, str) else list(tasks)
        packed = self.predict_packed(
            imgs, task_list, pool=pool, max_tokens=max_tokens
        )
        tree_task = (
            "logits" if any(t == "logits" for t in task_list) else "features"
        )
        ref_pooled: list = [None] * n
        ref_logits: list = [None] * n
        by_res: dict[int, list[int]] = {}
        for i, im in enumerate(imgs):
            by_res.setdefault(int(im.shape[0]), []).append(i)
        self._reset_breakdown()
        for res, idxs in sorted(by_res.items()):
            stack = np.stack([imgs[i] for i in idxs]).astype(np.uint8, copy=False)
            for off in range(0, len(idxs), self.max_batch):
                out = self._run(
                    f"{tree_task}.full:{pool}@{res}",
                    None,
                    stack[off : off + self.max_batch],
                )
                for j, i_req in enumerate(idxs[off : off + self.max_batch]):
                    ref_pooled[i_req] = out["pooled"][j]
                    if "logits" in out:
                        ref_logits[i_req] = out["logits"][j]
        cosines: list[float] = []
        top1: list[int] = []
        rows = []
        for i in range(n):
            if task_list[i] == "logits":
                agree = int(np.argmax(packed[i]) == np.argmax(ref_logits[i]))
                top1.append(agree)
                rows.append({"task": "logits", "top1_agree": agree})
            else:
                a = packed[i].ravel().astype(np.float64)
                b = ref_pooled[i].ravel().astype(np.float64)
                denom = np.linalg.norm(a) * np.linalg.norm(b)
                cos = float(a @ b / denom) if denom else 1.0
                cosines.append(cos)
                rows.append({"task": "features", "cosine": round(cos, 6)})
        cos_min = min(cosines) if cosines else None
        top1_agree = float(np.mean(top1)) if top1 else None
        ok = (cos_min is None or cos_min >= feature_cos_min) and (
            top1_agree is None or top1_agree >= logits_top1_min
        )
        self._m_pack_parity.set(cos_min if cos_min is not None else 1.0)
        if not ok:
            self._m_pack_parity_fail.inc()
        return {
            "n": n,
            "pool": pool,
            "feature_cosine_min": cos_min,
            "logits_top1_agree": top1_agree,
            "feature_cos_threshold": feature_cos_min,
            "logits_top1_threshold": logits_top1_min,
            "pass": ok,
            "requests": rows,
        }

    def warmup_packed(
        self,
        resolutions,
        tasks: tuple[str, ...] = ("features",),
        *,
        pool: str = "cls",
        max_tokens: int | None = None,
    ) -> int:
        """Precompile the packed path for a representative resolution mix:
        each resolution's embed executable plus the packed executable the
        mix's FFD plan lands on. Returns compiles performed (warmcache
        loads are free, same contract as :meth:`warmup`)."""
        resolutions = [int(r) for r in resolutions]
        if not resolutions:
            return 0
        tree_task = "logits" if "logits" in tuple(tasks) else "features"
        lengths = [self.seq_len(r) for r in resolutions]
        rungs = packing.budget_rungs(int(max_tokens or self.max_tokens))
        budget, plan = packing.choose_budget(lengths, rungs)
        rows_b = ceil_pow2(plan.rows)
        smax_b = ceil_pow2(plan.max_segments)
        before = sum(self.compile_counts.values())
        t0 = time.perf_counter()
        counts: dict[int, int] = {}
        for r in resolutions:
            counts[r] = counts.get(r, 0) + 1
        for res, cnt in sorted(counts.items()):
            self._executable(
                f"{tree_task}.embed@{res}",
                None,
                bucket_for(min(cnt, self.max_batch), self.max_batch),
            )
        self._executable(
            f"{tree_task}.packed:{pool}@r{rows_b}s{smax_b}", None, budget
        )
        self._m_warm_start.set(time.perf_counter() - t0)
        return sum(self.compile_counts.values()) - before
