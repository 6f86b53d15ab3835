"""Streaming dataloader: shards → decode → augment → batches → device.

The TPU-native replacement for the reference's webdataset + torch DataLoader
stack (``/root/reference/src/dataset.py:100-161``). Same external contracts:

- train: infinite stream, deterministic shard order shuffle per epoch,
  per-process striping, per-worker split, streaming sample shuffle,
  repeated augmentation with clones de-interleaved across the batch
  (``collate_and_shuffle``, ``/root/reference/src/dataset.py:85-92``);
- valid: one sequential pass, final partial batch padded to full size with
  ``valid=False`` rows and ``label=-1`` (the reference's ``-1``-pad contract,
  ``/root/reference/src/dataset.py:95-97``), so every process issues the same
  number of identically-shaped steps;
- batches are host numpy uint8 NHWC; normalization runs on device.

Differences by design: workers are ``multiprocessing`` processes owned by
this module (no torch), every worker's stream is reproducible from (seed,
process_index, worker_index, epoch), and batches land on device through a
double-buffered ``jax.device_put`` with an explicit ``NamedSharding`` so
host→device copy overlaps compute (the reference relied on pmap's implicit
transfer with no overlap).
"""

from __future__ import annotations

import queue as queue_mod
import time
from collections.abc import Iterator
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from jumbo_mae_tpu_tpu.data.decode import decode_image, decode_label, find_image_key
from jumbo_mae_tpu_tpu.faults.inject import fault_point
from jumbo_mae_tpu_tpu.obs.metrics import get_registry
from jumbo_mae_tpu_tpu.data.randaugment import auto_augment_factory
from jumbo_mae_tpu_tpu.data.shards import expand_shards, shuffle_shards, split_shards
from jumbo_mae_tpu_tpu.data.tario import RetryPolicy, iter_shards_samples
from jumbo_mae_tpu_tpu.data.transforms import (
    color_jitter,
    eval_transform,
    random_erasing,
    random_hflip,
    random_resized_crop,
    simple_resize_crop,
)


@dataclass(frozen=True)
class DataConfig:
    """Pipeline knobs; defaults mirror the reference's argparse defaults
    (``/root/reference/src/main_finetune.py:97-160``)."""

    train_shards: str | list[str] = ""
    valid_shards: str | list[str] = ""
    image_size: int = 224
    # mode lm: tokens a sequence is trained on (a batch row holds seq_len + 1
    # + the model's multi-token-prediction depth ids)
    seq_len: int = 0
    labeled: bool = True
    crop_mode: str = "rrc"  # rrc | src | none
    min_scale: float = 0.2
    hflip: float = 0.5
    auto_augment: str = "none"
    color_jitter: float = 0.0
    random_erasing: float = 0.0
    repeats: int = 1
    shuffle_buffer: int = 1000
    test_crop_ratio: float = 0.875
    seed: int = 0
    workers: int = 4
    prefetch_batches: int = 4
    # shard-read resilience (data/tario.py): transient OSError/pipe failures
    # get shard_retries attempts with capped exponential backoff (base
    # shard_retry_backoff_s, jittered) before the shard is quarantined for
    # the rest of the epoch pass (counted + surfaced in /healthz)
    shard_retries: int = 3
    shard_retry_backoff_s: float = 0.05
    # samples per epoch — used only to convert a resumed step count into the
    # stream's starting epoch (coarse data-cursor resume)
    dataset_size: int = 1_281_167
    # use the native C++ threaded tar reader (native/tario.cc) as the IO
    # substrate instead of per-worker Python tarfile streams
    use_native: bool = False
    native_io_threads: int = 4
    decode_threads: int = 4
    # directory for the on-disk validation-sample cache (data/valcache.py):
    # the first eval pass writes post-transform tensors there, every later
    # eval streams from the cache with zero shard reads/decodes (parity+:
    # the reference cached the raw val tars, /root/reference/src/dataset.py:141).
    # Empty string disables caching.
    valid_cache: str = ""


@dataclass
class StreamCursor:
    """Mutable position of a train sample stream: ``offset`` samples (clones
    included) have been yielded within ``epoch``. Updated in place by the
    stream generators after every yield, so whoever drains the stream can
    snapshot an exact resume point (sample-exact resume — beyond the
    reference, whose restart lost the data position entirely,
    ``/root/reference/src/utils.py:55-63``)."""

    epoch: int = 0
    offset: int = 0


def _retry_policy(cfg: DataConfig) -> RetryPolicy:
    """The shard-read retry policy every stream in this module uses."""
    return RetryPolicy(
        attempts=max(1, cfg.shard_retries),
        backoff_s=max(0.0, cfg.shard_retry_backoff_s),
    )


def _aug_rng(
    seed: int, process_index: int, worker_index: int, epoch: int, idx: int
) -> np.random.Generator:
    """Per-sample augmentation RNG, derived independently of the shuffle RNG.

    Keying augmentation on the yielded-sample index (instead of sharing the
    epoch stream's generator) is what makes fast-skip possible: a resumed
    stream can skip the transform compute for already-consumed samples
    without perturbing any RNG state the remaining samples depend on.
    """
    return np.random.default_rng((seed, 3, process_index, worker_index, epoch, idx))


class TrainTransform:
    """Per-sample train augmentation chain (crop → flip → policy → jitter →
    erasing), reproducing ``create_transforms`` train branch
    (``/root/reference/src/dataset.py:56-75``)."""

    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        self.policy = auto_augment_factory(cfg.auto_augment)

    def __call__(self, rng: np.random.Generator, img: np.ndarray) -> np.ndarray:
        cfg = self.cfg
        if cfg.crop_mode == "rrc":
            img = random_resized_crop(
                rng, img, cfg.image_size, scale=(cfg.min_scale, 1.0)
            )
        elif cfg.crop_mode == "src":
            img = simple_resize_crop(rng, img, cfg.image_size)
        else:
            from jumbo_mae_tpu_tpu.data.transforms import resize

            img = resize(img, (cfg.image_size, cfg.image_size))
        img = random_hflip(rng, img, cfg.hflip)
        if self.policy is not None:
            img = self.policy(rng, img)
        if cfg.color_jitter > 0:
            img = color_jitter(rng, img, cfg.color_jitter)
        if cfg.random_erasing > 0:
            img = random_erasing(rng, img, cfg.random_erasing)
        return np.ascontiguousarray(img)


def _shuffle_stream(
    it: Iterator, buffer_size: int, rng: np.random.Generator
) -> Iterator:
    """Streaming buffer shuffle (webdataset ``detshuffle`` equivalent)."""
    if buffer_size <= 1:
        yield from it
        return
    buf: list = []
    for x in it:
        if len(buf) < buffer_size:
            buf.append(x)
            continue
        i = int(rng.integers(len(buf)))
        buf[i], x = x, buf[i]
        yield x
    rng.shuffle(buf)  # type: ignore[arg-type]
    yield from buf


def train_sample_stream(
    cfg: DataConfig,
    *,
    process_index: int = 0,
    process_count: int = 1,
    worker_index: int = 0,
    worker_count: int = 1,
    start_epoch: int = 0,
    skip_samples: int = 0,
    cursor: StreamCursor | None = None,
    ledger=None,
    epoch_shard_override: list | None = None,
) -> Iterator[tuple[np.ndarray, int]]:
    """Infinite (image, label) stream for one (process, worker) pair.

    ``skip_samples`` fast-forwards past already-consumed samples of the
    starting epoch: shard order, shuffle-buffer draws, and decode all replay
    (they define WHICH samples come next) but the augmentation transform —
    the expensive part — is skipped, and per-sample RNG keying keeps the
    remaining stream bit-identical to an uninterrupted one.

    ``ledger`` (a :class:`~jumbo_mae_tpu_tpu.data.resize.ShardLedger`)
    tracks which epoch shards have been FULLY yielded through the shuffle
    buffer — the cursor a resized resume stripes the remainder from.
    ``epoch_shard_override`` replaces the stream's shard stripe for the
    STARTING epoch only (``(global_index, url)`` pairs from
    :func:`~jumbo_mae_tpu_tpu.data.resize.resize_assignment`); later
    epochs stripe normally at the current topology.
    """
    shards = expand_shards(cfg.train_shards)
    transform = TrainTransform(cfg)
    # per-sample decode time — in a worker subprocess this lands in that
    # process's own registry (unexported), in the inline/native path it
    # feeds the exporter directly
    reg = get_registry()
    m_decode = reg.histogram(
        "data_decode_seconds", "image decode time per sample"
    )
    m_decode_fail = reg.counter(
        "data_decode_failures_total", "samples dropped by a failed decode"
    )
    retry = _retry_policy(cfg)
    epoch = start_epoch
    to_skip = max(0, skip_samples)
    while True:
        rng = np.random.default_rng(
            (cfg.seed, 1, process_index, worker_index, epoch)
        )
        order = shuffle_shards(shards, seed=cfg.seed, epoch=epoch)
        if epoch_shard_override is not None and epoch == start_epoch:
            epoch_pairs = [(int(g), str(u)) for g, u in epoch_shard_override]
        else:
            gidx = split_shards(
                list(range(len(order))),  # type: ignore[arg-type]
                process_index=process_index,
                process_count=process_count,
                worker_index=worker_index,
                worker_count=worker_count,
            )
            epoch_pairs = [(g, order[g]) for g in gidx]

        def decoded():
            # one iter_shards_samples call per shard (instead of one for
            # the whole stripe) so the ledger sees shard boundaries; retry
            # and quarantine are per-shard in tario, so behavior is
            # unchanged
            for g, url in epoch_pairs:
                for sample in iter_shards_samples([url], retry=retry):
                    img_key = find_image_key(sample)
                    if img_key is None:
                        continue
                    t0 = time.perf_counter()
                    payload = fault_point(
                        "data.decode",
                        key=str(sample.get("__key__", "")),
                        data=sample[img_key],
                    )
                    img = decode_image(payload)  # type: ignore[arg-type]
                    m_decode.observe(time.perf_counter() - t0)
                    if img is None:
                        m_decode_fail.inc()
                        continue
                    label = decode_label(sample["cls"]) if "cls" in sample else -1
                    if ledger is not None:
                        ledger.note_read(epoch, g)
                    yield g, (img, label)
                if ledger is not None:
                    ledger.note_read_done(epoch, g)

        idx = 0
        for g, (img, label) in _shuffle_stream(decoded(), cfg.shuffle_buffer, rng):
            if ledger is not None:
                ledger.note_yield(epoch, g)
            for _ in range(cfg.repeats):
                if to_skip > 0:
                    to_skip -= 1
                    idx += 1
                    continue
                aug = _aug_rng(cfg.seed, process_index, worker_index, epoch, idx)
                out = transform(aug, img), label
                idx += 1
                if cursor is not None:
                    cursor.epoch, cursor.offset = epoch, idx
                yield out
        epoch += 1


def valid_sample_stream(
    cfg: DataConfig, *, process_index: int = 0, process_count: int = 1
) -> Iterator[tuple[np.ndarray, int]]:
    """One sequential eval pass over this process's stripe of the valid set."""
    shards = split_shards(
        expand_shards(cfg.valid_shards),
        process_index=process_index,
        process_count=process_count,
    )
    for sample in iter_shards_samples(shards, retry=_retry_policy(cfg)):
        img_key = find_image_key(sample)
        if img_key is None:
            continue
        img = decode_image(sample[img_key])  # type: ignore[arg-type]
        if img is None:
            continue
        label = decode_label(sample["cls"]) if "cls" in sample else -1
        yield eval_transform(img, cfg.image_size, crop_ratio=cfg.test_crop_ratio), label


def native_train_stream(
    cfg: DataConfig,
    *,
    process_index: int = 0,
    process_count: int = 1,
    start_epoch: int = 0,
    skip_samples: int = 0,
    cursor: StreamCursor | None = None,
) -> Iterator[tuple[np.ndarray, int]]:
    """Native-IO train stream: C++ reader threads feed raw image bytes, a
    thread pool does decode+augment (cv2/PIL release the GIL, so this scales
    within one process where the pure-Python path needs worker processes).

    One epoch of the process's shard stripe per native reader; shard order is
    reshuffled per epoch like :func:`train_sample_stream`. SAMPLE-EXACTLY
    RESUMABLE: the C++ reader gives each thread static ownership of every
    T-th shard and merges thread queues in strict round-robin
    (``native/tario.cc``), so the sample order is a pure function of
    (shard list, ``native_io_threads``) and ``skip_samples`` replays the
    consumed prefix exactly, same contract as :func:`train_sample_stream`
    (decode and shuffle-buffer draws replay; the augmentation transform is
    skipped).
    """
    from concurrent.futures import ThreadPoolExecutor

    from jumbo_mae_tpu_tpu.data.native import NativeShardReader

    shards = expand_shards(cfg.train_shards)
    transform = TrainTransform(cfg)
    reg = get_registry()
    m_decode = reg.histogram(
        "data_decode_seconds", "image decode time per sample"
    )
    m_decode_fail = reg.counter(
        "data_decode_failures_total", "samples dropped by a failed decode"
    )
    epoch = start_epoch
    to_skip = max(0, skip_samples)
    with ThreadPoolExecutor(max_workers=max(1, cfg.decode_threads)) as pool:
        while True:
            rng = np.random.default_rng((cfg.seed, 2, process_index, epoch))
            epoch_shards = split_shards(
                shuffle_shards(shards, seed=cfg.seed, epoch=epoch),
                process_index=process_index,
                process_count=process_count,
            )

            def decode_one(pair):
                payload, label = pair
                t0 = time.perf_counter()
                payload = fault_point("data.decode", data=payload)
                img = decode_image(payload)
                m_decode.observe(time.perf_counter() - t0)
                if img is None:
                    m_decode_fail.inc()
                    return None
                return (img, label)

            def decoded(reader):
                # bounded in-flight futures (NOT pool.map, which eagerly
                # drains the whole reader and buffers an epoch of JPEGs):
                # the window is what keeps backpressure on the C++ queue
                from collections import deque

                window: deque = deque()
                depth = max(2, cfg.decode_threads * 4)
                for pair in reader:
                    window.append(pool.submit(decode_one, pair))
                    if len(window) >= depth:
                        r = window.popleft().result()
                        if r is not None:
                            yield r
                while window:
                    r = window.popleft().result()
                    if r is not None:
                        yield r

            with NativeShardReader(
                epoch_shards, threads=cfg.native_io_threads, loop=False
            ) as reader:
                idx = 0
                for img, label in _shuffle_stream(
                    decoded(reader), cfg.shuffle_buffer, rng
                ):
                    for _ in range(cfg.repeats):
                        if to_skip > 0:
                            to_skip -= 1
                            idx += 1
                            continue
                        aug = _aug_rng(cfg.seed, process_index, 0, epoch, idx)
                        out = transform(aug, img), label
                        idx += 1
                        if cursor is not None:
                            cursor.epoch, cursor.offset = epoch, idx
                        yield out
            epoch += 1


def _deinterleave(indices: int, repeats: int) -> np.ndarray:
    """Batch reorder that spreads repeated-augmentation clones across the
    batch: position j ← sample j*repeats % n adjusted — equivalent to the
    reference's ``batch[i::repeats]`` concatenation
    (``/root/reference/src/dataset.py:91-92``)."""
    order = np.arange(indices)
    return np.concatenate([order[i::repeats] for i in range(repeats)])


def batch_train_samples(
    stream: Iterator[tuple[np.ndarray, int]],
    batch_size: int,
    repeats: int = 1,
    cursor: StreamCursor | None = None,
    ledger=None,
) -> Iterator[dict[str, np.ndarray]]:
    """Assemble train batches; de-interleave repeat clones. With ``cursor``
    (the SAME object the stream updates), each batch carries a ``_cursor``
    key — the (epoch, offset) reached after its last sample — so consumers
    can checkpoint a sample-exact resume point. With ``ledger`` (the SAME
    object the stream updates), each batch also carries a ``_shards`` key —
    the consumed-shard snapshot as of its last sample — for resize-safe
    elastic resume."""
    order = _deinterleave(batch_size, max(1, repeats))
    while True:
        pairs = [next(stream) for _ in range(batch_size)]
        images = np.stack([p[0] for p in pairs])[order]
        labels = np.asarray([p[1] for p in pairs], np.int32)[order]
        batch = {"images": images, "labels": labels}
        if cursor is not None:
            batch["_cursor"] = (cursor.epoch, cursor.offset)
        if ledger is not None:
            batch["_shards"] = ledger.snapshot()
        yield batch


def batch_valid_samples(
    stream: Iterator[tuple[np.ndarray, int]],
    batch_size: int,
    image_size: int,
) -> Iterator[dict[str, np.ndarray]]:
    """Assemble eval batches; pad the final partial batch (valid=False,
    label=-1) so step shapes stay constant."""
    images = np.zeros((batch_size, image_size, image_size, 3), np.uint8)
    labels = np.full((batch_size,), -1, np.int32)
    valid = np.zeros((batch_size,), bool)
    n = 0
    for img, label in stream:
        images[n], labels[n], valid[n] = img, label, True
        n += 1
        if n == batch_size:
            yield {"images": images.copy(), "labels": labels.copy(), "valid": valid.copy()}
            images = np.zeros_like(images)
            labels = np.full_like(labels, -1)
            valid = np.zeros_like(valid)
            n = 0
    if n:
        yield {"images": images, "labels": labels, "valid": valid}


class _Worker:
    """One data-worker subprocess + its pipe-reader thread and batch queue.

    The worker is a FRESH interpreter (``python -m
    jumbo_mae_tpu_tpu.data._worker``), not a multiprocessing child — see
    ``data/_worker.py`` for why (spawn re-imports the user's __main__; fork
    duplicates a live multithreaded XLA runtime). The reader thread turns the
    stdout frame stream into a bounded queue; EOF marks the worker dead so
    the consumer can skip it instead of hanging.
    """

    def __init__(self, spec: dict, queue_size: int):
        import json
        import os
        import subprocess
        import sys
        import threading

        from jumbo_mae_tpu_tpu.utils.procenv import cpu_subprocess_env

        # workers never use jax; JAX_PLATFORMS=cpu keeps any import of it off
        # the chip this process holds (see utils/procenv.py)
        env = cpu_subprocess_env()
        repo_root = str(Path(__file__).resolve().parent.parent.parent)
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "jumbo_mae_tpu_tpu.data._worker", json.dumps(spec)],
            stdout=subprocess.PIPE,
            env=env,
        )
        self.queue: queue_mod.Queue = queue_mod.Queue(maxsize=queue_size)
        self.dead = False
        self._thread = threading.Thread(target=self._read_loop, daemon=True)
        self._thread.start()

    def _read_loop(self):
        import pickle
        import struct

        stream = self.proc.stdout
        try:
            while True:
                header = stream.read(8)
                if len(header) < 8:
                    break
                (length,) = struct.unpack(">Q", header)
                payload = stream.read(length)
                if len(payload) < length:
                    break
                self.queue.put(pickle.loads(payload))
        except (OSError, ValueError):  # pragma: no cover - pipe torn down
            pass
        finally:
            self.dead = True

    def stop(self):
        self.dead = True
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5)
            except Exception:  # noqa: BLE001  # pragma: no cover
                self.proc.kill()
        if self.proc.stdout is not None:
            self.proc.stdout.close()


class TrainLoader:
    """Infinite train-batch iterator backed by worker subprocesses.

    Each worker owns a disjoint shard stripe and yields WHOLE per-process
    batches (the torch IterableDataset-per-worker batching the reference
    inherited); the parent consumes worker queues in STRICT round-robin order
    — batch n always comes from worker ``n % workers`` — so the global batch
    sequence is a pure function of the config, which is what makes
    sample-exact resume possible. ``workers=0`` runs inline — the mode tests
    and CPU smoke configs use.

    ``snapshot()`` returns a JSON-able cursor (per-worker stream positions +
    the round-robin phase); constructing a loader with ``cursor=`` resumes
    the exact batch sequence from that point.
    """

    def __init__(
        self,
        cfg: DataConfig,
        batch_size: int,
        *,
        process_index: int = 0,
        process_count: int = 1,
        start_epoch: int = 0,
        cursor: dict | None = None,
        epoch_shard_override: list | None = None,
        shard_preconsumed: dict | None = None,
    ):
        if batch_size % max(1, cfg.repeats):
            raise ValueError(
                f"repeats ({cfg.repeats}) must divide the per-process batch "
                f"size ({batch_size})"
            )
        self.cfg = cfg
        self.batch_size = batch_size
        self._workers: list[_Worker] = []
        self._shard_states: list = []
        # epoch the active epoch_shard_override applies to — stamped into
        # snapshots while any stream is still inside it, so a same-world
        # restart knows the sample cursor was measured on the override
        # stripe (not the topology stripe) and must re-derive it
        self._override_epoch: int | None = None
        # loader telemetry (obs/metrics.py): how long the train loop waits
        # for batches, and whether workers are stalling or dying under it
        reg = get_registry()
        self._m_wait = reg.histogram(
            "data_batch_wait_seconds", "host wait in TrainLoader.__next__"
        )
        self._m_batches = reg.counter(
            "data_batches_total", "train batches yielded"
        )
        self._m_stalls = reg.counter(
            "data_worker_stalls_total",
            "5 s waits on an alive worker's empty queue",
            labels=("worker",),
        )
        self._m_deaths = reg.counter(
            "data_worker_deaths_total", "workers found dead at read time"
        )
        if cfg.use_native:
            # the C++ reader's deterministic per-thread shard ownership +
            # round-robin merge makes this stream a pure function of
            # (config, native_io_threads) — but only for the SAME thread
            # count, so a cursor records it and resume validates it
            if epoch_shard_override is not None:
                raise ValueError(
                    "resize-consistent resume (epoch_shard_override) is not "
                    "supported by the native-IO loader — the reader merges "
                    "per-thread queues without shard-boundary accounting; "
                    "restart with data.use_native=false or fall back to "
                    "epoch resume"
                )
            if cursor is not None:
                saved_threads = cursor.get("native_threads")
                if saved_threads is None:
                    raise ValueError(
                        "resume cursor was written by the subprocess-worker "
                        "loader (different sample order); restart with "
                        "data.use_native=false or fall back to epoch resume"
                    )
                if saved_threads != cfg.native_io_threads:
                    raise ValueError(
                        f"resume cursor was written with native_io_threads="
                        f"{saved_threads} but the loader is configured with "
                        f"{cfg.native_io_threads} — the merged sample order "
                        "differs; restart with the checkpointed thread count"
                    )
                (start, skip) = tuple(cursor["workers"][0])
                self.batches_yielded = int(cursor["batches"])
            else:
                start, skip = start_epoch, 0
                self.batches_yielded = 0
            self._native_threads = cfg.native_io_threads
            self._cursors = [(start, skip)]
            track = StreamCursor(start, skip)
            self._stream = native_train_stream(
                cfg,
                process_index=process_index,
                process_count=process_count,
                start_epoch=start,
                skip_samples=skip,
                cursor=track,
            )
            self._inline = batch_train_samples(
                self._stream, batch_size, cfg.repeats, cursor=track
            )
            return
        n_streams = 1 if cfg.workers <= 0 else cfg.workers
        if cursor is not None:
            if cursor.get("native_threads") is not None:
                raise ValueError(
                    "resume cursor was written by the native-IO loader "
                    "(round-robin-over-threads sample order); restart with "
                    "data.use_native=true or fall back to epoch resume"
                )
            starts = [tuple(c) for c in cursor["workers"]]
            if len(starts) != n_streams:
                raise ValueError(
                    f"resume cursor has {len(starts)} worker streams but the "
                    f"loader is configured for {n_streams} — restart with the "
                    f"checkpointed worker count or fall back to epoch resume"
                )
            self.batches_yielded = int(cursor["batches"])
        else:
            starts = [(start_epoch, 0)] * n_streams
            self.batches_yielded = 0
        self._cursors = list(starts)
        self._shard_states = [None] * n_streams
        if epoch_shard_override is not None:
            self._override_epoch = min(e for e, _ in starts)
        if cfg.workers <= 0:
            from jumbo_mae_tpu_tpu.data.resize import ShardLedger

            led = ShardLedger(preconsumed=shard_preconsumed)
            track = StreamCursor(*starts[0])
            self._stream = train_sample_stream(
                cfg,
                process_index=process_index,
                process_count=process_count,
                start_epoch=starts[0][0],
                skip_samples=starts[0][1],
                cursor=track,
                ledger=led,
                epoch_shard_override=epoch_shard_override,
            )
            self._inline = batch_train_samples(
                self._stream, batch_size, cfg.repeats, cursor=track, ledger=led
            )
            return
        self._inline = None
        from dataclasses import asdict

        per_worker_q = max(1, cfg.prefetch_batches // cfg.workers)
        for w in range(cfg.workers):
            spec = {
                "data": asdict(cfg),
                "batch_size": batch_size,
                "process_index": process_index,
                "process_count": process_count,
                "worker_index": w,
                "worker_count": cfg.workers,
                "start_epoch": starts[w][0],
                "skip_samples": starts[w][1],
            }
            if epoch_shard_override is not None:
                # worker w owns every W-th pair of the process's remainder
                # stripe — same [w::W] discipline as split_shards
                spec["epoch_shard_override"] = [
                    [int(g), str(u)]
                    for g, u in epoch_shard_override[w :: cfg.workers]
                ]
            if shard_preconsumed is not None:
                spec["shard_preconsumed"] = shard_preconsumed
            self._workers.append(_Worker(spec, per_worker_q))

    def snapshot(self) -> dict | None:
        """Resume cursor as of the last batch returned by ``__next__``.
        Native-IO snapshots also record the reader thread count — the
        deterministic merge order depends on it, so resume validates it.
        While any stream is still inside an active ``epoch_shard_override``
        epoch, the snapshot carries ``override_epoch``: its offsets were
        measured against the override stripe, so a restart — even at the
        SAME world size — must re-derive the override from the journaled
        shard cursors instead of replaying the offsets on the topology
        stripe. Once every stream has crossed into a later (normally
        striped) epoch, the marker drops off and sample-exact resume is
        valid again."""
        if not self._cursors:
            return None
        snap = {
            "workers": [list(c) for c in self._cursors],
            "batches": self.batches_yielded,
        }
        if getattr(self, "_native_threads", None) is not None:
            snap["native_threads"] = self._native_threads
        if self._override_epoch is not None and any(
            e <= self._override_epoch for e, _ in self._cursors
        ):
            snap["override_epoch"] = self._override_epoch
        return snap

    def shard_snapshot(self) -> dict | None:
        """Merged consumed-shard state across this process's streams, as of
        the last batch returned by ``__next__`` — the per-host payload of
        the ``shard_cursor`` journal event a resized resume reads. ``None``
        on the native path (no shard-boundary accounting)."""
        if not self._shard_states:
            return None
        from jumbo_mae_tpu_tpu.data.resize import merge_shard_states

        merged = merge_shard_states(self._shard_states)
        return {"epochs": {str(e): sorted(v) for e, v in merged.items()}}

    def __iter__(self):
        return self

    def __next__(self) -> dict[str, np.ndarray]:
        t_wait = time.perf_counter()
        if self._inline is not None:
            batch = next(self._inline)
            slot = 0
        else:
            slot = self.batches_yielded % len(self._workers)
            w = self._workers[slot]
            attempts_left = 120  # x 5s = 10 min of silence before giving up
            while True:
                if w.dead and w.queue.empty():
                    # skipping a dead worker would silently fork the batch
                    # sequence away from the deterministic schedule
                    self._m_deaths.inc()
                    raise RuntimeError(
                        f"data worker {slot} died; deterministic stream lost"
                    )
                try:
                    batch = w.queue.get(timeout=5)
                    break
                except queue_mod.Empty:
                    self._m_stalls.labels(str(slot)).inc()
                    attempts_left -= 1
                    if attempts_left <= 0:
                        raise RuntimeError(
                            f"data worker {slot} alive but produced nothing "
                            "for 10 minutes"
                        ) from None
        self._m_wait.observe(time.perf_counter() - t_wait)
        self._m_batches.inc()
        cur = batch.pop("_cursor", None)
        if cur is not None:
            self._cursors[slot] = (int(cur[0]), int(cur[1]))
        sh = batch.pop("_shards", None)
        if sh is not None and self._shard_states:
            self._shard_states[slot] = sh
        self.batches_yielded += 1
        return batch

    def close(self):
        for w in self._workers:
            w.stop()
        self._workers.clear()
        # close inline generators now (innermost first) so stream resources
        # (native reader threads, decode pools) unwind while the interpreter
        # is still fully alive, not at GC-at-exit time
        if getattr(self, "_inline", None) is not None:
            self._inline.close()
            self._inline = None
        if getattr(self, "_stream", None) is not None:
            self._stream.close()
            self._stream = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass


def valid_loader(
    cfg: DataConfig,
    batch_size: int,
    *,
    process_index: int = 0,
    process_count: int = 1,
) -> Iterator[dict[str, np.ndarray]]:
    """Fresh sequential eval iterator (construct per evaluation). With
    ``cfg.valid_cache`` set, the first pass populates the on-disk sample
    cache and every later pass streams from it without touching the shards."""
    if cfg.valid_cache:
        from jumbo_mae_tpu_tpu.data.valcache import ValidSampleCache

        cache = ValidSampleCache(
            cfg.valid_cache,
            key_fields={
                "shards": expand_shards(cfg.valid_shards),
                "image_size": cfg.image_size,
                "test_crop_ratio": cfg.test_crop_ratio,
                "process_index": process_index,
                "process_count": process_count,
            },
            image_size=cfg.image_size,
        )
        if cache.complete():
            stream = cache.read()
        else:
            stream = cache.capture(
                valid_sample_stream(
                    cfg, process_index=process_index, process_count=process_count
                )
            )
    else:
        stream = valid_sample_stream(
            cfg, process_index=process_index, process_count=process_count
        )
    return batch_valid_samples(stream, batch_size, cfg.image_size)


def split_for_accum(batch: dict, grad_accum: int) -> dict:
    """Reshape (B, ...) leaves to (accum, B/accum, ...) for the scan-based
    accumulation step."""
    if grad_accum <= 1:
        return batch
    return {
        k: v.reshape(grad_accum, v.shape[0] // grad_accum, *v.shape[1:])
        for k, v in batch.items()
    }


def prefetch_to_device(it: Iterator[dict], sharding, buffer_size: int = 2) -> Iterator[dict]:
    """Double-buffered host→device transfer: keep ``buffer_size`` batches in
    flight as sharded device arrays so the copy overlaps the previous step's
    compute. With a multi-process mesh, per-host batches are the local stripe
    of the global batch (``jax.make_array_from_process_local_data``)."""
    import jax

    from jumbo_mae_tpu_tpu.obs.trace import SPAN_H2D, span_timer

    def put(batch):
        try:
            return jax.tree_util.tree_map(
                lambda x: jax.make_array_from_process_local_data(sharding, x), batch
            )
        except ValueError:
            return jax.device_put(batch, sharding)

    # the host's share of a transfer (the copy itself is asynchronous)
    sp_h2d = span_timer(SPAN_H2D)
    pending: list = []
    for batch in it:
        with sp_h2d:
            pending.append(put(batch))
        if len(pending) > buffer_size:
            yield pending.pop(0)
    yield from pending
