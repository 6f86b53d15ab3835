"""Persistent, crash-safe cache of AOT-compiled serving executables.

A serving replica's startup cost is the per-(task, bucket) compile ladder —
seconds on CPU smoke, minutes for a real encoder at a full bucket set. The
engine already guarantees the *request path* never compiles; this module
makes the *warmup* free after the first process on a host: compiled
executables are serialized (``jax.experimental.serialize_executable``) to a
versioned on-disk cache and restarted replicas load them instead of
compiling.

Design constraints, in order:

- **A corrupt entry must never crash the process.** A writer killed
  mid-write must not leave a truncated serialized executable for XLA to
  deserialize: a sha256 digest over the payload is verified *before* any
  bytes reach XLA, writes are atomic (unique tmp + ``os.replace``), and
  any entry that fails the header,
  digest, unpickle, or XLA load is moved to ``quarantine/`` — kept for a
  postmortem, never retried.
- **Keyed so reuse is provably safe.** The entry name carries the model
  fingerprint (every architecture/config field the traced program depends
  on, plus jax/jaxlib versions, backend, and the device kind — on the CPU
  backend the host CPU fingerprint, since XLA:CPU executables embed machine
  features), the task, the bucket, the compute dtype, and the quant mode.
  Parameters are executable *arguments*,
  not constants, so different checkpoints of the same architecture share
  entries by construction — the engine keeps anything value-dependent
  (BatchNorm stats included) out of closure constants.
- **Concurrent processes race safely.** Writers use per-process unique tmp
  names; ``os.replace`` is atomic, last-writer-wins, and readers see either
  a complete old entry or a complete new one, never a partial write.

``python -m jumbo_mae_tpu_tpu.infer.warmcache`` is the restart probe: it
builds an engine against a cache dir, warms it, runs a hot-path batch, and
prints one JSON line with compile/hit counts and timings — bench_infer's
cold/warm A/B and CI's restart-reuses-warmcache assertion both drive it.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import sys
import time
import uuid
from pathlib import Path

from jumbo_mae_tpu_tpu.obs.journal import fsync_dir

# format version is part of MAGIC: bump it and every older entry misses
# cleanly (no attempt to parse an incompatible layout)
MAGIC = b"JWC1"
_DIGEST_LEN = 32  # sha256


def host_fingerprint() -> str:
    """Short stable hash of this host's CPU identity. Part of the entry key
    on the CPU backend only: XLA:CPU executables embed the compiling
    machine's CPU features, so an entry must never load on another CPU."""
    import platform

    parts = [platform.machine()]
    wanted = {"model name", "flags", "Features", "CPU implementer"}
    seen: set[str] = set()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in wanted and key not in seen:
                    seen.add(key)
                    parts.append(line.strip())
                if seen == wanted:
                    break
    except OSError:
        pass
    return hashlib.sha256("\n".join(parts).encode()).hexdigest()[:12]


def fingerprint(spec: dict) -> str:
    """Stable short hash of a JSON-able spec dict (the engine feeds every
    compile-relevant config field through this)."""
    blob = json.dumps(spec, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def entry_name(
    fp: str, task_key: str, bucket: int, dtype: str, quant: str | None
) -> str:
    """Filesystem-safe cache entry name — the (fingerprint, task, bucket,
    dtype, quant) key schema README documents."""
    safe = lambda s: re.sub(r"[^A-Za-z0-9_.-]", "_", str(s))  # noqa: E731
    return (
        f"{safe(fp)}-{safe(task_key)}-b{int(bucket)}"
        f"-{safe(dtype)}-{safe(quant or 'none')}.exe"
    )


class WarmCache:
    """One directory of serialized executables, with quarantine semantics.

    All failure paths degrade to a miss: the caller compiles as if the
    cache were cold. ``stats()`` plus the ``infer_warmcache_*`` counters
    expose what actually happened.

    ``quarantine/`` is bounded: entries beyond ``quarantine_keep`` (newest
    kept) or older than ``quarantine_max_age_s`` are deleted when the cache
    directory is claimed (construction) and after each new quarantine — a
    crash-looping replica that corrupts an entry per restart must not fill
    the disk with postmortem copies.
    """

    def __init__(
        self,
        root: str | os.PathLike,
        *,
        registry=None,
        quarantine_keep: int = 32,
        quarantine_max_age_s: float = 7 * 24 * 3600.0,
        cache_max_bytes: int = 0,
    ):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        if registry is None:
            from jumbo_mae_tpu_tpu.obs.metrics import get_registry

            registry = get_registry()
        self._m = registry.counter(
            "infer_warmcache_events_total",
            "warm-start executable cache events",
            labels=("event",),
        )
        self._m_pruned = registry.counter(
            "infer_warmcache_quarantine_pruned_total",
            "quarantined entries deleted by the count/age cap",
        )
        self._m_disk = registry.gauge(
            "infer_warmcache_disk_bytes",
            "on-disk bytes of main-dir cache entries + sidecars",
        )
        self.quarantine_keep = int(quarantine_keep)
        self.quarantine_max_age_s = float(quarantine_max_age_s)
        # optional byte bound on the MAIN dir (quarantine has its own
        # count/age cap): oldest-mtime entries and their sidecars are
        # deleted until the footprint fits. 0 = unbounded (historical).
        self.cache_max_bytes = int(cache_max_bytes)
        self.hits = 0
        self.misses = 0
        self.puts = 0
        self.put_errors = 0
        self.quarantined = 0
        self.quarantine_pruned = 0
        self.main_pruned = 0
        # claim-time sweep: whoever opens the cache dir pays the prune, so
        # the bound holds even if every previous process crashed mid-flight
        self._prune_quarantine()
        self._prune_main()

    # ------------------------------------------------------------------ io

    def get(self, name: str):
        """Load one executable, or None (miss / quarantined corrupt entry)."""
        path = self.root / name
        try:
            blob = path.read_bytes()
        except OSError:
            self.misses += 1
            self._m.labels("miss").inc()
            return None
        try:
            if len(blob) < len(MAGIC) + _DIGEST_LEN or blob[: len(MAGIC)] != MAGIC:
                raise ValueError("bad magic/header")
            digest = blob[len(MAGIC) : len(MAGIC) + _DIGEST_LEN]
            payload = blob[len(MAGIC) + _DIGEST_LEN :]
            if hashlib.sha256(payload).digest() != digest:
                raise ValueError("payload digest mismatch (truncated write?)")
            # the pickled in/out treedefs may reference QuantizedTensor;
            # importing quant registers the pytree node before unpickling
            from jumbo_mae_tpu_tpu.infer import quant as _quant  # noqa: F401
            from jax.experimental.serialize_executable import (
                deserialize_and_load,
            )

            import jax

            serialized, in_tree, out_tree = pickle.loads(payload)
            # the engine's executables run on the default device; without
            # execution_devices jax loads for EVERY local device, and on a
            # multi-chip host the first call fails for want of N shards
            ex = deserialize_and_load(
                serialized,
                in_tree,
                out_tree,
                execution_devices=jax.local_devices()[:1],
            )
        except Exception as e:  # noqa: BLE001 — any corruption is a miss
            self._quarantine(path, e)
            self.misses += 1
            self._m.labels("miss").inc()
            return None
        self.hits += 1
        self._m.labels("hit").inc()
        return ex

    def put(self, name: str, compiled, meta: dict | None = None) -> int:
        """Serialize + atomically publish one executable; best-effort (a
        full disk or an unserializable program must not fail serving).

        Returns the serialized blob size in bytes (0 on failure — callers
        that only care whether the put landed keep working, callers that
        gauge executable size get it for free). ``meta`` lands in a
        ``<name>.meta.json`` sidecar (compile wall time, cost analysis) so
        a warm-started process can credit what the hit saved it."""
        path = self.root / name
        tmp = None
        try:
            from jax.experimental.serialize_executable import serialize

            payload = pickle.dumps(serialize(compiled))
            blob = MAGIC + hashlib.sha256(payload).digest() + payload
            tmp = path.with_name(
                f".{name}.{os.getpid()}.{uuid.uuid4().hex[:8]}.tmp"
            )
            tmp.write_bytes(blob)
            os.replace(tmp, path)
            fsync_dir(self.root)  # rename alone is not durable over power loss
        except Exception as e:  # noqa: BLE001
            if tmp is not None:
                Path(tmp).unlink(missing_ok=True)
            self.put_errors += 1
            self._m.labels("put_error").inc()
            print(f"[warmcache] put({name}) failed: {e}", file=sys.stderr)
            return 0
        self.puts += 1
        self._m.labels("put").inc()
        self._put_meta(name, {"executable_bytes": len(blob), **(meta or {})})
        # re-enforce the byte bound (and refresh the disk gauge) after every
        # publish — the writer pays for its own growth
        self._prune_main()
        return len(blob)

    def _put_meta(self, name: str, meta: dict) -> None:
        """Atomic best-effort sidecar write; a corrupt/missing sidecar only
        loses metadata, never the executable."""
        path = self.root / f"{name}.meta.json"
        tmp = path.with_name(f".{path.name}.{os.getpid()}.{uuid.uuid4().hex[:8]}.tmp")
        try:
            tmp.write_text(json.dumps(meta, sort_keys=True, default=str))
            os.replace(tmp, path)
            fsync_dir(self.root)
        except Exception:  # noqa: BLE001
            Path(tmp).unlink(missing_ok=True)

    def entry_meta(self, name: str) -> dict | None:
        """The ``put()`` metadata sidecar for one entry, or None."""
        try:
            return json.loads((self.root / f"{name}.meta.json").read_text())
        except Exception:  # noqa: BLE001 — metadata is advisory
            return None

    def _quarantine(self, path: Path, err: Exception):
        """Move a bad entry aside — kept for postmortem, never re-read."""
        qdir = self.root / "quarantine"
        dst = qdir / f"{path.name}.{os.getpid()}.{uuid.uuid4().hex[:8]}"
        try:
            qdir.mkdir(exist_ok=True)
            os.replace(path, dst)
            # both directories changed; sync both or a crash can resurrect
            # the corrupt entry under its servable name
            fsync_dir(qdir)
            fsync_dir(self.root)
        except OSError:
            path.unlink(missing_ok=True)
        self.quarantined += 1
        self._m.labels("quarantined").inc()
        print(
            f"[warmcache] quarantined corrupt entry {path.name}: {err}",
            file=sys.stderr,
        )
        self._prune_quarantine()

    def _prune_quarantine(self) -> int:
        """Enforce the quarantine count/age cap; returns entries deleted.
        Newest entries win the count cap — the freshest corruption is the
        one a postmortem wants."""
        qdir = self.root / "quarantine"
        try:
            entries = sorted(
                ((p.stat().st_mtime, p) for p in qdir.iterdir() if p.is_file()),
                reverse=True,
            )
        except OSError:
            return 0
        now = time.time()
        pruned = 0
        for rank, (mtime, path) in enumerate(entries):
            if rank < self.quarantine_keep and now - mtime <= self.quarantine_max_age_s:
                continue
            try:
                path.unlink()
            except OSError:
                continue
            pruned += 1
        if pruned:
            self.quarantine_pruned += pruned
            self._m_pruned.inc(pruned)
        return pruned

    def disk_bytes(self) -> int:
        """Main-dir footprint in bytes (entries + sidecars + in-flight
        tmps; ``quarantine/`` excluded — it has its own count/age bound).
        The memory accountant's ``warmcache_disk`` component probe."""
        total = 0
        try:
            for p in self.root.iterdir():
                if not p.is_file():
                    continue
                try:
                    total += p.stat().st_size
                except OSError:
                    continue
        except OSError:
            return 0
        return total

    def _prune_main(self) -> int:
        """Enforce ``cache_max_bytes`` over the main dir, LRU by mtime:
        oldest entries (and their sidecars) are deleted until the footprint
        fits. Always refreshes ``infer_warmcache_disk_bytes``. Returns
        entries deleted."""
        pruned = 0
        if self.cache_max_bytes > 0:
            try:
                entries = sorted(
                    (p.stat().st_mtime, p) for p in self.root.glob("*.exe")
                )
            except OSError:
                entries = []
            total = self.disk_bytes()
            for _mtime, path in entries:
                if total <= self.cache_max_bytes:
                    break
                for victim in (path, self.root / f"{path.name}.meta.json"):
                    try:
                        size = victim.stat().st_size
                        victim.unlink()
                    except OSError:
                        continue
                    total -= size
                pruned += 1
                self._m.labels("pruned").inc()
            if pruned:
                self.main_pruned += pruned
        self._m_disk.set(self.disk_bytes())
        return pruned

    def stats(self) -> dict:
        return {
            "root": str(self.root),
            "entries": len(list(self.root.glob("*.exe"))),
            "disk_bytes": self.disk_bytes(),
            "hits": self.hits,
            "misses": self.misses,
            "puts": self.puts,
            "put_errors": self.put_errors,
            "quarantined": self.quarantined,
            "quarantine_pruned": self.quarantine_pruned,
            "main_pruned": self.main_pruned,
        }


# ------------------------------------------------------------- restart probe


def _probe_main(argv: list[str] | None = None) -> dict:
    """Restart probe: engine up against ``--dir``, warm, serve one hot batch,
    print a JSON line. Run twice against the same dir to measure cold vs
    warm start; the second run must report ``"compiles": 0``."""
    import argparse
    import time

    p = argparse.ArgumentParser(description=_probe_main.__doc__)
    p.add_argument("--dir", required=True, help="warmcache directory")
    p.add_argument("--recipe", default=None, help="YAML recipe (default: CPU smoke)")
    p.add_argument(
        "--task", choices=("features", "logits", "reconstruct"), default="features"
    )
    p.add_argument("--pool", choices=("cls", "gap", "tokens"), default="cls")
    p.add_argument("--ckpt", default="")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--buckets", type=int, nargs="*", default=None)
    p.add_argument("--quant", choices=("int8",), default=None)
    p.add_argument("--dtype", default=None)
    p.add_argument("--probe-images", type=int, default=3)
    p.add_argument("--out", default="", help="also write the JSON here")
    p.add_argument(
        "--set", dest="overrides", metavar="KEY.PATH=VALUE",
        nargs="*", action="extend", default=[],
    )
    args = p.parse_args(argv)

    import numpy as np

    from jumbo_mae_tpu_tpu.config import load_config
    from jumbo_mae_tpu_tpu.infer import InferenceEngine

    recipe = args.recipe
    if recipe is None:
        recipe = str(
            Path(__file__).resolve().parents[2] / "recipes" / "smoke_cpu.yaml"
        )
    cfg = load_config(recipe, args.overrides)

    t0 = time.perf_counter()
    engine = InferenceEngine(
        cfg,
        ckpt=args.ckpt,
        dtype=args.dtype,
        max_batch=args.max_batch,
        quant=args.quant,
        warm_cache=args.dir,
    )
    init_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    compiles = engine.warmup(
        (args.task,),
        pool=args.pool,
        buckets=tuple(args.buckets) if args.buckets else None,
    )
    warmup_s = time.perf_counter() - t1
    after_warm = sum(engine.compile_counts.values())
    images = (
        np.random.RandomState(0)
        .randint(0, 256, (args.probe_images, engine.image_size, engine.image_size, 3))
        .astype(np.uint8)
    )
    kw = {"pool": args.pool} if args.task == "features" else {}
    engine.predict(images, task=args.task, **kw)
    report = {
        "probe": "warmcache",
        "dir": args.dir,
        "task": args.task,
        "quant": args.quant,
        "init_s": round(init_s, 3),
        "warmup_s": round(warmup_s, 3),
        "compiles": compiles,
        "warm_hits": sum(engine.warm_hits.values()),
        "hot_path_compiles": sum(engine.compile_counts.values()) - after_warm,
        "executables": len(engine._exec),
        "warmcache": engine.warmcache.stats() if engine.warmcache else None,
    }
    line = json.dumps(report)
    print(line)
    if args.out:
        Path(args.out).write_text(line + "\n")
    return report


if __name__ == "__main__":
    _probe_main()
