"""Replicated serving tier contracts (infer/replicaset.py).

The invariants this tier stands on:

- **exactly-once**: every submitted future resolves exactly once — ok,
  ok-with-retry attribution, or a typed error — under replica crashes,
  hangs, requeues, zombie wakeups, and a racing close(); access-log rows
  match futures one-to-one by rid;
- **crash isolation**: a raising / fault-injected / hung replica loses
  only itself — its queued and in-flight requests ride to survivors with
  the failed replica excluded, attributed via ``retries``/``requeued_from``;
- **self-healing**: the supervisor restarts down replicas with capped
  exponential backoff, and the quorum circuit breaker (soft degraded in
  /healthz) opens below quorum and closes on recovery;
- **gated hot-swap**: a weight push is promoted only through the parity
  gate (feature cosine vs live weights) and a live canary window; a
  corrupt push or a breaching canary rolls back automatically with the
  previous weights restored and ``serve_swap_rollbacks_total`` bumped.

Stub engines keep the pool mechanics fast; two real-engine tests prove the
chaos/swap story end-to-end on ``InferenceEngine`` (restart warms from the
persistent executable cache with zero compiles; a corrupt checkpoint push
is rejected at parity while a faithful one promotes).
"""

import threading
import time
from pathlib import Path

import numpy as np
import pytest

from jumbo_mae_tpu_tpu import faults
from jumbo_mae_tpu_tpu.infer import (
    DeadlineExceededError,
    PoolUnhealthyError,
    QueueFullError,
    ReplicaSet,
    RetriesExhaustedError,
    ShutdownError,
    WeightSwapController,
)
from jumbo_mae_tpu_tpu.obs import AccessLog, RequestTracer
from jumbo_mae_tpu_tpu.obs.journal import read_journal
from jumbo_mae_tpu_tpu.obs.metrics import MetricsRegistry


@pytest.fixture
def fault_plan():
    yield faults.install_plan
    faults.clear_plan()


# ----------------------------------------------------------- stub harness


class StubEngine:
    """Versioned stand-in for InferenceEngine: swap/restore move a string."""

    def __init__(self, idx, version="v0"):
        self.idx = idx
        self.version = version

    def swap_weights(self, params, batch_stats=None, *, ckpt=""):
        snap = {"version": self.version}
        self.version = params
        return snap

    def restore_snapshot(self, snap):
        self.version = snap["version"]


def _img(v=0.0):
    return np.full((2, 2, 3), v, np.float32)


def run_echo(eng, batch, metas):
    return {"y": batch[:, 0, 0, 0].astype(np.float64)}


def _pool(run=run_echo, *, provider=None, tracer=None, **kw):
    reg = MetricsRegistry()
    kw.setdefault("replicas", 2)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_delay_ms", 1.0)
    kw.setdefault("supervise_interval_s", 0.02)
    rs = ReplicaSet(
        provider or (lambda idx: StubEngine(idx)),
        run,
        registry=reg,
        tracer=tracer,
        **kw,
    )
    return rs, reg


def _rows(log):
    log.close()
    return [e for e in read_journal(log.path) if e["type"] == "request"]


def _counter(reg, name, labels=(), **lbl):
    fam = reg.counter(name, "x", labels=labels)
    return (fam.labels(*lbl.values()) if labels else fam).value


# --------------------------------------------------------------- routing


def test_pool_routes_and_resolves():
    with _pool()[0] as rs:
        futs = [rs.submit(_img(i)) for i in range(20)]
        vals = sorted(f.result(timeout=5)["y"] for f in futs)
    assert vals == sorted(float(i) for i in range(20))
    st = rs.stats()
    assert st["healthy"] == 2
    assert sum(r["served"] for r in st["replicas"].values()) == 20
    # least-loaded routing actually spread the work
    assert all(r["served"] > 0 for r in st["replicas"].values())


def test_pool_shed_shutdown_and_validation(tmp_path):
    with pytest.raises(ValueError):
        ReplicaSet(lambda i: StubEngine(i), run_echo, replicas=0)
    gate = threading.Event()

    def run_block(eng, batch, metas):
        gate.wait(5.0)
        return {"y": np.zeros(len(batch))}

    rs, _ = _pool(run_block, replicas=1, max_queue=1)
    first = rs.submit(_img())  # occupies the worker
    time.sleep(0.05)
    held = rs.submit(_img())  # sits in the queue: depth == max_queue
    with pytest.raises(QueueFullError):
        rs.submit(_img())
    gate.set()
    assert first.result(timeout=5) is not None
    assert held.result(timeout=5) is not None
    rs.close()
    with pytest.raises(ShutdownError):
        rs.submit(_img())


def test_close_resolves_everything_bounded():
    """A wedged replica cannot hang close(): its requests are swept with
    ShutdownError inside the join bound."""
    gate = threading.Event()

    def run_wedge(eng, batch, metas):
        gate.wait(30.0)  # simulates a stuck predict
        return {"y": np.zeros(len(batch))}

    rs, reg = _pool(run_wedge, replicas=1, hang_timeout_s=60.0)
    futs = [rs.submit(_img()) for _ in range(6)]
    time.sleep(0.05)
    t0 = time.monotonic()
    rs.close(timeout_s=0.5)
    assert time.monotonic() - t0 < 5.0
    for f in futs:
        assert f.done()
        assert isinstance(f.exception(timeout=0), ShutdownError)
    gate.set()


# ------------------------------------------------------- crash isolation


def test_crash_requeues_to_survivor_with_attribution(tmp_path):
    """r1 always raises: every request still resolves ok on r0, with the
    retry attributed to r1 in the access log and metrics."""
    log = AccessLog(tmp_path / "access")
    reg = MetricsRegistry()
    tracer = RequestTracer(registry=reg, access_log=log)

    def run(eng, batch, metas):
        if eng.idx == 1:
            raise RuntimeError("boom")
        return {"y": batch[:, 0, 0, 0].astype(np.float64)}

    rs = ReplicaSet(
        lambda i: StubEngine(i), run, replicas=2, max_batch=4,
        max_delay_ms=1.0, registry=reg, tracer=tracer,
        restart_backoff_s=30.0,  # keep r1 down for the whole test
        supervise_interval_s=0.02,
    )
    futs = [rs.submit(_img(i)) for i in range(16)]
    for f in futs:
        assert f.result(timeout=5) is not None
    rs.close()
    rows = _rows(log)
    assert len(rows) == 16
    assert all(r["outcome"] == "ok" for r in rows)
    retried = [r for r in rows if r.get("retries")]
    assert retried, "some requests must have routed to r1 first"
    assert all(r["requeued_from"] == "r1" for r in retried)
    assert all(r["replica"] == "r0" for r in retried)
    assert _counter(reg, "serve_replica_requeued_total",
                    labels=("replica",), replica="r1") == len(retried)
    assert _counter(reg, "serve_replica_crashes_total",
                    labels=("replica", "kind"), r="r1", k="crash") >= 1


def test_retries_exhausted_typed_error():
    def run(eng, batch, metas):
        raise RuntimeError("always")

    rs, reg = _pool(run, replicas=2, max_retries=0, restart_backoff_s=30.0)
    f = rs.submit(_img())
    with pytest.raises(RetriesExhaustedError):
        f.result(timeout=5)
    rs.close()


def test_pool_unhealthy_when_every_replica_excluded():
    def run(eng, batch, metas):
        raise RuntimeError("always")

    rs, reg = _pool(run, replicas=2, max_retries=5, restart_backoff_s=30.0)
    f = rs.submit(_img())
    with pytest.raises(PoolUnhealthyError):
        f.result(timeout=5)
    # ...and a fresh submit against a fully-down pool is refused up front
    time.sleep(0.1)
    with pytest.raises(PoolUnhealthyError):
        rs.submit(_img())
    rs.close()


def test_restart_backoff_recovery_and_generation():
    crashed = threading.Event()

    def run(eng, batch, metas):
        if eng.idx == 0 and not crashed.is_set():
            crashed.set()
            raise RuntimeError("first batch dies")
        return {"y": np.zeros(len(batch))}

    rs, reg = _pool(run, replicas=1, restart_backoff_s=0.05, max_retries=0)
    with pytest.raises(RetriesExhaustedError):
        rs.submit(_img()).result(timeout=5)
    restarts = lambda: _counter(reg, "serve_replica_restarts_total",
                                labels=("replica",), replica="r0")
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        # "up" alone also holds before the crash is booked (the future fails
        # first); and the supervisor installs and starts the new incarnation
        # before it books the restart, so the counter is waited for too
        if (rs.generation(0) == 1 and rs.stats()["replicas"]["r0"]["state"] == "up"
                and restarts() == 1):
            break
        time.sleep(0.02)
    assert rs.generation(0) == 1  # new incarnation
    assert rs.submit(_img()).result(timeout=5) is not None
    assert restarts() == 1
    rs.close()


def test_restart_provider_failure_backs_off_then_recovers():
    builds = {"n": 0}

    def provider(idx):
        builds["n"] += 1
        if builds["n"] in (2, 3):  # the first two rebuilds fail
            raise RuntimeError("provider down")
        return StubEngine(idx)

    first = threading.Event()

    def run(eng, batch, metas):
        if not first.is_set():
            first.set()
            raise RuntimeError("die once")
        return {"y": np.zeros(len(batch))}

    rs, reg = _pool(run, provider=provider, replicas=1,
                    restart_backoff_s=0.03, max_retries=0)
    with pytest.raises(RetriesExhaustedError):
        rs.submit(_img()).result(timeout=5)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if rs.stats()["replicas"]["r0"]["state"] == "up":
            break
        time.sleep(0.02)
    assert rs.stats()["replicas"]["r0"]["state"] == "up"
    assert _counter(reg, "serve_replica_crashes_total",
                    labels=("replica", "kind"), r="r0",
                    k="restart_error") == 2
    assert rs.submit(_img()).result(timeout=5) is not None
    rs.close()


def test_quorum_breaker_opens_and_closes():
    healthy_again = threading.Event()

    def run(eng, batch, metas):
        if eng.idx == 1 and not healthy_again.is_set():
            raise RuntimeError("r1 sick")
        return {"y": np.zeros(len(batch))}

    rs, reg = _pool(run, replicas=2, quorum=2, restart_backoff_s=0.05,
                    max_retries=2)
    assert not rs.degraded()
    futs = [rs.submit(_img()) for _ in range(8)]
    for f in futs:
        f.result(timeout=5)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not rs.degraded():
        time.sleep(0.01)
    assert rs.degraded()  # healthy=1 < quorum=2 while r1 is down
    g = reg.gauge("serve_replica_breaker_open", "x")
    assert g.value == 1
    healthy_again.set()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and rs.degraded():
        time.sleep(0.02)
    assert not rs.degraded()
    assert g.value == 0
    assert _counter(reg, "serve_replica_breaker_trips_total") >= 1
    rs.close()


def test_hang_detected_requeued_and_zombie_loses_settle(tmp_path):
    """A hung predict is declared dead by the supervisor and its in-flight
    requests rescued onto the survivor; when the zombie thread finally
    wakes, it loses the settle race — no double resolution, no extra
    access-log row."""
    log = AccessLog(tmp_path / "access")
    reg = MetricsRegistry()
    tracer = RequestTracer(registry=reg, access_log=log)
    hang = threading.Event()

    def run(eng, batch, metas):
        if eng.idx == 0 and not hang.is_set():
            hang.set()
            time.sleep(1.2)  # >> hang_timeout_s
        return {"y": batch[:, 0, 0, 0].astype(np.float64)}

    rs = ReplicaSet(
        lambda i: StubEngine(i), run, replicas=2, max_batch=2,
        max_delay_ms=1.0, registry=reg, tracer=tracer,
        hang_timeout_s=0.15, supervise_interval_s=0.03,
        restart_backoff_s=30.0,
    )
    futs = [rs.submit(_img(i)) for i in range(8)]
    vals = [f.result(timeout=10)["y"] for f in futs]
    assert sorted(vals) == sorted(float(i) for i in range(8))
    time.sleep(1.3)  # let the zombie wake and try to re-resolve
    rs.close()
    rows = _rows(log)
    assert len(rows) == 8  # exactly one row per request, zombie added none
    assert all(r["outcome"] == "ok" for r in rows)
    rescued = [r for r in rows if r.get("requeued_from") == "r0"]
    assert rescued, "the hung batch must have been rescued"
    assert _counter(reg, "serve_replica_crashes_total",
                    labels=("replica", "kind"), r="r0", k="hang") == 1


def test_late_deadline_after_admission_is_late_not_ok(tmp_path):
    log = AccessLog(tmp_path / "access")
    reg = MetricsRegistry()
    tracer = RequestTracer(registry=reg, access_log=log)

    def run(eng, batch, metas):
        time.sleep(0.2)
        return {"y": np.zeros(len(batch))}

    rs = ReplicaSet(
        lambda i: StubEngine(i), run, replicas=1, max_batch=4,
        max_delay_ms=1.0, registry=reg, tracer=tracer,
    )
    f = rs.submit(_img(), deadline_ms=50.0)
    with pytest.raises(DeadlineExceededError):
        f.result(timeout=5)
    rs.close()
    rows = _rows(log)
    assert [r["outcome"] for r in rows] == ["late"]
    assert _counter(reg, "infer_requests_late_total") == 1


# -------------------------------------------- satellite: exactly-once storm


def test_stress_mid_stream_kill_every_future_exactly_once(tmp_path):
    """8 threads x 40 requests against a 3-replica pool while r1 is killed
    mid-stream through the ``serve.replica`` fault site: every future
    resolves exactly once (ok, retried-ok, or typed error), access-log
    rows match futures 1:1 by rid, and teardown joins bounded."""
    faults.install_plan("serve.replica:raise(RuntimeError)@key~r1")
    try:
        log = AccessLog(tmp_path / "access")
        reg = MetricsRegistry()
        tracer = RequestTracer(registry=reg, access_log=log)

        def run(eng, batch, metas):
            time.sleep(0.002)
            return {"y": batch[:, 0, 0, 0].astype(np.float64)}

        rs = ReplicaSet(
            lambda i: StubEngine(i), run, replicas=3, max_batch=8,
            max_delay_ms=1.0, max_queue=None, registry=reg, tracer=tracer,
            restart_backoff_s=0.05, supervise_interval_s=0.02,
        )
        futures, submit_errors = [], []
        lock = threading.Lock()
        n_threads, per_thread = 8, 40

        def client(tid):
            rng = np.random.RandomState(tid)
            for i in range(per_thread):
                dl = None if i % 3 else float(rng.uniform(50.0, 500.0))
                try:
                    f = rs.submit(_img(tid), deadline_ms=dl)
                except (QueueFullError, PoolUnhealthyError,
                        ShutdownError) as e:
                    with lock:
                        submit_errors.append(e)
                else:
                    with lock:
                        futures.append(f)
                if i % 16 == 15:
                    time.sleep(0.001)

        threads = [
            threading.Thread(target=client, args=(t,))
            for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t0 = time.monotonic()
        for f in futures:
            f.result(timeout=30) if f.exception(timeout=30) is None else None
        rs.close()
        assert time.monotonic() - t0 < 60.0  # bounded join

        ok = retried_ok = typed = 0
        for f in futures:
            assert f.done(), "a future was left unresolved"
            exc = f.exception(timeout=0)
            if exc is None:
                ok += 1
            else:
                assert isinstance(
                    exc,
                    (DeadlineExceededError, RetriesExhaustedError,
                     PoolUnhealthyError, ShutdownError),
                ), f"untyped failure leaked: {exc!r}"
                typed += 1
        assert ok > 0
        rows = _rows(log)
        assert len(rows) == len(futures) + len(submit_errors)
        rids = [r["rid"] for r in rows]
        assert len(set(rids)) == len(rids)
        by_rid = {r["rid"]: r for r in rows}
        for f in futures:
            row = by_rid[f.rid]
            if f.exception(timeout=0) is None:
                assert row["outcome"] == "ok"
                if row.get("retries"):
                    retried_ok += 1
                    assert "r1" in row["requeued_from"]
            else:
                assert row["outcome"] in ("deadline", "late", "aborted",
                                          "shutdown")
        assert retried_ok > 0, "the kill must have forced retried-ok rows"
    finally:
        faults.clear_plan()


# ------------------------------------------------------------- hot swap


def _swap_rig(run=None, *, features=None, replicas=3, **ctl_kw):
    reg = MetricsRegistry()
    rs = ReplicaSet(
        lambda i: StubEngine(i),
        run or (lambda eng, batch, metas: {"y": np.zeros(len(batch))}),
        replicas=replicas, max_batch=4, max_delay_ms=1.0, registry=reg,
        supervise_interval_s=0.02,
    )

    def default_features(eng, images):
        f = np.ones((len(images), 8))
        if isinstance(eng.version, str) and "bad" in eng.version:
            f[:, ::2] = -1.0  # direction flip: cosine collapses
        return f

    ctl_kw.setdefault("restore_fn", lambda p: (Path(p).name, None))
    ctl_kw.setdefault("features_fn", features or default_features)
    ctl_kw.setdefault("parity_images", np.zeros((4, 2, 2, 3), np.uint8))
    ctl_kw.setdefault("canary_requests", 2)
    ctl_kw.setdefault("canary_timeout_s", 3.0)
    ctl = WeightSwapController(rs, registry=reg, **ctl_kw)
    return rs, ctl, reg


def _bg_traffic(rs, stop, deadline_ms=None):
    def loop():
        while not stop.is_set():
            try:
                rs.submit(_img(), deadline_ms=deadline_ms).result(timeout=5)
            except Exception:
                pass
            time.sleep(0.005)

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    return t


def test_swap_promotes_under_load_and_restarts_use_new_weights():
    promoted = []
    rs, ctl, reg = _swap_rig(on_promote=promoted.append)
    stop = threading.Event()
    t = _bg_traffic(rs, stop)
    try:
        rep = ctl.swap("/push/v1")
        assert rep["verdict"] == "promoted"
        assert rep["parity"]["within_tolerance"]
        assert rep["canary_eval"]["requests"] >= 2
        assert [rs.replica(i).engine.version for i in range(3)] == ["v1"] * 3
        assert promoted == ["/push/v1"]
        assert _counter(reg, "serve_swap_promoted_total") == 1
        assert _counter(reg, "serve_swap_rollbacks_total") == 0
    finally:
        stop.set()
        t.join(timeout=5)
        rs.close()


def test_swap_parity_failure_rolls_back_all_weights():
    rs, ctl, reg = _swap_rig()
    stop = threading.Event()
    t = _bg_traffic(rs, stop)
    try:
        rep = ctl.swap("/push/vbad")
        assert rep["verdict"] == "rolled_back"
        assert rep["stage"] == "parity"
        assert not rep["parity"]["within_tolerance"]
        # nothing kept the bad weights; traffic never saw them routable
        assert [rs.replica(i).engine.version for i in range(3)] == ["v0"] * 3
        assert _counter(reg, "serve_swap_rollbacks_total") == 1
        assert _counter(reg, "serve_swap_promoted_total") == 0
        # and the pool still serves after the rollback
        assert rs.submit(_img()).result(timeout=5) is not None
    finally:
        stop.set()
        t.join(timeout=5)
        rs.close()


def test_swap_canary_breach_rolls_back():
    """Parity passes (same feature direction) but the new weights are slow
    enough that canary traffic goes late — the burn-rate window must veto
    the promotion and restore the old weights."""

    def run(eng, batch, metas):
        if eng.version == "vslow":
            time.sleep(0.12)
        return {"y": np.zeros(len(batch))}

    rs, ctl, reg = _swap_rig(
        run, features=lambda eng, images: np.ones((len(images), 8)),
        canary_slo="success_rate>=0.99", canary_requests=4,
        canary_timeout_s=5.0,
    )
    stop = threading.Event()
    t = _bg_traffic(rs, stop, deadline_ms=60.0)
    try:
        rep = ctl.swap("/push/vslow")
        assert rep["verdict"] == "rolled_back"
        assert rep["stage"] == "canary"
        assert [rs.replica(i).engine.version for i in range(3)] == ["v0"] * 3
        assert _counter(reg, "serve_swap_rollbacks_total") == 1
    finally:
        stop.set()
        t.join(timeout=5)
        rs.close()


def test_swap_rejected_on_restore_error():
    def restore(path):
        raise FileNotFoundError(path)

    rs, ctl, reg = _swap_rig(restore_fn=restore)
    try:
        rep = ctl.swap("/push/missing")
        assert rep["verdict"] == "rejected"
        assert rep["stage"] == "restore"
        assert _counter(reg, "serve_swap_rejected_total") == 1
        assert _counter(reg, "serve_swap_rollbacks_total") == 0
        assert [rs.replica(i).engine.version for i in range(3)] == ["v0"] * 3
    finally:
        rs.close()


def test_swap_ckpt_load_corrupt_fault_site(fault_plan):
    """GRAFT_FAULTS ``ckpt.load:corrupt`` perturbs the restored tree, and
    the parity gate catches it — the CI chaos-smoke scenario in miniature.
    The stub features read the tree, so corruption shows up as a direction
    change."""
    fault_plan("ckpt.load:corrupt(4)")

    def restore(path):
        return {"w": {"kernel": np.ones((4, 2), np.float32)}}, None

    def features(eng, images):
        v = eng.version
        if isinstance(v, dict):
            leaf = np.asarray(v["w"]["kernel"], np.float64)
            return np.tile(leaf.reshape(-1), (len(images), 1))
        return np.ones((len(images), 8))

    rs, ctl, reg = _swap_rig(restore_fn=restore, features=features)
    # parity ref comes from the live stub (all-ones); the corrupted tree's
    # leaves are scaled to -3x-0.5 so the candidate direction flips
    ctl.parity_images = np.zeros((4, 2, 2, 3), np.uint8)
    try:
        rep = ctl.swap("/push/corrupt")
        assert rep["verdict"] == "rolled_back"
        assert rep["stage"] == "parity"
        assert _counter(reg, "serve_swap_rollbacks_total") == 1
    finally:
        rs.close()


# ------------------------------------------------------- real engine e2e


def tiny_cfg():
    from jumbo_mae_tpu_tpu.config import load_config

    recipe = Path(__file__).resolve().parent.parent / "recipes" / "smoke_cpu.yaml"
    return load_config(
        recipe,
        [
            "model.overrides.dtype=float32",
            "model.dec_layers=1",
            "model.dec_dim=32",
            "model.dec_heads=2",
            "model.dec_dtype=float32",
        ],
    )


def _real_images(n, size=32):
    return (
        np.random.RandomState(0)
        .randint(0, 256, (n, size, size, 3))
        .astype(np.uint8)
    )


def test_real_engine_pool_crash_restart_warms_with_zero_compiles(
    tmp_path, fault_plan
):
    """Chaos proof on the real engine: kill r1's first predict through
    ``serve.replica``; every request still resolves ok, and the restarted
    replica comes up from the persistent executable cache with zero fresh
    compiles."""
    from jumbo_mae_tpu_tpu.infer import InferenceEngine

    cfg = tiny_cfg()
    wc = str(tmp_path / "wc")
    reg = MetricsRegistry()
    engines = {}

    def provider(idx):
        eng = InferenceEngine(cfg, max_batch=4, warm_cache=wc)
        eng.warmup(("features",))
        engines.setdefault(idx, []).append(eng)
        return eng

    fault_plan("serve.replica:raise(RuntimeError)@key~r1")

    def run(eng, batch, metas):
        return eng.predict(batch, task="features")

    rs = ReplicaSet(
        provider, run, replicas=2, max_batch=4, max_delay_ms=2.0,
        registry=reg, restart_backoff_s=0.05, supervise_interval_s=0.02,
    )
    try:
        futs = [rs.submit(img) for img in _real_images(8)]
        for f in futs:
            assert f.result(timeout=120) is not None
        faults.clear_plan()  # stop killing r1 so its restart sticks
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            st = rs.stats()["replicas"]["r1"]
            if st["state"] == "up" and rs.generation(1) >= 1:
                break
            time.sleep(0.05)
        assert rs.generation(1) >= 1
        restarted = engines[1][-1]
        assert len(engines[1]) >= 2
        # the warm restart compiled nothing: every executable came from disk
        assert sum(restarted.compile_counts.values()) == 0
        assert sum(restarted.warm_hits.values()) > 0
        # and it serves: force traffic through r1 only
        rs.pause(0)
        assert rs.submit(_real_images(1)[0]).result(timeout=120) is not None
    finally:
        rs.close()


def test_real_engine_hot_swap_good_promotes_corrupt_rolls_back(tmp_path):
    """End-to-end swap on the real engine: a faithful checkpoint push
    promotes with parity cosine ~1 and zero failed requests; a corrupt
    push (``ckpt.load:corrupt``) is rolled back at the parity gate."""
    from jumbo_mae_tpu_tpu.infer import InferenceEngine
    from jumbo_mae_tpu_tpu.train.checkpoint import export_params_msgpack

    cfg = tiny_cfg()
    reg = MetricsRegistry()

    def provider(idx):
        return InferenceEngine(cfg, max_batch=4, warm_cache=False)

    def run(eng, batch, metas):
        return eng.predict(batch, task="features")

    rs = ReplicaSet(
        provider, run, replicas=2, max_batch=4, max_delay_ms=2.0,
        registry=reg, supervise_interval_s=0.02,
    )
    try:
        eng0 = rs.replica(0).engine
        # build the features task, then export its live weights — the
        # "faithful push" is bit-identical to what is already serving
        eng0.predict(_real_images(1), task="features")
        ckpt = tmp_path / "push" / "weights.msgpack"
        ckpt.parent.mkdir()
        export_params_msgpack(
            eng0._tasks["features"]["variables"]["params"], ckpt
        )
        probe = _real_images(4)
        ctl = WeightSwapController(
            rs, parity_images=probe, canary_requests=2,
            canary_timeout_s=10.0, registry=reg,
        )
        stop = threading.Event()
        failures = []

        def loop():
            while not stop.is_set():
                try:
                    rs.submit(_real_images(1)[0]).result(timeout=60)
                except Exception as e:  # pragma: no cover - would fail below
                    failures.append(e)
                time.sleep(0.01)

        t = threading.Thread(target=loop, daemon=True)
        t.start()
        try:
            rep = ctl.swap(str(ckpt))
        finally:
            stop.set()
            t.join(timeout=30)
        assert rep["verdict"] == "promoted", rep
        assert rep["parity"]["cosine_min"] > 0.999
        assert not failures  # a good swap under load drops zero requests

        faults.install_plan("ckpt.load:corrupt(6)")
        try:
            rep2 = ctl.swap(str(ckpt))
        finally:
            faults.clear_plan()
        assert rep2["verdict"] == "rolled_back", rep2
        assert rep2["stage"] == "parity"
        assert _counter(reg, "serve_swap_rollbacks_total") == 1
        # the rolled-back pool still serves correct features
        assert rs.submit(_real_images(1)[0]).result(timeout=60) is not None
    finally:
        rs.close()


def test_swap_headroom_rejection():
    """A push whose double-buffer footprint (new tree + rollback snapshot)
    doesn't fit host memory is rejected up front — before any parity probe,
    canary pick, or weight flip — and a broken probe never blocks a swap."""
    calls = []

    def tight(need):
        calls.append(need)
        return "needs 512 KiB but only 1 KiB of host memory is safely available"

    params = {"w": np.zeros((256, 256), np.float32)}
    rs, ctl, reg = _swap_rig(
        restore_fn=lambda p: (params, None), headroom_fn=tight
    )
    try:
        rep = ctl.swap("/push/v1")
        assert rep["verdict"] == "rejected" and rep["stage"] == "headroom"
        assert "512 KiB" in rep["error"]
        assert calls == [2 * 256 * 256 * 4]  # double-buffered tree bytes
        assert [rs.replica(i).engine.version for i in range(3)] == ["v0"] * 3
        assert _counter(reg, "serve_swap_rejected_total") == 1
        # a probe that raises must not veto the swap
        rs2, ctl2, _ = _swap_rig(headroom_fn=lambda need: 1 / 0)
        try:
            assert ctl2.swap("/push/v1")["verdict"] == "promoted"
        finally:
            rs2.close()
    finally:
        rs.close()


# ------------------------------------------------------------- preemption


def test_preempt_drains_zero_dropped_then_restarts(tmp_path):
    """A preemption notice mid-traffic: the replica leaves routing, every
    request it held resolves ok (zero dropped), the journal carries
    ``replica_preempted``, the metric bumps, and the supervisor brings the
    capacity back without a failure-count penalty."""
    log = AccessLog(tmp_path / "access")
    reg = MetricsRegistry()
    tracer = RequestTracer(registry=reg, access_log=log)

    def run(eng, batch, metas):
        time.sleep(0.005)
        return {"y": batch[:, 0, 0, 0].astype(np.float64)}

    rs = ReplicaSet(
        lambda i: StubEngine(i), run, replicas=2, max_batch=4,
        max_delay_ms=1.0, supervise_interval_s=0.02,
        restart_backoff_s=0.05, registry=reg, tracer=tracer,
    )
    futs, stop = [], threading.Event()

    def pump():
        for i in range(150):
            if stop.is_set():
                return
            try:
                futs.append(rs.submit(_img(i)))
            except QueueFullError:
                pass
            time.sleep(0.002)

    t = threading.Thread(target=pump)
    t.start()
    time.sleep(0.05)
    assert rs.preempt(1) is True
    t.join()
    for f in futs:
        assert f.result(timeout=10) is not None  # zero dropped
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if rs.stats()["replicas"]["r1"]["state"] == "up":
            break
        time.sleep(0.02)
    st = rs.stats()["replicas"]["r1"]
    assert st["state"] == "up"
    assert st["gen"] == 1  # a fresh incarnation took the slot
    assert st["restarts"] == 0  # preemption is not a failure
    assert _counter(reg, "serve_replica_preempted_total",
                    labels=("replica",), replica="r1") == 1
    rs.close()
    events = [e["type"] for e in read_journal((tmp_path / "access"))]
    assert "replica_preempted" in events


def test_preempt_rejects_down_restarting_and_closed():
    rs, _ = _pool(replicas=2)
    assert rs.preempt(7) is False  # out of range
    with rs._state_lock:
        rs._slots[1].state = "down"
    assert rs.preempt(1) is False  # already down
    with rs._state_lock:
        rs._slots[1].state = "up"
    rs.close()
    assert rs.preempt(0) is False  # closed pool


def test_serve_preempt_fault_site_drains_via_supervisor(fault_plan, tmp_path):
    """``serve.preempt:raise@n=1`` fires on the supervisor's second site
    visit (r1 on the first tick): the replica drains exactly as a manual
    preempt() would, under the same zero-drop contract."""
    log = AccessLog(tmp_path / "access")
    reg = MetricsRegistry()
    tracer = RequestTracer(registry=reg, access_log=log)
    fault_plan("serve.preempt:raise@n=1")
    rs = ReplicaSet(
        lambda i: StubEngine(i), run_echo, replicas=2, max_batch=4,
        max_delay_ms=1.0, supervise_interval_s=0.02,
        restart_backoff_s=0.05, registry=reg, tracer=tracer,
    )
    futs = [rs.submit(_img(i)) for i in range(30)]
    for f in futs:
        assert f.result(timeout=10) is not None
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if _counter(reg, "serve_replica_preempted_total",
                    labels=("replica",), replica="r1") == 1:
            break
        time.sleep(0.02)
    assert _counter(reg, "serve_replica_preempted_total",
                    labels=("replica",), replica="r1") == 1
    rs.close()
    events = [e["type"] for e in read_journal(tmp_path / "access")]
    assert "replica_preempted" in events


def test_close_during_restart_never_respawns_slot():
    """Regression for the close/restart race: a restart thread past its
    pre-build check must NOT install a new incarnation once close() has
    latched shutdown — the old code checked ``_closed`` only before taking
    the state lock, so a slot could respawn (live thread, live engine)
    after the close sweep."""
    built = threading.Event()
    release = threading.Event()
    crashed = threading.Event()

    def provider(idx):
        if crashed.is_set():
            # the restart build: park here until close() has begun
            built.set()
            assert release.wait(10.0)
        return StubEngine(idx)

    victim = {}

    def run(eng, batch, metas):
        if not crashed.is_set():
            crashed.set()
            victim["idx"] = eng.idx
            raise RuntimeError("die once")
        return {"y": np.zeros(len(batch))}

    rs, _ = _pool(run, provider=provider, replicas=2,
                  restart_backoff_s=0.01, max_retries=1)
    rs.submit(_img()).result(timeout=5)  # retried onto the survivor
    assert built.wait(10.0)  # the restart thread is inside the provider
    closer = threading.Thread(target=rs.close)
    closer.start()
    time.sleep(0.1)  # close() is joining; the latch is set
    release.set()  # let the restart thread race the install
    closer.join(timeout=10.0)
    assert not closer.is_alive()
    # the slot must not have respawned: no running worker thread, and the
    # incarnation still the crashed gen-0 one (never replaced)
    rep = rs.replica(victim["idx"])
    assert rep.gen == 0
    assert rep.thread is None or not rep.thread.is_alive()
    with pytest.raises(ShutdownError):
        rs.submit(_img())


# ------------------------------------------------- atomic dispatch groups


def test_submit_group_boundaries_never_merge_in_one_flush():
    """The worker must not coalesce across ``submit_group`` boundaries:
    the occupancy (and, packed, the token geometry) the scheduler
    assembled is what the replica runs. Two groups queued back-to-back on
    one busy replica flush as two batches, never one merged batch — even
    though max_batch would allow the merge."""
    from concurrent.futures import wait

    gate = threading.Event()
    flushes = []

    def run_gated(eng, batch, metas):
        gate.wait(timeout=10)
        flushes.append([im.shape[0] for im in batch] if isinstance(
            batch, list) else [batch.shape[1]] * batch.shape[0])
        return {"y": np.zeros(len(metas) if isinstance(batch, list)
                              else batch.shape[0])}

    rs, reg = _pool(run_gated, replicas=1, max_batch=16, max_delay_ms=1.0)
    try:
        now = time.monotonic()
        # park the worker on a decoy so both groups are queued before any
        # coalescing loop runs
        decoy = rs.submit(_img())
        time.sleep(0.05)
        g1 = rs.submit_group([(np.full((4, 4, 3), 1.0, np.float32),
                               now + 30.0, None, None)] * 2)
        g2 = rs.submit_group([(np.full((4, 4, 3), 2.0, np.float32),
                               now + 30.0, None, None)] * 3)
        gate.set()
        done, _ = wait([decoy] + g1 + g2, timeout=10)
        assert len(done) == 6
    finally:
        rs.close()
    # three flushes: the decoy, then each group intact — never [2+3] merged
    assert [len(f) for f in flushes] == [1, 2, 3]


def test_worker_carry_lookahead_is_not_lost_on_exit():
    """A worker that peeked past a group boundary holds a carry record;
    close() (or a crash) must requeue/resolve it, never orphan it."""
    gate = threading.Event()

    def run_gated(eng, batch, metas):
        gate.wait(timeout=10)
        n = len(metas)
        return {"y": np.zeros(n)}

    rs, reg = _pool(run_gated, replicas=1, max_batch=16, max_delay_ms=1.0)
    now = time.monotonic()
    decoy = rs.submit(_img())
    time.sleep(0.05)
    g1 = rs.submit_group([(_img(1.0), now + 30.0, None, None)] * 2)
    g2 = rs.submit_group([(_img(2.0), now + 30.0, None, None)] * 2)
    gate.set()
    rs.close()  # drain: everything queued (carry included) must resolve
    for f in [decoy] + g1 + g2:
        assert f.done()
        # ok or shutdown are both legal under close(); lost/hung is not
        exc = f.exception()
        assert exc is None or isinstance(exc, ShutdownError)
