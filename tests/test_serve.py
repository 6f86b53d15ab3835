"""Traffic-shaping tier contracts (serve/ + the pool's scaling surface).

What this tier must guarantee:

- **weighted admission**: token-bucket quotas shed a tenant that exceeds
  its contracted rate; under pool pressure the *low* priority classes
  shed first (scavenger at half load, batch at heavy load, interactive
  only at a genuinely full queue) — never the other way around;
- **continuous batching**: concurrent arrivals coalesce into one
  dispatched group that lands on ONE replica as one flush; partial
  batches dispatch bucket-aligned (power-of-2, zero pad rows) when no
  due entry would be held back; over-full accumulators admit the highest
  class first (the priority queue-jump);
- **exactly-once through the stack**: every future from
  ``ContinuousScheduler.submit`` resolves exactly once — ok, typed shed,
  deadline, or shutdown — under replica crash storms, priority
  reordering, racing scale-downs, and close();
- **elastic pool**: ``scale_to`` adds/removes replica slots live;
  scale-down drains (never kills in-flight work) and refuses rather than
  waits forever; the autoscaler steps up immediately on demand/burn and
  down conservatively (``down_hold``), journaling every resize;
- **occupancy telemetry is honest**: ``stats()["batch_occupancy"]`` is a
  windowed EWMA over recent flushes, not whatever the last flush alone
  happened to be (the regression that motivated ``OccupancyWindow``).
"""

import threading
import time
from concurrent.futures import wait

import numpy as np
import pytest

from jumbo_mae_tpu_tpu import faults
from jumbo_mae_tpu_tpu.infer import (
    DeadlineExceededError,
    MicroBatcher,
    QueueFullError,
    ReplicaSet,
    ShutdownError,
)
from jumbo_mae_tpu_tpu.infer.batching import OccupancyWindow
from jumbo_mae_tpu_tpu.obs import AccessLog, RequestTracer
from jumbo_mae_tpu_tpu.obs.journal import read_journal
from jumbo_mae_tpu_tpu.obs.metrics import MetricsRegistry
from jumbo_mae_tpu_tpu.serve import (
    AdmissionController,
    Autoscaler,
    ContinuousScheduler,
    TenantPressureError,
    TenantQuotaError,
    TenantSpec,
    parse_tenants,
    roofline_capacity,
)
from jumbo_mae_tpu_tpu.serve.scheduler import floor_bucket


@pytest.fixture
def fault_plan():
    yield faults.install_plan
    faults.clear_plan()


def _img(v=0.0):
    return np.full((2, 2, 3), v, np.float32)


def run_echo(eng, batch, metas):
    return {"y": batch[:, 0, 0, 0].astype(np.float64)}


class StubEngine:
    def __init__(self, idx):
        self.idx = idx


def make_pool(reg, tracer=None, *, replicas=2, run=run_echo, **kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("max_delay_ms", 1.0)
    kw.setdefault("supervise_interval_s", 0.02)
    kw.setdefault("restart_backoff_s", 0.05)
    return ReplicaSet(
        lambda i: StubEngine(i), run, replicas=replicas, registry=reg,
        tracer=tracer, **kw,
    )


# ----------------------------------------------------- occupancy telemetry


def test_occupancy_window_ewma_and_window_mean():
    w = OccupancyWindow(8, alpha=0.5, window=4)
    snap = w.snapshot()
    assert snap["ewma"] == 0.0 and snap["batches"] == 0
    w.observe(8)  # occ 1.0
    w.observe(4)  # occ 0.5 -> ewma 0.75
    snap = w.snapshot()
    assert snap["ewma"] == pytest.approx(0.75)
    assert snap["window_mean"] == pytest.approx(0.75)
    assert snap["last"] == pytest.approx(0.5)
    assert snap["batches"] == 2


def test_microbatcher_occupancy_is_windowed_not_last_flush():
    """Regression: batch_occupancy fed from the last flush alone made one
    trailing single-request flush erase a history of full batches."""
    done = threading.Event()

    def run(batch):
        return {"y": batch[:, 0, 0, 0].astype(np.float64)}

    mb = MicroBatcher(run, max_batch=4, max_delay_ms=1.0)
    try:
        # one full batch, then one singleton
        futs = [mb.submit(_img(i)) for i in range(4)]
        wait(futs, timeout=10)
        futs = [mb.submit(_img(9))]
        wait(futs, timeout=10)
        for _ in range(200):
            if len(mb.batch_sizes) >= 2:
                break
            time.sleep(0.005)
        s = mb.stats()
        assert s["last_batch_occupancy"] == pytest.approx(0.25)
        # the headline number remembers the full flush
        assert s["batch_occupancy"] > 0.25
        assert s["window_batch_occupancy"] == pytest.approx(0.625)
    finally:
        done.set()
        mb.close()


# ------------------------------------------------------------- admission


def test_parse_tenants_specs_and_errors():
    ts = parse_tenants("web=interactive:rate=50:burst=100,scrape=batch:rate=5")
    assert ts[0] == TenantSpec("web", "interactive", 50.0, 100.0)
    assert ts[1] == TenantSpec("scrape", "batch", 5.0, None)
    with pytest.raises(ValueError, match="unknown tenant class"):
        parse_tenants("web=interacttive")
    with pytest.raises(ValueError, match="duplicate"):
        parse_tenants("a=batch,a=batch")
    with pytest.raises(ValueError, match="unknown tenant option"):
        parse_tenants("a=batch:rte=5")
    with pytest.raises(ValueError, match="empty tenant spec"):
        parse_tenants(" , ")


def test_quota_bucket_sheds_and_refills():
    t = {"now": 100.0}
    adm = AdmissionController(
        parse_tenants("s=batch:rate=2:burst=2"),
        registry=MetricsRegistry(),
        clock=lambda: t["now"],
    )
    assert adm.admit("s").tclass == "batch"
    adm.admit("s")
    with pytest.raises(TenantQuotaError):
        adm.admit("s")
    t["now"] += 1.0  # refill 2 tokens
    adm.admit("s")
    adm.admit("s")
    with pytest.raises(TenantQuotaError):
        adm.admit("s")
    st = adm.stats()
    assert st["admitted"]["s"] == 4
    assert st["shed"]["s:quota"] == 2


def test_pressure_sheds_low_classes_first():
    p = {"v": 0.0}
    adm = AdmissionController(
        parse_tenants("web=interactive,crawl=batch,fill=scavenger"),
        pressure_fn=lambda: p["v"],
        registry=MetricsRegistry(),
    )
    for name in ("web", "crawl", "fill"):
        adm.admit(name)
    p["v"] = 0.6  # scavenger gives way at half load
    adm.admit("web")
    adm.admit("crawl")
    with pytest.raises(TenantPressureError):
        adm.admit("fill")
    p["v"] = 0.9  # batch gives way at heavy load
    adm.admit("web")
    with pytest.raises(TenantPressureError):
        adm.admit("crawl")
    p["v"] = 1.0  # a full queue sheds everyone
    with pytest.raises(TenantPressureError):
        adm.admit("web")
    assert adm.stats()["shed"] == {
        "fill:pressure": 1, "crawl:pressure": 1, "web:pressure": 1
    }


def test_unknown_and_none_tenant_default_to_batch_unmetered():
    adm = AdmissionController(
        parse_tenants("web=interactive"), registry=MetricsRegistry()
    )
    assert adm.admit(None).name == "_default"
    sp = adm.admit("stranger")
    assert (sp.tclass, sp.rate) == ("batch", None)
    for _ in range(50):  # no quota on unknown tenants
        adm.admit("stranger")


def test_broken_pressure_probe_fails_open():
    def boom():
        raise RuntimeError("probe died")

    adm = AdmissionController(
        parse_tenants("fill=scavenger"),
        pressure_fn=boom,
        registry=MetricsRegistry(),
    )
    adm.admit("fill")  # pressure reads 0.0, not an exception


# ------------------------------------------------------------- scheduler


def test_floor_bucket_ladder():
    assert [floor_bucket(k, 16) for k in (1, 2, 3, 5, 8, 11, 16, 40)] == [
        1, 2, 2, 4, 8, 8, 16, 16
    ]


class DispatchStub:
    """Backend standing in for ReplicaSet.submit_group: records batches,
    resolves futures inline (optionally gated on an event)."""

    def __init__(self, gate=None, fail=None):
        self.batches = []
        self.gate = gate
        self.fail = fail
        self.lock = threading.Lock()

    def __call__(self, items):
        if self.gate is not None:
            assert self.gate.wait(timeout=10)
        if self.fail is not None:
            raise self.fail
        with self.lock:
            self.batches.append(items)
        futs = []
        from concurrent.futures import Future

        for image, deadline, meta, tr in items:
            f = Future()
            f.set_result({"y": float(image[0, 0, 0])})
            futs.append(f)
        return futs


def test_scheduler_coalesces_concurrent_arrivals_into_one_flush():
    stub = DispatchStub()
    sched = ContinuousScheduler(
        stub, max_batch=8, max_delay_ms=30.0, registry=MetricsRegistry()
    )
    with sched:
        futs = [sched.submit(_img(i)) for i in range(8)]
        done, _ = wait(futs, timeout=10)
        assert len(done) == 8
    assert len(stub.batches[0]) == 8  # full batch dispatched as one group
    assert all(f.result()["y"] == float(i) for i, f in enumerate(futs))


def test_scheduler_bucket_aligned_partial_dispatch():
    """3 due entries in an accumulator of 6 dispatch as a zero-pad bucket
    of 4, holding the 2 youngest to seed the next batch."""
    stub = DispatchStub()
    sched = ContinuousScheduler(
        stub, max_batch=16, max_delay_ms=80.0, registry=MetricsRegistry()
    )
    with sched:
        futs = [sched.submit(_img(i)) for i in range(3)]
        time.sleep(0.04)
        futs += [sched.submit(_img(10 + i)) for i in range(3)]
        done, _ = wait(futs, timeout=10)
        assert len(done) == 6
    sizes = [len(b) for b in stub.batches]
    assert sizes[0] == 4  # floor_bucket(6) covering the 3 due entries
    assert sum(sizes) == 6


def test_scheduler_priority_jumps_overfull_accumulator():
    gate = threading.Event()
    stub = DispatchStub(gate=gate)
    reg = MetricsRegistry()
    adm = AdmissionController(
        parse_tenants("vip=interactive,fill=scavenger"), registry=reg
    )
    sched = ContinuousScheduler(
        stub, max_batch=2, max_delay_ms=5.0, admission=adm, registry=reg
    )
    try:
        # first full batch blocks the dispatcher on the gate...
        first = [sched.submit(_img(0), tenant="fill") for _ in range(2)]
        time.sleep(0.05)
        # ...while an over-full accumulator builds: scavengers first
        late = [sched.submit(_img(1), tenant="fill") for _ in range(2)]
        time.sleep(0.02)
        vips = [sched.submit(_img(2), tenant="vip") for _ in range(2)]
        gate.set()
        done, _ = wait(first + late + vips, timeout=10)
        assert len(done) == 6
    finally:
        sched.close()
    # batch 2 is the vips jumping the earlier-arrived scavengers
    assert [float(i[0][0, 0, 0]) for i in stub.batches[1]] == [2.0, 2.0]
    assert "serve_sched_priority_jumps_total 2" in reg.render()


def test_scheduler_deadline_expires_while_pending():
    gate = threading.Event()
    stub = DispatchStub(gate=gate)
    sched = ContinuousScheduler(
        stub, max_batch=2, max_delay_ms=5.0, registry=MetricsRegistry()
    )
    try:
        blockers = [sched.submit(_img()) for _ in range(2)]
        time.sleep(0.02)
        doomed = sched.submit(_img(), deadline_ms=30.0)
        time.sleep(0.08)  # deadline passes while the dispatcher is gated
        gate.set()
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=10)
        wait(blockers, timeout=10)
    finally:
        sched.close()
    assert sched.stats()["expired"] == 1


def test_scheduler_queue_full_sheds_with_trace(tmp_path):
    gate = threading.Event()
    stub = DispatchStub(gate=gate)
    log = AccessLog(tmp_path / "access")
    reg = MetricsRegistry()
    tracer = RequestTracer(registry=reg, access_log=log)
    adm = AdmissionController(
        parse_tenants("web=interactive"), registry=reg
    )
    sched = ContinuousScheduler(
        stub, max_batch=4, max_delay_ms=5.0, max_queue=2,
        admission=adm, tracer=tracer, registry=reg,
    )
    try:
        keep = [sched.submit(_img(), tenant="web") for _ in range(2)]
        with pytest.raises(QueueFullError):
            sched.submit(_img(), tenant="web")
        gate.set()
        wait(keep, timeout=10)
    finally:
        sched.close()
        tracer.close()
    rows = read_journal(tmp_path / "access")
    shed = [r for r in rows if r["outcome"] == "shed"]
    assert len(shed) == 1
    assert (shed[0]["tenant"], shed[0]["class"]) == ("web", "interactive")


def test_scheduler_close_drain_fails_pending_with_shutdown():
    gate = threading.Event()
    stub = DispatchStub(gate=gate)
    sched = ContinuousScheduler(
        stub, max_batch=8, max_delay_ms=500.0, registry=MetricsRegistry()
    )
    pending = [sched.submit(_img()) for _ in range(3)]
    gate.set()
    sched.close(drain=True)
    for f in pending:
        with pytest.raises(ShutdownError):
            f.result(timeout=5)
    with pytest.raises(ShutdownError):
        sched.submit(_img())


def test_scheduler_close_no_drain_dispatches_leftovers():
    stub = DispatchStub()
    sched = ContinuousScheduler(
        stub, max_batch=8, max_delay_ms=500.0, registry=MetricsRegistry()
    )
    pending = [sched.submit(_img(i)) for i in range(3)]
    sched.close(drain=False)
    done, _ = wait(pending, timeout=10)
    assert len(done) == 3 and all(f.exception() is None for f in pending)


def test_scheduler_dispatch_error_fails_the_batch_futures():
    stub = DispatchStub(fail=RuntimeError("backend down"))
    sched = ContinuousScheduler(
        stub, max_batch=2, max_delay_ms=2.0, registry=MetricsRegistry()
    )
    with sched:
        futs = [sched.submit(_img()) for _ in range(2)]
        for f in futs:
            with pytest.raises(RuntimeError, match="backend down"):
                f.result(timeout=10)


# ---------------------------------------------- scheduler -> pool, end to end


def test_scheduler_batch_lands_on_one_replica_as_one_flush(tmp_path):
    reg = MetricsRegistry()
    log = AccessLog(tmp_path / "access")
    tracer = RequestTracer(registry=reg, access_log=log)
    rs = make_pool(reg, tracer, replicas=3, max_delay_ms=20.0)
    sched = ContinuousScheduler(
        rs.submit_group, max_batch=8, max_delay_ms=20.0,
        tracer=tracer, registry=reg,
    )
    try:
        futs = [sched.submit(_img(i)) for i in range(8)]
        done, _ = wait(futs, timeout=10)
        assert len(done) == 8
        assert [f.result()["y"] for f in futs] == [float(i) for i in range(8)]
    finally:
        sched.close()
        rs.close()
        tracer.close()
    rows = [
        r for r in read_journal(tmp_path / "access")
        if r.get("type") == "request"
    ]
    assert len(rows) == 8
    # the whole group ran on one replica, as one batch of 8
    assert len({r["replica"] for r in rows}) == 1
    assert {r["batch"] for r in rows} == {8}


def test_exactly_once_under_crash_storm_and_priority_reorder(
    tmp_path, fault_plan
):
    """8 threads x 25 requests from mixed-class tenants through the
    continuous scheduler into a 3-replica pool whose r1 dies on every
    batch: every future resolves exactly once (ok, typed shed, deadline,
    or retried error) and access rows match resolved traces 1:1."""
    fault_plan("serve.replica:raise(RuntimeError)@key~r1")
    reg = MetricsRegistry()
    log = AccessLog(tmp_path / "access")
    tracer = RequestTracer(registry=reg, access_log=log)

    def run(eng, batch, metas):
        time.sleep(0.002)
        return {"y": batch[:, 0, 0, 0].astype(np.float64)}

    rs = make_pool(reg, tracer, replicas=3, run=run, max_queue=None)
    adm = AdmissionController(
        parse_tenants("vip=interactive,crawl=batch,fill=scavenger"),
        registry=reg,
    )
    sched = ContinuousScheduler(
        rs.submit_group, max_batch=8, max_delay_ms=2.0, max_queue=None,
        admission=adm, tracer=tracer, registry=reg,
    )
    tenants = ("vip", "crawl", "fill")
    futures, submit_errors = [], []
    lock = threading.Lock()

    def client(tid):
        rng = np.random.RandomState(tid)
        for i in range(25):
            dl = None if i % 3 else float(rng.uniform(50.0, 500.0))
            try:
                f = sched.submit(
                    _img(tid), deadline_ms=dl, tenant=tenants[i % 3]
                )
            except (QueueFullError, ShutdownError) as e:
                with lock:
                    submit_errors.append(e)
            else:
                with lock:
                    futures.append(f)

    threads = [
        threading.Thread(target=client, args=(t,)) for t in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    done, not_done = wait(futures, timeout=60)
    assert not not_done, f"{len(not_done)} future(s) never resolved"
    sched.close()
    rs.close()
    tracer.close()
    ok = sum(1 for f in futures if f.exception() is None)
    assert ok > 0  # survivors absorbed the storm
    assert len(futures) + len(submit_errors) == 8 * 25
    rows = [
        r for r in read_journal(tmp_path / "access")
        if r.get("type") == "request"
    ]
    # every resolved future produced exactly one trace row
    assert len(rows) == len(futures)
    assert len({r["rid"] for r in rows}) == len(rows)
    assert {r["tenant"] for r in rows} <= set(tenants)


# -------------------------------------------------------------- scale_to


def test_scale_to_up_and_down_updates_pool(tmp_path):
    reg = MetricsRegistry()
    log = AccessLog(tmp_path / "access")
    tracer = RequestTracer(registry=reg, access_log=log)
    rs = make_pool(reg, tracer, replicas=2)
    try:
        report = rs.scale_to(4)
        assert (report["from"], report["to"]) == (2, 4)
        assert len(rs.stats()["replicas"]) == 4
        futs = [rs.submit(_img(i)) for i in range(8)]
        done, _ = wait(futs, timeout=10)
        assert len(done) == 8
        report = rs.scale_to(2, drain_timeout_s=5.0)
        assert report["to"] == 2
        assert len(rs.stats()["replicas"]) == 2
        # the shrunk pool still serves
        f = rs.submit(_img(5.0))
        assert f.result(timeout=10)["y"] == 5.0
    finally:
        rs.close()
        tracer.close()
    ev = [
        r["type"] for r in read_journal(tmp_path / "access")
        if r.get("type") in ("replica_added", "replica_removed")
    ]
    assert ev.count("replica_added") == 2
    assert ev.count("replica_removed") == 2


def test_scale_down_drains_never_kills_in_flight():
    reg = MetricsRegistry()

    def slow_run(eng, batch, metas):
        time.sleep(0.1)
        return {"y": batch[:, 0, 0, 0].astype(np.float64)}

    rs = make_pool(reg, replicas=3, run=slow_run, max_delay_ms=1.0)
    try:
        futs = [rs.submit(_img(i)) for i in range(12)]
        report = rs.scale_to(1, drain_timeout_s=10.0)
        assert report["to"] == 1
        done, not_done = wait(futs, timeout=30)
        assert not not_done
        assert all(f.exception() is None for f in futs)
    finally:
        rs.close()


def test_scale_down_refuses_below_one_and_times_out_busy():
    reg = MetricsRegistry()
    release = threading.Event()
    entered = threading.Semaphore(0)

    def stuck_run(eng, batch, metas):
        entered.release()
        release.wait(timeout=10)
        return {"y": batch[:, 0, 0, 0].astype(np.float64)}

    rs = make_pool(reg, replicas=2, run=stuck_run)
    try:
        with pytest.raises(ValueError):
            rs.scale_to(0)
        # a replica shows as busy from its run on, not while its worker still
        # gathers a batch: wait for each to be inside its run (on a loaded
        # machine the last slot was still gathering, looked idle and was
        # removed). Once the first is stuck, the next request queues behind
        # it and the one after goes to the other replica.
        futs = [rs.submit(_img())]
        assert entered.acquire(timeout=30)
        futs += [rs.submit(_img()) for _ in range(3)]
        assert entered.acquire(timeout=30)
        # both replicas busy: a tiny drain budget can't free the last slot
        report = rs.scale_to(1, drain_timeout_s=0.05)
        assert report["to"] == 2  # refused, not forced
        release.set()
        done, _ = wait(futs, timeout=30)
        assert len(done) == 4
        assert all(f.exception() is None for f in futs)
    finally:
        release.set()
        rs.close()


@pytest.mark.slow  # 8-thread storm starves on the 1-CPU gate runner and
# loses futures to timeouts that are load, not logic — slow lane only
def test_scale_races_submit_storm_every_future_resolves():
    """Scale 3->1->3 repeatedly under an 8-thread submit storm: no future
    is lost to a removed slot (the retired-queue rescue) and the pool
    ends at the commanded size."""
    reg = MetricsRegistry()

    def run(eng, batch, metas):
        time.sleep(0.001)
        return {"y": batch[:, 0, 0, 0].astype(np.float64)}

    rs = make_pool(reg, replicas=3, run=run, max_queue=None)
    futures, submit_errors = [], []
    lock = threading.Lock()
    stop = threading.Event()

    def client(tid):
        while not stop.is_set():
            try:
                f = rs.submit(_img(tid))
            except (QueueFullError, ShutdownError) as e:
                with lock:
                    submit_errors.append(e)
            else:
                with lock:
                    futures.append(f)
            time.sleep(0.0005)

    threads = [
        threading.Thread(target=client, args=(t,)) for t in range(8)
    ]
    for t in threads:
        t.start()
    for _ in range(3):
        rs.scale_to(1, drain_timeout_s=5.0)
        rs.scale_to(3)
    stop.set()
    for t in threads:
        t.join()
    done, not_done = wait(futures, timeout=60)
    assert not not_done, f"{len(not_done)} future(s) lost in scaling"
    bad = [
        f for f in futures
        if f.exception() is not None
        and not isinstance(f.exception(), (QueueFullError, ShutdownError))
    ]
    assert not bad, f"unexpected failures: {bad[:3]}"
    assert len(rs.stats()["replicas"]) == 3
    rs.close()


# ------------------------------------------------------------- autoscaler


class FakePool:
    """Scripted ReplicaSet facade: the autoscaler sees exactly the
    signals the test sets."""

    def __init__(self, n=2):
        self.n = n
        self.submitted = 0
        self.served = 0
        self.queue_depth = 0
        self.breaker = False
        self.calls = []

    def stats(self):
        return {
            "requests_submitted": self.submitted,
            "queue_depth": self.queue_depth,
            "breaker_open": self.breaker,
            "healthy": self.n,
            "batch_occupancy": 0.5,
            "replicas": {
                f"r{i}": {"served": self.served // self.n}
                for i in range(self.n)
            },
        }

    def scale_to(self, target, *, drain_timeout_s=10.0):
        report = {"from": self.n, "to": target}
        self.calls.append(target)
        self.n = target
        return report


def test_autoscaler_scales_up_on_demand_down_after_hold():
    pool = FakePool(n=2)
    asc = Autoscaler(
        pool, min_replicas=2, max_replicas=4, interval_s=1.0,
        capacity_fn=lambda: 100.0, down_hold=3, start=False,
        registry=MetricsRegistry(), clock=lambda: 0.0,
    )
    asc.tick(now=0.0)  # baseline sample
    pool.submitted += 300  # 300 req/s arrives
    pool.queue_depth = 150
    d = asc.tick(now=1.0)
    assert d["target"] > 2 and d["reason"] == "demand"
    assert pool.calls and pool.calls[-1] == d["target"]
    assert asc.events[-1]["current"] == 2
    # demand collapses: down only after down_hold consecutive low ticks,
    # one step at a time
    pool.queue_depth = 0
    t, start_n = 2.0, pool.n
    for _ in range(asc.down_hold - 1):
        asc.tick(now=t)
        t += 1.0
    assert pool.n == start_n  # held
    asc.tick(now=t)
    assert pool.n == start_n - 1  # exactly one step
    assert asc.events[-1]["reason"] == "demand"


def test_autoscaler_burn_and_breaker_force_step_up():
    class HotSLO:
        def worst_burn(self, now=None):
            return 5.0

    pool = FakePool(n=2)
    asc = Autoscaler(
        pool, min_replicas=1, max_replicas=4, slo=HotSLO(),
        capacity_fn=lambda: 1000.0, start=False,
        registry=MetricsRegistry(), clock=lambda: 0.0,
    )
    d = asc.tick(now=0.0)
    assert d["reason"] == "burn" and pool.n == 3
    pool2 = FakePool(n=2)
    pool2.breaker = True
    asc2 = Autoscaler(
        pool2, min_replicas=1, max_replicas=4,
        capacity_fn=lambda: 1000.0, start=False,
        registry=MetricsRegistry(), clock=lambda: 0.0,
    )
    d2 = asc2.tick(now=0.0)
    assert d2["reason"] == "breaker" and pool2.n == 3


def test_autoscaler_respects_bounds_and_validates():
    with pytest.raises(ValueError):
        Autoscaler(FakePool(), min_replicas=3, max_replicas=2, start=False)
    pool = FakePool(n=4)
    asc = Autoscaler(
        pool, min_replicas=2, max_replicas=4, capacity_fn=lambda: 1.0,
        down_hold=1, start=False, registry=MetricsRegistry(),
        clock=lambda: 0.0,
    )
    asc.tick(now=0.0)
    pool.submitted += 10_000  # way past max capacity
    d = asc.tick(now=1.0)
    assert d["target"] == 4  # clamped to max


def test_roofline_capacity_positive_and_derated():
    full = roofline_capacity(1e9, 1e7, utilization=1.0)
    half = roofline_capacity(1e9, 1e7, utilization=0.5)
    assert full > 0
    assert half == pytest.approx(full * 0.5)


# ---------------------------------------------------- loadgen (pure parts)


def test_loadgen_schedule_deterministic_and_profiled():
    import sys
    from pathlib import Path

    sys.path.insert(
        0, str(Path(__file__).resolve().parent.parent / "tools")
    )
    import loadgen

    mix = [("web", 0.5), ("scrape", 0.5)]
    a = loadgen.build_schedule("flash", 10.0, 10.0, 200.0, mix, seed=3)
    b = loadgen.build_schedule("flash", 10.0, 10.0, 200.0, mix, seed=3)
    assert a == b  # same seed, same schedule
    c = loadgen.build_schedule("flash", 10.0, 10.0, 200.0, mix, seed=4)
    assert a != c
    # the flash crowd concentrates arrivals in the middle window
    mid = sum(1 for t, _ in a if 4.0 <= t < 6.0)
    edge = sum(1 for t, _ in a if t < 2.0)
    assert mid > 4 * edge
    # diurnal peaks mid-run, steady doesn't
    assert loadgen.rate_at("diurnal", 5.0, 10.0, 10.0, 200.0) == 200.0
    assert loadgen.rate_at("diurnal", 0.0, 10.0, 10.0, 200.0) == 10.0
    assert loadgen.rate_at("steady", 5.0, 10.0, 10.0, 200.0) == 10.0
    with pytest.raises(ValueError):
        loadgen.rate_at("tsunami", 0.0, 1.0, 1.0, 1.0)
    assert {t for _, t in a} == {"web", "scrape"}


# ------------------------------------------------- token-packed scheduling


def _sq(size, v=0.0):
    """A square image whose side doubles as its token count via
    ``seq_len_fn=lambda a: a.shape[0]``."""
    return np.full((size, size, 3), v, np.float32)


_tok = staticmethod(lambda arr: arr.shape[0])


def test_packed_scheduler_fills_token_budget_not_image_count():
    """Mixed 'resolutions' accumulate into ONE packed group that fires
    when the token budget fills — image count alone never would."""
    stub = DispatchStub()
    sched = ContinuousScheduler(
        stub, max_batch=64, max_delay_ms=500.0, registry=MetricsRegistry(),
        packed=True, token_budget=100, seq_len_fn=lambda a: a.shape[0],
    )
    with sched:
        futs = [sched.submit(_sq(s)) for s in (40, 30, 30)]  # = 100 tokens
        done, _ = wait(futs, timeout=10)
        assert len(done) == 3
    # one dispatch, all three sizes, long before the 500ms cutoff
    assert [i[0].shape[0] for i in stub.batches[0]] == [40, 30, 30]


def test_packed_scheduler_skims_past_overflowing_entry():
    """An entry that would overflow the remaining budget is skipped, not a
    wall: smaller entries behind it top up the rung, and the skip counts
    as a priority jump."""
    gate = threading.Event()
    stub = DispatchStub(gate=gate)
    reg = MetricsRegistry()
    sched = ContinuousScheduler(
        stub, max_batch=64, max_delay_ms=40.0, registry=reg,
        packed=True, token_budget=100, seq_len_fn=lambda a: a.shape[0],
    )
    try:
        # a budget-filling decoy parks the dispatcher on the gate so all
        # three contested entries are in the accumulator before any take
        decoy = sched.submit(_sq(100))
        time.sleep(0.05)
        futs = [sched.submit(_sq(s)) for s in (60, 50, 30)]  # 140 > budget
        time.sleep(0.02)
        gate.set()
        done, _ = wait([decoy] + futs, timeout=10)
        assert len(done) == 4
    finally:
        sched.close()
    sizes = [[i[0].shape[0] for i in b] for b in stub.batches]
    assert sizes[0] == [100]
    assert sizes[1] == [60, 30], "50 should be skimmed past, 30 taken"
    assert sizes[2] == [50], "skipped entry ships next (head of order)"
    assert "serve_sched_priority_jumps_total 1" in reg.render()


def test_packed_scheduler_rejects_oversized_and_requires_seq_len_fn():
    stub = DispatchStub()
    with pytest.raises(ValueError, match="seq_len_fn"):
        ContinuousScheduler(
            stub, max_batch=8, registry=MetricsRegistry(),
            packed=True, token_budget=100,
        )
    sched = ContinuousScheduler(
        stub, max_batch=8, max_delay_ms=5.0, registry=MetricsRegistry(),
        packed=True, token_budget=100, seq_len_fn=lambda a: a.shape[0],
    )
    with sched:
        with pytest.raises(ValueError, match="token_budget"):
            sched.submit(_sq(101))


@pytest.mark.parametrize("packed", [True, False])
def test_scheduler_stamps_token_counts_on_traces(tmp_path, packed):
    """With a seq_len_fn the scheduler prices every entry and stamps
    ``tr.tokens`` — packed or not (the image-bucket control leg bills its
    padded token count pro-rata through the same field)."""
    log = AccessLog(tmp_path / "access")
    tracer = RequestTracer(registry=MetricsRegistry(), access_log=log)
    stub = DispatchStub()
    sched = ContinuousScheduler(
        stub, max_batch=8, max_delay_ms=5.0, registry=MetricsRegistry(),
        tracer=tracer, packed=packed,
        token_budget=100 if packed else None,
        seq_len_fn=lambda a: a.shape[0],
    )
    try:
        futs = [sched.submit(_sq(40)), sched.submit(_sq(40))]
        wait(futs, timeout=10)
    finally:
        sched.close()
        tracer.close()
    traces = [tr for b in stub.batches for (_, _, _, tr) in b]
    assert sorted(tr.tokens for tr in traces) == [40, 40]


def test_loadgen_resolution_grammar_and_size_draws():
    import sys
    from pathlib import Path

    sys.path.insert(
        0, str(Path(__file__).resolve().parent.parent / "tools")
    )
    import loadgen

    # 'lo-hi:w' and 'size:w' entries; bare weight defaults to 1
    assert loadgen.parse_res_spec("160-224:0.5,448:0.3,896") == [
        (160, 224, 0.5), (448, 448, 0.3), (896, 896, 1.0),
    ]
    rng = np.random.RandomState(7)
    draws = loadgen.draw_sizes(rng, [(24, 32, 1.0), (52, 64, 2.0)], 400, 4)
    assert all(b in (32, 64) for _, b in draws)
    for native, bucket in draws:
        lo = 24 if bucket == 32 else 52
        assert lo <= native <= bucket and native % 4 == 0
    # weighted: the 52-64 range should dominate ~2:1
    hi = sum(1 for _, b in draws if b == 64)
    assert 200 < hi < 340
    # seeded determinism: same seed, same draws
    again = loadgen.draw_sizes(
        np.random.RandomState(7), [(24, 32, 1.0), (52, 64, 2.0)], 400, 4
    )
    assert draws == again
