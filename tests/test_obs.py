"""Telemetry subsystem contracts (jumbo_mae_tpu_tpu/obs).

What the subsystem stands on:

- the registry is exact under concurrent writers (serving threads all hit
  the same counters/histograms);
- histogram buckets follow Prometheus ``le`` semantics bit-exactly (a
  scraper's histogram_quantile depends on it);
- the text exposition is stable (golden) and parseable;
- ``/metrics`` and ``/healthz`` work over a real socket, and health flips
  with readiness/liveness;
- spans aggregate into the registry and export chrome-trace JSON;
- engine + micro-batcher traffic populates the serving metrics the
  acceptance criteria name (request latency, batch occupancy, bucket-cache
  hits/misses).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from jumbo_mae_tpu_tpu.obs import (
    NULL_REGISTRY,
    HealthState,
    MetricsRegistry,
    TelemetryServer,
    get_registry,
    set_registry,
    span,
)
from jumbo_mae_tpu_tpu.obs.trace import programs, span_timer

# ---------------------------------------------------------------- registry


def test_counter_exact_under_threads():
    reg = MetricsRegistry()
    c = reg.counter("hits_total", "x", labels=("who",))
    h = reg.histogram("lat_seconds", buckets=(0.5, 1.0))
    n_threads, n_incs = 8, 1000

    def worker(i):
        child = c.labels(str(i % 2))
        for _ in range(n_incs):
            child.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = c.labels("0").value + c.labels("1").value
    assert total == n_threads * n_incs
    assert h.count == n_threads * n_incs
    assert h.sum == pytest.approx(0.25 * n_threads * n_incs)


def test_histogram_bucket_edges():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 2.0, 5.0))
    # Prometheus le semantics: value == bound lands IN that bucket
    for v in (0.5, 1.0, 1.5, 2.0, 5.0, 7.0):
        h.observe(v)
    cum = dict(h.cumulative())
    assert cum[1.0] == 2  # 0.5, 1.0
    assert cum[2.0] == 4  # + 1.5, 2.0
    assert cum[5.0] == 5  # + 5.0
    assert cum[float("inf")] == 6  # + 7.0
    assert h.quantile(0.5) == 2.0
    assert h.quantile(1.0) == float("inf")


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError):
        MetricsRegistry().histogram("bad", buckets=(2.0, 1.0))


def test_registry_type_and_label_conflicts():
    reg = MetricsRegistry()
    reg.counter("a_total")
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("a_total")
    reg.counter("b_total", labels=("x",))
    with pytest.raises(ValueError, match="labels"):
        reg.counter("b_total", labels=("y",))
    # re-registration with the same schema returns the same family
    assert reg.counter("a_total") is reg.counter("a_total")


def test_prometheus_golden_output():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests served", labels=("task",)).labels(
        "features"
    ).inc(3)
    reg.gauge("depth", "queue depth").set(2)
    reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0)).observe(0.05)
    assert reg.render() == (
        "# HELP depth queue depth\n"
        "# TYPE depth gauge\n"
        "depth 2\n"
        "# HELP lat_seconds latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.1"} 1\n'
        'lat_seconds_bucket{le="1"} 1\n'
        'lat_seconds_bucket{le="+Inf"} 1\n'
        "lat_seconds_sum 0.05\n"
        "lat_seconds_count 1\n"
        "# HELP req_total requests served\n"
        "# TYPE req_total counter\n"
        'req_total{task="features"} 3\n'
    )


def test_label_escaping():
    reg = MetricsRegistry()
    reg.counter("c_total", labels=("p",)).labels('a"b\\c\nd').inc()
    assert 'c_total{p="a\\"b\\\\c\\nd"} 1' in reg.render()


def test_null_registry_and_swap():
    prev = set_registry(NULL_REGISTRY)
    try:
        c = get_registry().counter("dropped_total")
        c.inc(100)
        assert c.value == 0.0
        assert get_registry().render() == ""
    finally:
        set_registry(prev)
    # after restore, new handles record again
    get_registry().counter("kept_total").inc()
    assert get_registry().counter("kept_total").value >= 1


# ---------------------------------------------------------------- exporter


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def test_exporter_metrics_and_healthz_over_socket():
    reg = MetricsRegistry()
    reg.counter("served_total", "x").inc(7)
    health = HealthState()
    with TelemetryServer(reg, health, host="127.0.0.1", port=0) as srv:
        url = f"http://127.0.0.1:{srv.port}"
        # not ready yet → 503 with a JSON body
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{url}/healthz", timeout=10)
        assert e.value.code == 503
        assert json.loads(e.value.read().decode())["ready"] is False

        health.set_ready(True)
        status, body = _get(f"{url}/healthz")
        assert status == 200 and json.loads(body)["ok"] is True

        status, body = _get(f"{url}/metrics")
        assert status == 200
        assert "served_total 7" in body

        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{url}/nope", timeout=10)
        assert e.value.code == 404


def test_healthz_liveness_heartbeats():
    health = HealthState(ready=True)
    health.watch("step", max_age_s=0.2)
    ok, report = health.report()
    assert not ok  # watched but never beaten → not live
    assert report["checks"]["step"]["age_s"] is None
    health.beat("step")
    ok, report = health.report()
    assert ok and report["checks"]["step"]["ok"]
    time.sleep(0.25)
    ok, report = health.report()
    assert not ok  # stale heartbeat
    health.unwatch("step")
    ok, _ = health.report()
    assert ok


# ------------------------------------------------------------------- spans


def test_span_aggregates_into_registry():
    reg = MetricsRegistry()
    for _ in range(3):
        with span("stage_a", registry=reg):
            pass
    snap = reg.snapshot()
    assert snap["span_seconds"]["stage_a"]["count"] == 3
    assert snap["span_seconds"]["stage_a"]["sum"] >= 0


def test_span_timer_reuse_and_last_s():
    reg = MetricsRegistry()
    st = span_timer("loop", registry=reg)
    with st:
        time.sleep(0.01)
    assert st.last_s >= 0.01
    st.observe(0.5)
    snap = reg.snapshot()["span_seconds"]["loop"]
    assert snap["count"] == 2
    assert snap["sum"] >= 0.51


def _host_event_names(trace_dir):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    return {
        e.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    }


def test_spans_sit_on_the_profilers_clock(tmp_path):
    """A span and a span_timer entered while jax.profiler runs are host
    events of the written trace, by name, beside the device's operations —
    and still observe into span_seconds."""
    import jax

    reg = MetricsRegistry()
    timer = span_timer("probe_timer", registry=reg)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with span("probe_span", registry=reg):
            jax.block_until_ready(jax.jit(lambda x: x + 1)(np.ones(8, np.float32)))
        with timer:
            pass
    finally:
        jax.profiler.stop_trace()
    assert {"probe_span", "probe_timer"} <= _host_event_names(tmp_path)
    snap = reg.snapshot()["span_seconds"]
    assert snap["probe_span"]["count"] == 1 and snap["probe_timer"]["count"] == 1
    # with no profiler running the annotation is a no-op; the histogram stays
    with span("probe_span", registry=reg):
        pass
    assert reg.snapshot()["span_seconds"]["probe_span"]["count"] == 2


def _span_count(name):
    return get_registry().snapshot().get("span_seconds", {}).get(name, {}).get("count", 0)


def test_first_train_step_records_its_build_and_its_program(monkeypatch):
    """The AOT compile point times itself (``program_build:train_step``) and
    notes the executable; the HLO text is asked for by whoever reduces a
    trace, never by set-up or by a step."""
    import jax

    from jumbo_mae_tpu_tpu.config import MeshConfig, OptimConfig
    from jumbo_mae_tpu_tpu.models import DecoderConfig, MAEPretrainModel, preset
    from jumbo_mae_tpu_tpu.parallel import create_mesh
    from jumbo_mae_tpu_tpu.train import (
        create_sharded_state,
        make_eval_step,
        make_optimizer,
        make_train_step,
    )

    asked = []
    real = jax.stages.Compiled.as_text
    monkeypatch.setattr(jax.stages.Compiled, "as_text",
                        lambda self, *a, **k: asked.append(1) or real(self, *a, **k))
    enc = preset("vit_t16", labels=None, mask_ratio=0.75, image_size=32, dtype="float32")
    model = MAEPretrainModel(enc, DecoderConfig(layers=1, dim=32, heads=2, dtype="float32"))
    batch = {"images": np.zeros((8, 32, 32, 3), np.uint8)}
    mesh = create_mesh(MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1])
    tx = make_optimizer(OptimConfig(warmup_steps=2, training_steps=10), global_batch_size=8)
    inits = _span_count("state_init")
    state, sharding = create_sharded_state(model, tx, batch, mesh, mode="pretrain")
    assert _span_count("state_init") == inits + 1
    step = make_train_step(mesh, sharding, mode="pretrain", guard_nonfinite=True)
    evaluate = make_eval_step(mesh, sharding, mode="pretrain")
    builds = _span_count("program_build:train_step")
    eval_builds = _span_count("program_build:eval_step")
    sums = evaluate(state, batch)
    state, _ = step(state, batch)
    assert _span_count("program_build:train_step") == builds + 1
    assert _span_count("program_build:eval_step") == eval_builds + 1
    (compiled,) = step.executables.values()
    assert programs()["train_step"] is compiled
    assert programs()["eval_step"] is next(iter(evaluate.executables.values()))
    state, metrics = step(state, batch)  # the same shapes build nothing
    assert _span_count("program_build:train_step") == builds + 1
    assert np.isfinite(float(metrics["loss"])) and float(sums["num_samples"]) == 8
    assert not asked
    assert "guard/jit(_where)/select_n" in compiled.as_text() and asked


def test_keeping_programs_outlives_the_builder():
    """``programs()`` forgets a program with the step that built it;
    ``keeping_programs()`` holds what is noted inside its block, the latest
    under a name, and nothing noted after it."""
    import gc

    from jumbo_mae_tpu_tpu.obs.trace import keeping_programs, note_program

    Program = type("Program", (), {})
    with keeping_programs() as kept:
        note_program("probe_step", Program())
        second = Program()
        note_program("probe_step", second)
        del second
    note_program("probe_late", Program())
    gc.collect()
    assert list(kept) == ["probe_step"] and programs()["probe_step"] is kept["probe_step"]
    kept.clear()
    gc.collect()
    assert "probe_step" not in programs() and "probe_late" not in programs()


def test_prefetch_to_device_records_one_h2d_per_batch():
    import jax
    from jax.sharding import SingleDeviceSharding

    from jumbo_mae_tpu_tpu.data.loader import prefetch_to_device

    before = _span_count("h2d")
    batches = ({"images": np.full((2, 4), i, np.float32)} for i in range(5))
    out = list(prefetch_to_device(batches, SingleDeviceSharding(jax.devices()[0])))
    assert [int(b["images"][0, 0]) for b in out] == [0, 1, 2, 3, 4]
    assert _span_count("h2d") == before + 5


# ------------------------------------------------------- compat shims


def test_utils_shims_point_at_obs():
    from jumbo_mae_tpu_tpu.obs import metrics as obs_metrics
    from jumbo_mae_tpu_tpu.obs import mfu as obs_mfu
    from jumbo_mae_tpu_tpu.utils import meters, mfu, profiling

    assert meters.AverageMeter is obs_metrics.AverageMeter
    assert mfu.mfu_report is obs_mfu.mfu_report
    assert mfu.detect_peak_tflops is obs_mfu.detect_peak_tflops
    from jumbo_mae_tpu_tpu.obs.trace import trace as obs_trace

    assert profiling.trace is obs_trace


# --------------------------------------------- engine integration (serve)


@pytest.fixture(scope="module")
def served():
    """A tiny engine + micro-batcher driving real traffic into a fresh
    registry; returns (registry, engine, batch_sizes)."""
    from pathlib import Path

    from jumbo_mae_tpu_tpu.config import load_config
    from jumbo_mae_tpu_tpu.infer import InferenceEngine, MicroBatcher

    recipe = Path(__file__).resolve().parent.parent / "recipes" / "smoke_cpu.yaml"
    cfg = load_config(
        recipe,
        [
            "model.overrides.dtype=float32",
            "model.dec_layers=1",
            "model.dec_dim=32",
            "model.dec_heads=2",
            "model.dec_dtype=float32",
        ],
    )
    reg = MetricsRegistry()
    engine = InferenceEngine(cfg, max_batch=8, registry=reg)
    images = (
        np.random.RandomState(0).randint(0, 256, (24, 32, 32, 3)).astype(np.uint8)
    )
    with MicroBatcher(
        lambda b: engine.features(b), max_batch=8, max_delay_ms=20.0,
        registry=reg,
    ) as mb:
        futs = [mb.submit(img) for img in images]
        rows = [f.result() for f in futs]
        sizes = list(mb.batch_sizes)
    assert len(rows) == 24
    return reg, engine, sizes


def test_engine_traffic_populates_serving_metrics(served):
    reg, _, sizes = served
    snap = reg.snapshot()
    n_requests = 24
    # request latency: one observation per submitted request
    assert snap["infer_request_latency_seconds"][""]["count"] == n_requests
    assert snap["infer_request_latency_seconds"][""]["sum"] > 0
    # batch occupancy: one observation per flushed batch
    assert snap["infer_batch_occupancy"][""]["count"] == len(sizes) > 0
    assert snap["infer_requests_total"][""] == n_requests
    assert snap["infer_batches_total"][""] == len(sizes)
    # bucket-cache: first batch at each bucket compiles (miss), the rest hit
    hits = sum(snap["infer_bucket_cache_hits_total"].values())
    misses = sum(snap["infer_bucket_cache_misses_total"].values())
    assert misses >= 1
    assert hits + misses == len(sizes)
    assert snap["infer_images_total"]["features"] == n_requests
    assert snap["infer_predict_seconds"]["features"]["count"] == len(sizes)
    assert snap["infer_compile_seconds"]["features:cls"]["count"] == misses


def test_engine_metrics_render_for_scrape(served):
    reg, _, _ = served
    text = reg.render()
    for needle in (
        "infer_request_latency_seconds_bucket",
        "infer_request_latency_seconds_count",
        "infer_batch_occupancy_bucket",
        "infer_bucket_cache_misses_total",
        "infer_queue_depth",
    ):
        assert needle in text, f"{needle} missing from scrape"


def test_batcher_error_counts_failed_requests():
    from jumbo_mae_tpu_tpu.infer import MicroBatcher

    reg = MetricsRegistry()

    def boom(batch):
        raise RuntimeError("kaput")

    with MicroBatcher(boom, max_batch=4, max_delay_ms=1.0, registry=reg) as mb:
        fut = mb.submit(np.zeros((2, 2, 3), np.uint8))
        with pytest.raises(RuntimeError, match="kaput"):
            fut.result(timeout=10)
    snap = reg.snapshot()
    assert snap["infer_requests_failed_total"][""] == 1
    # no latency recorded for failed requests
    assert snap["infer_request_latency_seconds"][""]["count"] == 0
