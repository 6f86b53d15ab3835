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
    with st:
        pass
    snap = reg.snapshot()["span_seconds"]["loop"]
    assert snap["count"] == 2
    assert snap["sum"] >= 0.01 and st.last_s < 0.01


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


# ---------------------------------------------------------- the span log


def _trace_mod():
    import importlib

    return importlib.import_module("jumbo_mae_tpu_tpu.obs.trace")  # obs.trace is also a function


def _mine(since_id):
    return [r for r in _trace_mod().spans() if r["id"] > since_id]


def _last_id():
    log = _trace_mod().spans()
    return max((r["id"] for r in log), default=0)


def test_span_log_records_parents_per_thread():
    """A nested span's parent is the enclosing span's id; one opened on
    another thread while it is open has none, and says which thread."""
    reg, since = MetricsRegistry(), _last_id()
    timer = span_timer("log_inner_timer", registry=reg)

    def elsewhere():
        with span("log_other_thread", registry=reg):
            pass

    with span("log_outer", registry=reg):
        with span("log_inner", registry=reg):
            with timer:
                pass
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join(timeout=10)
    by = {r["name"]: r for r in _mine(since)}
    assert by["log_outer"]["parent"] is None
    assert by["log_inner"]["parent"] == by["log_outer"]["id"]
    assert by["log_inner_timer"]["parent"] == by["log_inner"]["id"]
    assert by["log_other_thread"]["parent"] is None
    assert by["log_other_thread"]["thread"] != by["log_outer"]["thread"] == threading.get_ident()
    for child, parent in (("log_inner", "log_outer"), ("log_inner_timer", "log_inner")):
        assert by[parent]["start"] <= by[child]["start"] <= by[child]["end"] <= by[parent]["end"]


def test_self_seconds_is_duration_minus_the_union_of_children():
    tr = _trace_mod()
    rec = lambda i, parent, s, e: {"id": i, "parent": parent, "name": f"r{i}", "start": s,
                                   "end": e, "thread": 1}
    # two children that overlap by one second, and a grandchild that is not the parent's
    records = [rec(1, None, 0.0, 10.0), rec(2, 1, 1.0, 4.0), rec(3, 1, 3.0, 6.0),
               rec(4, 2, 1.5, 2.0)]
    own = tr.self_seconds(records)
    assert own == {1: 10.0 - 5.0, 2: 3.0 - 0.5, 3: 3.0, 4: 0.5}
    assert tr.union_seconds([(1.0, 4.0), (3.0, 6.0), (8.0, 8.5)]) == 5.5


def test_jax_events_enter_the_log_under_the_open_span():
    """A jit compiled inside a span leaves its trace, its lowering and its
    compile as that span's children, with JAX's own start and end; a jit
    traced inside another's trace lies inside it, so the union is not the
    sum; and one traced there in under a millisecond leaves no record."""
    import jax

    tr, since = _trace_mod(), _last_id()

    @jax.jit
    def log_probe_inner(x):
        time.sleep(2 * tr.NESTED_RECORD_MIN_S)  # while it is traced
        return x * 2

    @jax.jit
    def log_probe_outer(x):
        return log_probe_inner(x) + 1

    with span("log_jit_home", registry=MetricsRegistry()):
        jax.block_until_ready(log_probe_outer(np.ones(3, np.float32)))
    mine = _mine(since)
    by = {r["name"]: r for r in mine}
    home = by["log_jit_home"]
    for kind in ("jit_trace", "jit_lower", "backend_compile"):
        r = by[f"{kind}:log_probe_outer"]
        assert r["parent"] == home["id"]
        assert home["start"] <= r["start"] < r["end"] <= home["end"]
    outer, inner = by["jit_trace:log_probe_outer"], by["jit_trace:log_probe_inner"]
    assert inner["parent"] == home["id"]  # JAX's events open no span of their own
    assert outer["start"] <= inner["start"] and inner["end"] <= outer["end"]
    traces = [(r["start"], r["end"]) for r in mine if r["name"].startswith("jit_trace:")]
    assert tr.union_seconds(traces) == pytest.approx(outer["end"] - outer["start"])
    assert sum(e - s for s, e in traces) > outer["end"] - outer["start"]
    nested = [r for r in mine if r["name"].startswith("jit_trace:") and r is not outer
              and outer["start"] <= r["start"] and r["end"] <= outer["end"]]
    assert inner in nested  # the addition and the multiplication were traced there too
    assert all(r["end"] - r["start"] >= tr.NESTED_RECORD_MIN_S for r in nested)
    assert by["jit_trace:log_probe_outer"]["end"] <= by["jit_lower:log_probe_outer"]["start"]
    own = tr.self_seconds(mine)[home["id"]]
    assert 0 <= own < home["end"] - home["start"]


def test_setup_report_is_the_tree_with_self_times():
    import jax

    tr = _trace_mod()
    reg = MetricsRegistry()
    t0 = time.perf_counter()
    for _ in range(2):
        with span("log_report_root", registry=reg):
            jax.block_until_ready(jax.jit(lambda x: x - 7)(np.ones(2, np.float32)))
            with span("log_report_leaf", registry=reg):
                time.sleep(0.002)
    report = tr.setup_report(t0, time.perf_counter())
    (root,) = [n for n in report["roots"] if n["name"] == "log_report_root"]
    assert root["count"] == 2 and root["main"]
    assert {"jit_trace", "jit_lower", "backend_compile", "log_report_leaf"} <= set(root["kinds"])
    assert root["self_s"] == pytest.approx(
        root["seconds"] - tr.union_seconds(
            (r["start"], r["end"]) for r in tr.spans()
            if r["start"] >= t0 and r["parent"] is not None), abs=1e-6)
    assert report["spanned_s"] == pytest.approx(root["seconds"])
    assert report["spanned_s"] <= report["seconds"]
    lines = tr.format_setup_report(report, min_s=0.0)
    (line,) = [ln for ln in lines if ln.strip().startswith("log_report_root")]
    assert " x2 " in line and " = self " in line and "backend_compile" in line
    assert any(ln.strip().startswith("log_report_leaf x2") for ln in lines)
    # by default: from the process's start (or the oldest record) to now
    whole = tr.setup_report()
    assert whole["records"] >= report["records"] and whole["end"] >= report["end"]
    start = tr.process_start()
    assert start is None or start < min(r["start"] for r in tr.spans())


def test_cache_load_is_a_record_inside_its_compile(tmp_path):
    """A program read back from the persistent cache leaves ``cache_load``
    inside the ``backend_compile`` record that asked for it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        def probe():  # a new function each call: the same program, new to JAX's own memory
            def log_cached_probe(x):
                return x * 5 - 2

            return jax.jit(log_cached_probe)

        x = np.ones(6, np.float32)
        jax.block_until_ready(probe()(x))  # compiles and writes
        since = _last_id()
        with span("log_cache_home", registry=MetricsRegistry()):
            jax.block_until_ready(probe()(x))  # reads
    finally:
        jax.config.update("jax_enable_compilation_cache", False)
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
    by = {r["name"]: r for r in _mine(since)}
    load, compiled = by["cache_load"], by["backend_compile:log_cached_probe"]
    assert load["parent"] == compiled["parent"] == by["log_cache_home"]["id"]
    assert compiled["start"] - 1e-3 <= load["start"] < load["end"] <= compiled["end"] + 1e-3


def test_log_and_trace_share_a_clock(tmp_path):
    """A span entered under jax.profiler starts at the same moment in the log
    and on the written trace's host line (to 1 ms), through the log's own
    conversion; and the conversion to time.time() holds."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr, since = _trace_mod(), _last_id()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        time.sleep(0.02)
        with span("log_clock_probe", registry=MetricsRegistry()):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    planes = list(ProfileData.from_file(path).planes)
    (began,) = [v for p in planes for k, v in p.stats if k == "profile_start_time"]
    (event,) = [e for p in planes if p.name.startswith("/host:") for line in p.lines
                for e in line.events if e.name == "log_clock_probe"]
    (record,) = [r for r in _mine(since) if r["name"] == "log_clock_probe"]
    assert abs(tr.to_trace_ns(record["start"], began) - event.start_ns) < 1e6
    assert abs((record["end"] - record["start"]) * 1e9 - event.duration_ns) < 1e6
    now_perf, now_wall = time.perf_counter(), time.time()
    assert abs(tr.to_wall(now_perf) - now_wall) < 1e-3
    assert tr.from_wall(tr.to_wall(now_perf)) == pytest.approx(now_perf, abs=1e-6)


def test_span_log_stays_bounded():
    tr = _trace_mod()
    timer = span_timer("log_flood", registry=MetricsRegistry())
    for _ in range(tr.LOG_RECORDS + 10):
        with timer:
            pass
    log = tr.spans()
    assert len(log) == tr.LOG_RECORDS
    assert log[0]["name"] == log[-1]["name"] == "log_flood"  # the oldest went first


def test_one_jax_listener_of_each_kind_for_the_program():
    """Importing the modules again and arming further sentinels registers
    nothing further, and every sentinel still sees every compile."""
    import importlib

    import jax
    from jax._src import monitoring  # the public module has no getters

    from jumbo_mae_tpu_tpu.obs.retrace import RetraceSentinel

    def ours():
        return [[f for f in listeners
                 if getattr(f, "__module__", "").startswith("jumbo_mae_tpu_tpu")]
                for listeners in (monitoring.get_event_duration_listeners(),
                                  monitoring.get_event_time_span_listeners(),
                                  monitoring.get_event_listeners(),
                                  monitoring.get_scalar_listeners())]

    assert [len(kind) for kind in ours()] == [1, 1, 0, 1]
    importlib.import_module("jumbo_mae_tpu_tpu.obs.trace")
    importlib.import_module("jumbo_mae_tpu_tpu.obs.retrace")
    sentinels = [RetraceSentinel(f"log_listener_{i}", registry=MetricsRegistry())
                 for i in range(3)]
    try:
        for s in sentinels:
            s.arm()
        assert [len(kind) for kind in ours()] == [1, 1, 0, 1]
        with sentinels[0].expected("a probe"), sentinels[1].expected("a probe"), \
                sentinels[2].expected("a probe"):
            for k in (2, 3):
                jax.block_until_ready(jax.jit(lambda x: x + k)(np.ones(k, np.float32)))
        assert [s.summary()["compiles"] for s in sentinels] == [2, 2, 2]
        assert [s.summary()["violations"] for s in sentinels] == [0, 0, 0]
    finally:
        for s in sentinels:
            s.close()


def test_setup_spans_sit_where_the_work_happens(tmp_path, monkeypatch):
    """``state_shapes`` holds the first trace of the model's init (eval_shape
    and the sharding rules) and ends before ``state_init`` begins; the mesh,
    the optimizer, the model and the compile cache's path each leave a span."""
    import jax

    from jumbo_mae_tpu_tpu.cli.train import build_model
    from jumbo_mae_tpu_tpu.config import MeshConfig, OptimConfig, config_from_dict
    from jumbo_mae_tpu_tpu.parallel import create_mesh
    from jumbo_mae_tpu_tpu.train import create_sharded_state, make_optimizer
    from jumbo_mae_tpu_tpu.utils.procenv import enable_compile_cache

    since = _last_id()
    old_dir = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        enable_compile_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
    cfg = config_from_dict({
        "run": {"mode": "pretrain", "synthetic_data": True, "train_batch_size": 8},
        "model": {"preset": "vit_t16", "dec_layers": 1, "dec_dim": 32, "dec_heads": 2,
                  "overrides": {"image_size": 32, "dtype": "float32"}},
        "data": {"image_size": 32}})
    model, enc, _ = build_model(cfg)
    mesh = create_mesh(MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1])
    tx = make_optimizer(OptimConfig(warmup_steps=2, training_steps=10), global_batch_size=8)
    create_sharded_state(model, tx, {"images": np.zeros((8, 32, 32, 3), np.uint8)}, mesh,
                         mode="pretrain")
    mine = _mine(since)
    by = {r["name"]: r for r in mine}
    for name in ("compile_cache_setup", "model_build", "mesh_build", "optimizer_build",
                 "state_shapes", "state_init"):
        assert by[name]["parent"] is None, name
    assert by["state_shapes"]["end"] <= by["state_init"]["start"]
    homes = {r["parent"] for r in mine if r["name"] == "jit_trace:init_fn"}
    assert homes == {by["state_shapes"]["id"], by["state_init"]["id"]}


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
