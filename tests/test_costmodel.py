"""Compiled-cost observability contracts (ISSUE round 12).

Four layers, one extraction: XLA's own cost/memory accounting read off every
compiled executable (never triggering a compile), the analytic roofline
capacity model over those costs, the schema-versioned bench ledger
(BENCH_HISTORY.jsonl), and ``tools/perf_doctor.py``'s regression verdicts.

The load-bearing invariants:

- the train step dispatches through ONE AOT executable — cost extraction is
  a free readout, never a second compile of the hot path;
- engine bucket executables publish per-bucket costs, flops grow with the
  bucket, and the int8 variant's argument bytes shrink vs f32;
- extraction degrades to ``None`` on backends that report nothing (PJRT
  plugins may legally return empty analyses) — it must never raise;
- two bench runs on the same host get the SAME ledger ``env_key`` (the CI
  smoke asserts this across real subprocesses), and perf_doctor exits 2
  exactly when a leg moves beyond the noise band, naming the leg AND the
  dominant roofline term.
"""

import json

import numpy as np
import pytest

from jumbo_mae_tpu_tpu.obs.costmodel import (
    COST_SCHEMA_VERSION,
    ProgramCost,
    cost_asdict,
    extract_cost,
    publish_cost,
    utilization_report,
)
from jumbo_mae_tpu_tpu.obs.metrics import MetricsRegistry
from jumbo_mae_tpu_tpu.obs.perfledger import (
    append_row,
    comparable_env,
    env_key,
    make_row,
    read_ledger,
    resolve_history_path,
)
from jumbo_mae_tpu_tpu.obs.perfmodel import (
    chip_spec,
    detect_chip,
    dp_comm_bytes,
    fsdp_comm_bytes,
    prediction_asdict,
    publish_drift,
    roofline,
)

COST_KEYS = {
    "cost_schema",
    "program",
    "flops",
    "bytes_accessed",
    "argument_bytes",
    "output_bytes",
    "temp_bytes",
    "peak_bytes",
    "generated_code_bytes",
    "source",
}


# ------------------------------------------------------- train-step costs


@pytest.fixture(scope="module")
def train_step_cost():
    """One tiny pretrain step on the CPU mesh, stepped twice, plus its
    extracted cost — shared across the class below (the compile is the
    expensive part)."""
    import jax

    from jumbo_mae_tpu_tpu.models import DecoderConfig, MAEPretrainModel, preset
    from jumbo_mae_tpu_tpu.parallel import MeshConfig, create_mesh
    from jumbo_mae_tpu_tpu.train import (
        OptimConfig,
        create_sharded_state,
        make_optimizer,
        make_train_step,
    )

    tiny = preset("vit_t16", image_size=32, patch_size=8, dtype="float32")
    module = MAEPretrainModel(
        tiny.replace(mask_ratio=0.75, labels=None),
        DecoderConfig(layers=1, dim=32, heads=2, dtype="float32"),
    )
    opt = OptimConfig(
        name="adamw",
        learning_rate=1e-3,
        lr_scaling="none",
        warmup_steps=2,
        training_steps=20,
    )
    batch = {
        "images": np.random.RandomState(0)
        .randint(0, 256, (4, 32, 32, 3))
        .astype(np.uint8)
    }
    mesh = create_mesh(MeshConfig(data=1, fsdp=1))
    state, sharding = create_sharded_state(
        module,
        make_optimizer(opt, global_batch_size=256),
        batch,
        mesh,
        mode="pretrain",
        init_seed=0,
        rng_seed=0,
    )
    step = make_train_step(mesh, sharding, mode="pretrain")
    for _ in range(2):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics["loss"])
    execs = step.executables
    cost = extract_cost(next(iter(execs.values())), "train_step")
    return step, execs, cost


class TestTrainStepCost:
    def test_single_aot_executable_no_hot_path_recompile(self, train_step_cost):
        """Two steps at one batch shape → exactly one executable. The AOT
        handle IS the dispatched program, so reading its cost_analysis can
        never add a compile to the hot path."""
        _, execs, _ = train_step_cost
        assert len(execs) == 1

    def test_cost_extraction_nonzero(self, train_step_cost):
        _, _, cost = train_step_cost
        assert cost is not None and cost.program == "train_step"
        assert cost.flops > 0 and cost.bytes_accessed > 0
        assert cost.source in ("compiled", "lowered")
        if cost.source == "compiled":
            # peak is live-at-once: at least the scratch, at most the sum
            assert cost.peak_bytes >= cost.temp_bytes
            assert cost.peak_bytes <= (
                cost.argument_bytes + cost.output_bytes + cost.temp_bytes
            )

    def test_cost_asdict_schema_stable(self, train_step_cost):
        """Journal events and ledger rows carry this dict — the key set is
        the offline-reader contract and only moves with COST_SCHEMA_VERSION."""
        _, _, cost = train_step_cost
        d = cost_asdict(cost)
        assert set(d) == COST_KEYS
        assert d["cost_schema"] == COST_SCHEMA_VERSION
        json.dumps(d)  # journal-serializable as-is

    def test_publish_cost_sets_labeled_gauges(self, train_step_cost):
        _, _, cost = train_step_cost
        reg = MetricsRegistry()
        publish_cost(cost, bucket="", dtype="float32", registry=reg)
        fam = reg.gauge(
            "xla_flops", labels=("program", "bucket", "dtype")
        )
        assert fam.labels("train_step", "", "float32").value == cost.flops
        peak = reg.gauge("xla_peak_bytes", labels=("program", "bucket", "dtype"))
        assert peak.labels("train_step", "", "float32").value == cost.peak_bytes

    def test_utilization_split_hfu_vs_mfu(self, train_step_cost):
        """HFU counts what XLA actually scheduled (remat recompute included),
        MFU what the math requires — with XLA flops above analytic flops the
        split must order the same way."""
        _, _, cost = train_step_cost
        rep = utilization_report(
            cost.flops * 0.8, cost.flops, steps_per_sec=10.0, peak_tflops=275.0
        )
        assert rep.hardware_flops_utilization > rep.model_flops_utilization > 0
        assert rep.achieved_hardware_tflops == pytest.approx(
            cost.flops * 10.0 / 1e12
        )


class TestExtractionDegrades:
    """A backend that reports nothing yields None/partial — never a raise."""

    def test_cost_analysis_raises(self):
        class Ex:
            def cost_analysis(self):
                raise NotImplementedError("plugin says no")

        assert extract_cost(Ex(), "p") is None

    def test_cost_analysis_empty(self):
        class Ex:
            def cost_analysis(self):
                return []

        assert extract_cost(Ex(), "p") is None

    def test_memory_analysis_missing_degrades_to_lowered(self):
        class Ex:
            def cost_analysis(self):
                return [{"flops": 42.0, "bytes accessed": 7.0}]

            def memory_analysis(self):
                raise NotImplementedError

        cost = extract_cost(Ex(), "p")
        assert cost.source == "lowered"
        assert cost.flops == 42.0 and cost.bytes_accessed == 7.0
        assert cost.peak_bytes == 0.0

    def test_publish_none_is_noop(self):
        publish_cost(None, registry=MetricsRegistry())


# ---------------------------------------------------------- engine costs


def _tiny_cfg(extra=()):
    from pathlib import Path

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
        ]
        + list(extra),
    )


def _images(n, size=32, seed=0):
    return (
        np.random.RandomState(seed).randint(0, 256, (n, size, size, 3))
    ).astype(np.uint8)


@pytest.fixture(scope="module")
def engine_f32(tmp_path_factory):
    from jumbo_mae_tpu_tpu.infer import InferenceEngine

    reg = MetricsRegistry()
    cache = tmp_path_factory.mktemp("warmcache")
    eng = InferenceEngine(
        _tiny_cfg(), max_batch=8, warm_cache=cache, registry=reg
    )
    eng.features(_images(1))
    eng.features(_images(2))
    return eng, reg, cache


class TestEngineCosts:
    def test_per_bucket_costs_and_flops_ordering(self, engine_f32):
        eng, _, _ = engine_f32
        keys = sorted(eng.cost_reports, key=lambda k: k[1])
        assert [k[1] for k in keys] == [1, 2]
        c1, c2 = (eng.cost_reports[k] for k in keys)
        assert 0 < c1.flops <= c2.flops

    def test_bucket_gauges_published(self, engine_f32):
        eng, reg, _ = engine_f32
        flops = reg.gauge("xla_flops", labels=("program", "bucket", "dtype"))
        child = flops.labels("features:cls", "2", "float32")
        assert child.value == eng.cost_reports[("features:cls", 2)].flops
        compile_g = reg.gauge(
            "infer_bucket_compile_seconds", labels=("task", "bucket")
        )
        assert compile_g.labels("features:cls", "2").value > 0
        size_g = reg.gauge("infer_executable_bytes", labels=("task", "bucket"))
        assert size_g.labels("features:cls", "2").value > 0

    def test_drift_gauge_after_dispatch(self, engine_f32):
        eng, reg, _ = engine_f32
        drift = reg.gauge("perf_predict_vs_measured", labels=("program",))
        assert drift.labels("features:cls/b2").value > 0

    def test_warmcache_entry_meta(self, engine_f32):
        """The cache sidecar carries compile seconds, blob size, and the
        cost snapshot — a warm start can account for what it skipped."""
        eng, _, _ = engine_f32
        meta = eng.warmcache.entry_meta(eng._entry_name("features:cls", 1))
        assert meta is not None
        assert meta["compile_seconds"] > 0
        assert meta["executable_bytes"] > 0
        assert meta["cost"]["cost_schema"] == COST_SCHEMA_VERSION

    def test_warm_start_publishes_cost_and_saved_seconds(self, engine_f32):
        """A second engine over the same cache loads instead of compiling —
        and still publishes per-bucket costs plus the compile time it saved."""
        from jumbo_mae_tpu_tpu.infer import InferenceEngine

        eng, _, cache = engine_f32
        reg2 = MetricsRegistry()
        compiles = []
        eng2 = InferenceEngine(
            _tiny_cfg(),
            max_batch=8,
            warm_cache=cache,
            registry=reg2,
            on_compile=lambda task, bucket: compiles.append((task, bucket)),
        )
        eng2.features(_images(2))
        assert compiles == []  # served from the warm cache
        assert (("features:cls", 2)) in eng2.cost_reports
        saved = reg2.counter("infer_warmcache_saved_seconds_total", labels=("task",))
        assert saved.labels("features:cls").value > 0

    def test_int8_argument_bytes_below_f32(self, engine_f32):
        from jumbo_mae_tpu_tpu.infer import InferenceEngine

        eng, _, _ = engine_f32
        eng8 = InferenceEngine(
            _tiny_cfg(),
            max_batch=8,
            quant="int8",
            warm_cache=False,
            registry=MetricsRegistry(),
        )
        eng8.features(_images(1))
        (key,) = [k for k in eng8.cost_reports if k[1] == 1]
        c8 = eng8.cost_reports[key]
        cf = eng.cost_reports[("features:cls", 1)]
        if c8.source == "compiled" and cf.source == "compiled":
            assert c8.argument_bytes < cf.argument_bytes


# -------------------------------------------------------------- roofline


class TestRoofline:
    CHIP = chip_spec("TPU v4")

    def test_chip_spec_normalizes_and_refuses_unknown_kinds(self):
        assert chip_spec("TPU v5 lite").name == "v5e"
        assert chip_spec("TPU v4").peak_tflops == 275.0
        # the CPU backend's own kind gets the documented generic entry ...
        assert chip_spec("cpu").name == "cpu"
        assert detect_chip().name == "cpu"  # the suite runs on the CPU
        # ... but an accelerator that is not in the tables is an error,
        # never a made-up spec
        for kind in ("TPU v9 mystery", "mystery accelerator", ""):
            with pytest.raises(ValueError, match="no chip-spec entry"):
                chip_spec(kind)

    def test_bound_transitions(self):
        """Small flops at big bytes → bandwidth-bound; scale flops up and
        the same program goes compute-bound; add enough comm and it flips
        again."""
        lo = roofline(1e9, 1e9, self.CHIP)
        assert lo.bound == "bandwidth"
        hi = roofline(1e15, 1e9, self.CHIP)
        assert hi.bound == "compute"
        comm = roofline(1e9, 1e9, self.CHIP, comm_bytes=1e12)
        assert comm.bound == "comm"

    def test_step_time_monotone_in_flops_and_bytes(self):
        t = [
            roofline(f, 1e9, self.CHIP).step_time_s
            for f in (1e12, 1e13, 1e14, 1e15)
        ]
        assert t == sorted(t)
        t = [
            roofline(1e9, b, self.CHIP).step_time_s
            for b in (1e9, 1e10, 1e11)
        ]
        assert t == sorted(t)

    def test_throughput_scales_with_batch(self):
        """Per-item cost fixed → throughput grows linearly with batch."""
        p1 = roofline(1e12, 1e10, self.CHIP, batch=1)
        p8 = roofline(8e12, 8e10, self.CHIP, batch=8)
        assert p8.throughput_per_sec == pytest.approx(
            p1.throughput_per_sec, rel=1e-6
        )
        assert p8.step_time_s == pytest.approx(8 * p1.step_time_s, rel=1e-6)

    def test_comm_terms(self):
        # FSDP: all-gather fwd + all-gather bwd + reduce-scatter = 3·P·(n-1)/n
        assert fsdp_comm_bytes(1e9, fsdp=4) == pytest.approx(3e9 * 3 / 4)
        assert fsdp_comm_bytes(1e9, fsdp=1) == 0.0
        # DP ring all-reduce = 2·P·(n-1)/n
        assert dp_comm_bytes(1e9, dp=2) == pytest.approx(2e9 * 1 / 2)
        assert dp_comm_bytes(1e9, dp=1) == 0.0

    def test_prediction_asdict_round_trips(self):
        d = prediction_asdict(roofline(1e12, 1e10, self.CHIP, batch=4))
        json.dumps(d)
        assert d["bound"] in ("compute", "bandwidth", "comm")
        assert d["step_time_s"] > 0

    def test_publish_drift(self):
        reg = MetricsRegistry()
        ratio = publish_drift(0.010, 0.020, program="train_step", registry=reg)
        assert ratio == pytest.approx(2.0)
        fam = reg.gauge("perf_predict_vs_measured", labels=("program",))
        assert fam.labels("train_step").value == pytest.approx(2.0)
        pred = reg.gauge("perf_predicted_step_seconds", labels=("program",))
        assert pred.labels("train_step").value == pytest.approx(0.010)


class TestDeviceKindNormalizer:
    def test_known_spellings_collapse(self):
        from jumbo_mae_tpu_tpu.obs.mfu import (
            PEAK_TFLOPS,
            lookup_peak_tflops,
            normalize_device_kind,
        )

        assert normalize_device_kind("TPU v4") == "v4"
        assert normalize_device_kind("TPU v5 lite") == "v5e"
        assert normalize_device_kind("TPU v5litepod-8") == "v5e"
        assert normalize_device_kind("TPU v6 lite") == "v6e"
        assert normalize_device_kind("Tesla T4") is None
        assert lookup_peak_tflops("TPU v5 lite") == PEAK_TFLOPS["v5e"]

    def test_unknown_kind_raises(self):
        from jumbo_mae_tpu_tpu.obs.mfu import lookup_peak_tflops

        with pytest.raises(ValueError, match="weird-chip-x1"):
            lookup_peak_tflops("weird-chip-x1")


# ------------------------------------------------------------ perf ledger


class TestPerfLedger:
    def test_row_shape_and_env_key_stability(self):
        r1 = make_row(bench="train", metric="m", legs={"ms": 1.0})
        r2 = make_row(bench="train", metric="m", legs={"ms": 2.0})
        for r in (r1, r2):
            assert r["schema"] == 1 and r["bench"] == "train"
            assert "env" in r and "env_key" in r and "legs" in r
        # same process, same host → identical comparability key (the CI
        # smoke asserts this across two real bench subprocesses)
        assert r1["env_key"] == r2["env_key"]
        assert r1["env_key"] == env_key(comparable_env())
        # per-process noise must NOT leak into comparability
        assert "pid" not in r1["env"] and "argv" not in r1["env"]

    def test_append_read_round_trip(self, tmp_path):
        path = tmp_path / "hist.jsonl"
        for v in (1.0, 2.0):
            assert append_row(path, make_row(bench="train", metric="m", legs={"ms": v}))
        rows = read_ledger(path)
        assert [r["legs"]["ms"] for r in rows] == [1.0, 2.0]

    def test_torn_lines_tolerated(self, tmp_path):
        """A crash mid-write leaves a torn line — possibly with NO trailing
        newline. The next append must land intact and the reader must skip
        only the torn fragment."""
        path = tmp_path / "hist.jsonl"
        append_row(path, make_row(bench="train", metric="m", legs={"ms": 1.0}))
        with open(path, "a") as f:
            f.write('{"torn": tru')  # no newline: worst-case torn write
        assert append_row(path, make_row(bench="train", metric="m", legs={"ms": 2.0}))
        rows = read_ledger(path)
        assert [r["legs"]["ms"] for r in rows] == [1.0, 2.0]

    def test_append_never_raises(self, tmp_path):
        target = tmp_path / "dir_not_file"
        target.mkdir()
        assert append_row(target, {"schema": 1}) is False

    def test_resolve_history_path(self, monkeypatch):
        monkeypatch.delenv("BENCH_HISTORY", raising=False)
        assert resolve_history_path("x.jsonl").name == "x.jsonl"
        assert str(resolve_history_path(None)) == "BENCH_HISTORY.jsonl"
        monkeypatch.setenv("BENCH_HISTORY", "/tmp/h.jsonl")
        assert str(resolve_history_path(None)) == "/tmp/h.jsonl"
        assert resolve_history_path("off") is None
        monkeypatch.setenv("BENCH_HISTORY", "off")
        assert resolve_history_path(None) is None


# ------------------------------------------------------------ perf_doctor


def _ledger(tmp_path, values, *, leg="ms_step_bf16", metric="ms_step"):
    import tools.perf_doctor  # noqa: F401 - ensures tools is importable

    path = tmp_path / "BENCH_HISTORY.jsonl"
    pred = prediction_asdict(roofline(5e10, 2e9, chip_spec("cpu"), batch=8))
    for v in values:
        append_row(
            path,
            make_row(
                bench="train",
                metric=metric,
                legs={leg: v},
                quantiles={"p50_ms": v},
                prediction=pred,
            ),
        )
    return path


class TestPerfDoctor:
    def test_exit_0_on_steady_history(self, tmp_path):
        import tools.perf_doctor as doctor

        path = _ledger(tmp_path, [100.0, 102.0, 98.0, 101.0])
        assert doctor.main([str(path)]) == 0

    def test_exit_2_names_leg_and_roofline_term(self, tmp_path):
        import tools.perf_doctor as doctor

        path = _ledger(tmp_path, [100.0, 102.0, 98.0, 160.0])
        out = tmp_path / "report.md"
        assert doctor.main([str(path), "--out", str(out)]) == 2
        report = out.read_text()
        assert "ms_step_bf16" in report and "REGRESSION" in report
        assert "roofline term: bandwidth" in report

    def test_higher_is_better_legs_regress_on_drop(self, tmp_path):
        import tools.perf_doctor as doctor

        path = _ledger(
            tmp_path,
            [1000.0, 990.0, 1010.0, 600.0],
            leg="engine_imgs_per_sec",
            metric="imgs_per_sec",
        )
        out = tmp_path / "report.md"
        assert doctor.main([str(path), "--out", str(out)]) == 2
        assert "engine_imgs_per_sec" in out.read_text()

    def test_improvement_is_not_a_regression(self, tmp_path):
        import tools.perf_doctor as doctor

        path = _ledger(tmp_path, [100.0, 102.0, 98.0, 60.0])
        assert doctor.main([str(path)]) == 0

    def test_noise_band_is_respected(self, tmp_path):
        import tools.perf_doctor as doctor

        path = _ledger(tmp_path, [100.0, 102.0, 98.0, 106.0])
        assert doctor.main([str(path), "--noise", "0.08"]) == 0
        assert doctor.main([str(path), "--noise", "0.02"]) == 2

    def test_exit_2_on_missing_or_empty(self, tmp_path):
        import tools.perf_doctor as doctor

        assert doctor.main([str(tmp_path / "nope.jsonl")]) == 2
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert doctor.main([str(empty)]) == 2

    def test_journal_fallback_reports_compiled_programs(self, tmp_path):
        """Pointed at a run journal instead of a ledger, the doctor renders
        the compiled-program table (cost basis of the run) instead of
        exiting confused."""
        import tools.perf_doctor as doctor

        from jumbo_mae_tpu_tpu.obs.journal import RunJournal

        with RunJournal(tmp_path) as j:
            j.event(
                "compiled_program",
                program="train_step",
                flops=1e9,
                bytes_accessed=1e8,
                cost_schema=COST_SCHEMA_VERSION,
            )
        out = tmp_path / "report.md"
        assert doctor.main([str(tmp_path), "--out", str(out)]) == 0
        assert "train_step" in out.read_text()
