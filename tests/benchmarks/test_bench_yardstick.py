"""The benchmark's yardstick against what it was copied from or written
against: FLOP arithmetic, the plain reference, the mask derivation, the
seeded input, the trace reduction — and that the harness takes a new
configuration, traffic mix, driver and metric as files, with no edit."""

import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops, harness, schedule, trace_reduce
from benchmarks.drivers import train_steps
from benchmarks.reference import model as ref_model
from benchmarks.reference import params as ref_params

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
TINY = {"image_size": 64, "patch_size": 16, "enc_layers": 2, "enc_dim": 64,
        "enc_heads": 4, "num_cls_tokens": 3, "mask_ratio": 0.75, "posemb": "sincos2d",
        "dec_layers": 2, "dec_dim": 32, "dec_heads": 4, "norm_pix_loss": True}


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_flops_copy_matches_the_program(entry):
    """A drift between ``benchmarks/flops.py`` and ``obs/mfu.py`` is seen."""
    from jumbo_mae_tpu_tpu.cli.train import build_model
    from jumbo_mae_tpu_tpu.obs import mfu
    from benchmarks.drivers.common import program_config

    config = json.loads((ROOT / entry["file"]).read_text())
    _, _, program_flops = build_model(program_config(config))
    assert flops.pretrain_step(config["model"]) == pytest.approx(program_flops, rel=1e-12)
    assert flops.peak("TPU v5 lite") == mfu.lookup_peak_tflops("TPU v5 lite") * 1e12
    with pytest.raises(ValueError):
        flops.peak("cpu")


def _program_model():
    from jumbo_mae_tpu_tpu.models.config import DecoderConfig, preset
    from jumbo_mae_tpu_tpu.models.mae import MAEPretrainModel

    enc = preset("vit_t16", labels=None, mask_ratio=0.75, posemb="sincos2d",
                 dtype="float32", image_size=64)
    dec = DecoderConfig(layers=2, dim=32, heads=4, dtype="float32")
    return MAEPretrainModel(enc, dec, norm_pix_loss=True), enc


def test_reference_matches_the_program_in_float32():
    """Same weights, images and mask through ``models/`` at float32 and
    through the plain reference. Both are float32 with "highest" matmuls, so
    they differ by summation order only: 1e-5 on loss and features; 1e-4 of
    the largest gradient entry of a leaf (the k-bias gradients are exactly
    zero in theory and float noise in practice, hence the leaf-max scale)."""
    from jumbo_mae_tpu_tpu.models.vit import JumboViT, pool_tokens
    from jumbo_mae_tpu_tpu.ops.preprocess import normalize_images

    model, enc = _program_model()
    shapes = ref_params.mae_shapes(TINY)
    program_tree = jax.eval_shape(
        lambda: model.init({"params": jax.random.key(0), "noise": jax.random.key(1)},
                           jnp.zeros((2, 64, 64, 3), jnp.uint8)))["params"]
    from benchmarks.drivers.common import require_same_tree

    require_same_tree(program_tree, shapes, "test")
    params = ref_params.make_params(np.uint32(3_000_000_123 % 2**32), shapes)
    images = schedule.image_pool(5, 4, 64)
    noise = jax.random.uniform(jax.random.key(5), (16,))
    with jax.default_matmul_precision("highest"):
        prog = lambda p: model.apply({"params": p}, images, True, mask_noise=noise)["loss"]
        loss_p, grad_p = jax.value_and_grad(prog)(params)
        loss_r, grad_r = jax.value_and_grad(
            lambda p: ref_model.mae_loss(p, images, noise, TINY))(params)
        tokens = JumboViT(enc.replace(mask_ratio=None)).apply(
            {"params": params["encoder"]}, normalize_images(images, dtype=jnp.float32), True)
        feat_p = pool_tokens(tokens, 3, "cls")
        feat_r = ref_model.features(params["encoder"], images, TINY)
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)
    np.testing.assert_allclose(feat_p, feat_r, rtol=0, atol=1e-5 * float(jnp.abs(feat_r).max()))
    scale = max(float(jnp.abs(g).max()) for g in jax.tree_util.tree_leaves(grad_r))
    for a, b in zip(jax.tree_util.tree_leaves(grad_p), jax.tree_util.tree_leaves(grad_r)):
        assert float(jnp.abs(a - b).max()) <= 1e-4 * max(float(jnp.abs(b).max()), 1e-3 * scale)


def test_mask_noise_is_the_programs_own_draw():
    """The harness derives each step's mask without the program; it has to
    be the mask the program's step draws."""
    from jumbo_mae_tpu_tpu.train.state import TrainState, make_base_rng

    model, _ = _program_model()
    seed, step = 3_000_000_777, 2
    state = SimpleNamespace(rng=make_base_rng(seed, 0), step=jnp.asarray(step, jnp.int32))
    rngs = TrainState.step_rngs(state)
    params = ref_params.make_params(np.uint32(1), ref_params.mae_shapes(TINY))
    out = model.apply({"params": params}, schedule.image_pool(1, 2, 64), False,
                      True, rngs=rngs)
    noise = train_steps.mask_noise(np.uint32(seed), step, 16)
    keep = np.argsort(np.asarray(noise))[:4]
    want = np.ones(16)
    want[keep] = 0
    np.testing.assert_array_equal(np.asarray(out["mask"][0]), want)


def test_lower_precision_moves_the_reference():
    params = ref_params.make_params(np.uint32(7), ref_params.encoder_shapes(TINY))
    images = schedule.image_pool(2, 4, 64)
    exact = ref_model.features(params, images, TINY)
    gap = lambda r: float(jnp.linalg.norm(ref_model.features(params, images, TINY, r) - exact)
                          / jnp.linalg.norm(exact))
    assert 0 < gap("bfloat16") < gap("fp8")


def test_input_is_the_seeds_and_every_row_differs():
    gen = schedule.image_batches(3_000_000_001, 4, 32, 2)
    first, second, third = (next(gen)["images"] for _ in range(3))
    assert first.shape == (4, 32, 32, 3) and first.dtype == np.uint8
    rows = np.concatenate([first, second]).reshape(8, -1)
    assert len({r.tobytes() for r in rows}) == 8
    np.testing.assert_array_equal(first, third)  # the cycle comes round
    np.testing.assert_array_equal(
        first, next(schedule.image_batches(3_000_000_001, 4, 32, 2))["images"])


class _Ev(SimpleNamespace):
    pass


def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=[_Ev(name=n, start_ns=s, duration_ns=d, stats=st)
                                         for n, s, d, st in evs]) for ln, evs in lines])


def test_trace_reduce_on_a_hand_made_trace():
    """Busy time and the window both come from the trace: the window is the
    time a program held the device, the time between programs is a named gap
    outside it, and nothing is clamped."""
    ops = [("%fusion.1 = f32[8] fusion(x)", 100, 50, []), ("%fusion.1 = f32[8] fusion(x)", 140, 60, []),
           ("copy.2", 400, 100, []), ("fusion.1", 700, 100, [])]
    host = _plane("/host:CPU", [("python", [("dispatch", 0, 90, []), ("fetch", 190, 320, []),
                                            ("something_else", 0, 1000, [])])])
    device = _plane("/device:TPU:0", [
        ("XLA Ops", ops),
        ("XLA Modules", [("jit_step(123)", 100, 400, []), ("jit_step(123)", 700, 150, [])])])
    r = trace_reduce.reduce_planes([device, host], ("dispatch", "fetch"))
    assert r["busy_s"] == pytest.approx(300e-9) and r["window_s"] == pytest.approx(550e-9)
    assert r["device_ops"] == [["fusion.1", pytest.approx(210e-9)], ["copy.2", pytest.approx(100e-9)]]
    assert dict(map(tuple, r["idle_gaps"])) == {
        "fetch": pytest.approx(200e-9), "between_programs_traced": pytest.approx(200e-9),
        "outside_spans": pytest.approx(50e-9)}
    assert r["programs"] == [["jit_step", 2, pytest.approx(550e-9), pytest.approx(275e-9)]]
    # operations that outlast their program show as a busy share over 100%
    short = _plane("/device:TPU:0", [("XLA Ops", ops[:3]), ("XLA Modules", [("p", 100, 150, [])])])
    r = trace_reduce.reduce_planes([short, host], ())
    assert r["busy_s"] == pytest.approx(200e-9) and r["window_s"] == pytest.approx(150e-9)
    # no device plane (the CPU rehearsal): first event to last
    cpu = _plane("/host:CPU", [("t", [("dot", 100, 50, [("hlo_op", "dot")]), ("dispatch", 0, 400, [])])])
    r = trace_reduce.reduce_planes([cpu], ("dispatch",))
    assert r["busy_s"] == pytest.approx(50e-9) and r["window_s"] == pytest.approx(400e-9)
    assert r["programs"] == []


def test_trace_reduce_reproduces_the_recorded_trace():
    """A trace recorded on the TPU v5e, kept beside the expected reduction."""
    here = Path(__file__).parent
    want = json.loads((here / "recorded_trace.expected.json").read_text())
    got = trace_reduce.reduce_file(str(here / "recorded_trace.xplane.pb"), ("dispatch", "fetch"))
    assert got == want


def test_a_new_config_traffic_driver_and_metric_are_only_files(tmp_path, capsys):
    """Copy the benchmark, then ADD a configuration, a traffic mix, a driver,
    a metric reader and their entries: the new cell runs with no edit to any
    file that was there."""
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks").rglob("*") if p.is_file()}
    b = tmp_path / "benchmarks"
    config = json.loads((b / "configs" / "jumbo_vit_l16_mae.json").read_text())
    config["name"] = "extra_config"
    (b / "configs" / "extra_config.json").write_text(json.dumps(config))
    (b / "traffic" / "extra_mix.json").write_text(json.dumps(
        {"driver": "extra_driver", "trace_seconds": 0.05, "matmuls": 3}))
    (b / "drivers" / "extra_driver.py").write_text('''
import time, jax, jax.numpy as jnp
from benchmarks.harness import span
class Driver:
    def __init__(self, cell): self.n = cell["traffic"]["matmuls"]; self.f = jax.jit(lambda x: x @ x)
    def warm(self): self.x = self.f(jnp.ones((64, 64))).block_until_ready()
    def window(self, seconds, seed):
        t = time.perf_counter()
        for _ in range(self.n):
            with span("dispatch"):
                self.f(self.x).block_until_ready()
        return {"window_s": time.perf_counter() - t, "attempted": self.n, "failed": 0,
                "samples": self.n, "extra_count": self.n}
    def program_bytes(self): return 0
    def check(self): return [("always_zero", 0.0, 0.0)]
    def close(self): pass
def build(cell, *, devices, seed): return Driver(cell)
''')
    (b / "metrics" / "extra_rate.py").write_text(
        'def read(record):\n    return record["extra_count"] / record["window_s"] if "extra_count" in record else None\n')
    (b / "metrics" / "extra_count.py").write_text(
        'def read(record):\n    return record.get("extra_count")\n')
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "extra_config", "source": "test", "reduced": [], "why": "test",
                             "file": "benchmarks/configs/extra_config.json"})
    bench["workloads"].append({"name": "extra_cell", "config": "extra_config",
                               "traffic": "extra_mix", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "extra_rate", "unit": "1/s", "better": "higher", "bound": 0.1,
                                "source": "host_clock", "workloads": ["extra_cell"]})
    bench["per_layer"].append({"name": "extra_count.x", "unit": "1", "better": "higher",
                               "source": "program_counter", "layer": "test", "moves": "extra_rate"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for trace in (False, True):
        cell = harness.load_cell("extra_cell", tmp_path)
        r = harness.run_cell(cell, seed=1, seconds=0.1, trace=trace, t0=time.perf_counter(),
                             require_tpu=False, compile_cache=False, scratch=tmp_path / "s")
        want = {"compile_s", "extra_count.x"} if trace else {"extra_rate", "setup_s"}
        assert r["correct"] and set(r["metrics"]) == want
    # an old cell, read from the enlarged files, is what it was
    assert harness.load_cell("l16_pretrain_b128", tmp_path)["per_layer"] == \
        harness.load_cell("l16_pretrain_b128")["per_layer"]
    after = {p: p.read_bytes() for p in before}
    assert after == before
