"""``correct`` has to be able to come out false in the language-model cell
too: a run through the harness with the timed path broken underneath, and
the lower-precision control at test size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import driver_of, run_tiny, tiny_cell
from benchmarks import harness
from benchmarks.drivers import train_loop

pytestmark = pytest.mark.usefixtures("cpu_has_no_peak")
CELL = "joyai_flash_pretrain_2x8k"


def test_a_step_that_returns_its_state_unchanged_is_not_correct(tmp_path, monkeypatch, capsys):
    import jumbo_mae_tpu_tpu.train as train_pkg

    real_factory = train_pkg.make_train_step

    def factory(*args, **kw):
        real = real_factory(*args, **kw)

        def broken(state, batch):
            keep = jax.tree_util.tree_map(jnp.copy, state)
            _, metrics = real(state, batch)
            return keep.replace(step=keep.step + 1), metrics

        broken.executables = real.executables
        return broken

    monkeypatch.setattr(train_pkg, "make_train_step", factory)
    result = run_tiny(CELL, False, tmp_path, seconds=0.4, seed=2_147_483_999)[1]
    assert result["correct"] is False
    out = capsys.readouterr().out
    assert "param_change_norm_gap" in out and "FAILED" in out


def test_the_lower_precision_control_fails_the_limits_the_sound_run_passes():
    """The reference in the program's place at test size: computed in
    bfloat16 (the configuration's own precision) it passes the test-size
    limits, computed in fp8 (the control) it fails one of them."""
    cell = tiny_cell(harness.load_cell(CELL))
    mod = driver_of(cell)
    config, t, seed = cell["config"], cell["traffic"], 77
    gen = mod.token_batches(seed, config, t["sequences_per_chip"], t["seq"], 2)
    batches = [next(gen)["tokens"] for _ in range(train_loop.CHECK_STEPS)]
    ref = mod.reference_run(config, seed, batches)
    sound = mod.reference_run(config, seed, batches, rounding="bfloat16")
    control = mod.reference_run(config, seed, batches, rounding=mod.CONTROL)
    limits = mod.LIMITS | mod.TINY_LIMITS
    ok = lambda checks: all(v <= limit for _, v, limit in checks)
    assert ok(train_loop.compare(sound, ref, limits))
    assert not ok(train_loop.compare(control, ref, limits))


def test_the_driver_translates_every_size_of_the_configuration():
    """``lm_fields`` hands the program the published router width and
    vocabulary beside what the chip holds, and the program's tree is the
    reference's (``require_same_tree`` refuses a run otherwise)."""
    from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig

    cell = harness.load_cell(CELL)
    cfg = MlaMoeConfig(**driver_of(cell).lm_fields(cell["config"]))
    assert (cfg.n_routed_experts, cfg.held, cfg.vocab_size, cfg.rows) == (
        256, (0, 16), 129280, (0, 16160))
    assert (cfg.layers, cfg.first_k_dense, cfg.mtp_layers, cfg.experts_per_token) == (5, 1, 1, 8)
    assert (cfg.dim, cfg.heads, cfg.q_lora_rank, cfg.kv_lora_rank) == (2048, 32, 1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.dense_hidden, cfg.expert_hidden, cfg.rope_theta) == (7168, 768, 32e6)


def test_the_token_rate_is_the_sample_rate_times_the_sequence_length():
    """The cell's end-to-end rate counts samples (``train_img_per_s``, the
    benchmark's one accepted training rate): a sample is one sequence, and
    the per-layer ``train_tok_per_s.lm`` is the same work in tokens."""
    cell = harness.load_cell(CELL)
    assert "train_img_per_s" in {m["name"] for m in cell["end_to_end"]}
    rate = next(m for m in cell["per_layer"] if m["name"] == "train_tok_per_s.lm")
    assert rate["moves"] == "train_img_per_s" and rate["unit"] == "tok/s/chip"
    mod = driver_of(cell)
    driver = object.__new__(mod.Driver)
    t = cell["traffic"]
    driver.config, driver.batch, driver.seq = cell["config"], t["sequences_per_chip"], t["seq"]
    record = driver.work(25) | {"window_s": 24.0, "chips": 1}
    img = harness.load_module("metrics", "train_img_per_s").read(record)
    tok = harness.load_module("metrics", "train_tok_per_s").read(record)
    assert record["images"] == 25 * 2 and tok == pytest.approx(img * 8192, rel=1e-12)
