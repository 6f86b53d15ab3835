"""``correct`` has to be able to come out false: a run driven through the
harness with the timed path broken underneath, and the lower-precision
control at test size under the committed limits."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import tiny_cell
from benchmarks import harness
from benchmarks.drivers import train_steps

pytestmark = pytest.mark.usefixtures("cpu_has_no_peak")


def _run(name, tmp_path):
    return harness.run_cell(
        tiny_cell(harness.load_cell(name)), seed=2_147_483_999, seconds=0.4,
        trace=False, t0=time.perf_counter(), require_tpu=False,
        compile_cache=False, scratch=tmp_path,
    )


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
    result = _run("l16_pretrain_b128", tmp_path)
    assert result["correct"] is False
    out = capsys.readouterr().out
    assert "param_change_norm_gap" in out and "FAILED" in out


def test_the_lower_precision_control_fails_the_committed_limits():
    """The reference in the program's place, computed in fp8, at test size:
    bfloat16 (the configuration's own precision) passes, the control fails
    one number."""
    cell = tiny_cell(harness.load_cell("l16_pretrain_b128"))
    config, seed = cell["config"], 77
    from benchmarks import schedule

    gen = schedule.image_batches(seed, 8, 64, 2)
    batches = [next(gen)["images"] for _ in range(train_steps.CHECK_STEPS)]
    ref = train_steps.reference_run(config, seed, batches)
    sound = train_steps.reference_run(config, seed, batches, rounding="bfloat16")
    control = train_steps.reference_run(config, seed, batches, rounding="fp8")
    ok = lambda checks: all(v <= limit for _, v, limit in checks)
    assert ok(train_steps.compare(sound, ref))
    assert not ok(train_steps.compare(control, ref))

