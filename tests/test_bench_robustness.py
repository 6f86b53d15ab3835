"""bench.py contracts that need no chip: without a TPU the bench fails and
prints no result (a CPU run is not a device measurement), and the per-leg
knob resolution is pure and env-proof."""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_bench_fails_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=str(REPO),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0, proc.stdout
    assert proc.stdout.strip() == ""  # no result line of any kind
    assert "needs a TPU" in proc.stderr


def test_leg_config_f32_leg_is_env_proof():
    """The f32 leg is the FIXED reference-style baseline: neither BENCH_*
    env knobs nor the spec's bf16-leg defaults may leak into it — otherwise
    a sweep silently re-tunes its own baseline and the ratio is garbage."""
    import bench

    hostile_env = {
        "BENCH_REMAT": "0",
        "BENCH_REMAT_POLICY": "dots_no_batch",
        "BENCH_GATHER_IMPL": "onehot",
        "BENCH_MU_DTYPE": "bfloat16",
        "BENCH_NU_DTYPE": "bfloat16",
        "BENCH_DEC_REMAT_POLICY": "dots",
    }
    hostile_env["BENCH_ATTN_IMPL"] = "flash"
    got = bench.leg_config("vit_h14", "float32", env=hostile_env)
    assert got == dict(
        grad_ckpt=True,  # spec remat (f32@32 needs dots to fit 16 GB)
        remat_policy="dots",
        gather_impl="take",
        dec_remat=None,
        mu_dtype=None,
        nu_dtype=None,
        param_dtype=None,
        attn_impl="auto",
        dec_heads=0,
    )


def test_leg_config_bf16_defaults_and_overrides():
    import bench

    # vit_h14 bf16 leg, clean env: the baked-in A/B winners
    got = bench.leg_config("vit_h14", "bfloat16", env={})
    assert got == dict(
        grad_ckpt=False,
        remat_policy="dots",  # policy string only matters when ckpt is on
        gather_impl="onehot",
        dec_remat=None,
        mu_dtype="bfloat16",
        nu_dtype="bfloat16",
        param_dtype=None,
        attn_impl="auto",
        dec_heads=0,
    )
    # param storage dtype: env-only knob until an A/B promotes a default;
    # "float32" is the explicit off-spelling and normalizes to None
    got = bench.leg_config("vit_h14", "bfloat16", env={"BENCH_PARAM_DTYPE": "bfloat16"})
    assert got["param_dtype"] == "bfloat16"
    got = bench.leg_config("vit_h14", "bfloat16", env={"BENCH_PARAM_DTYPE": "float32"})
    assert got["param_dtype"] is None
    # malformed BENCH_REMAT dies with a clear message, not a ValueError
    import pytest as _pytest

    with _pytest.raises(SystemExit, match="BENCH_REMAT"):
        bench.leg_config("vit_h14", "bfloat16", env={"BENCH_REMAT": "true"})
    # explicit off-spellings flip every default-on knob back off
    off = {
        "BENCH_REMAT": "1",
        "BENCH_GATHER_IMPL": "take",
        "BENCH_MU_DTYPE": "float32",
        "BENCH_NU_DTYPE": "float32",
    }
    got = bench.leg_config("vit_h14", "bfloat16", env=off)
    assert got["grad_ckpt"] is True
    assert got["gather_impl"] == "take"
    assert got["mu_dtype"] == "float32"
    assert got["nu_dtype"] == "float32"
    # vit_l16 bf16 leg: bf16 moments, but take gather (onehot loses on L)
    got = bench.leg_config("vit_l16", "bfloat16", env={})
    assert got["gather_impl"] == "take"
    assert got["mu_dtype"] == "bfloat16"
    assert got["grad_ckpt"] is False
    # BENCH_REMAT_POLICY alone must turn remat ON for a remat=False model
    got = bench.leg_config("vit_l16", "bfloat16", env={"BENCH_REMAT_POLICY": "dots"})
    assert got["grad_ckpt"] is True and got["remat_policy"] == "dots"
