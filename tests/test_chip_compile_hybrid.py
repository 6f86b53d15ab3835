"""The hybrid language family's chip path without a chip: the real cut of
``recipes/pretrain_ling3_flash_ep64.yaml`` compiles for a described v5e and
fits, and ``chip_smoke``'s ``lm_train`` phase, given that recipe, runs end to
end on the CPU at toy size. (Its own file: the compile takes minutes, and the
suite spreads files over its workers.)"""

from __future__ import annotations

import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np

import chip_smoke
from test_chip_compile import v5e_chip, watch  # noqa: F401 - fixtures

RECIPE = str(chip_smoke.REPO / "recipes" / "pretrain_ling3_flash_ep64.yaml")
# what one AOT compile of this step read (PERF.md, PR 32; 15 875 868 160 with
# the chunk scan in place of the kernels, PR 31), and the chip's own line:
# 16 GiB less what the runtime keeps
PROGRAM_BYTES, CHIP_BYTES = 15_168_317_440, 16.9e9


def test_hybrid_language_model_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):  # noqa: F811
    """822 M parameters, 2 x 8192 tokens, through the trainer's own step
    factory: the one MLA block runs each causal kernel once, the six
    linear-attention blocks run the forward chunk kernel twice (forward and
    the block's recompute, which keeps every chunk's starting state) and the
    backward kernel once, with no loop left under ``kda_core``, and build
    nothing sized (tokens, d_k, d_v) or (tokens, chunk, d_k) for a whole
    sequence,
    the expert layers walk their held pairs in a loop, the guard adds no
    ``conditional``, and what the step holds fits the chip."""
    from jumbo_mae_tpu_tpu.cli.train import build_model
    from jumbo_mae_tpu_tpu.config import load_config
    from jumbo_mae_tpu_tpu.parallel import MeshConfig, create_mesh
    from jumbo_mae_tpu_tpu.parallel.sharding import batch_sharding, infer_state_sharding
    from jumbo_mae_tpu_tpu.train import make_optimizer, make_train_step
    from jumbo_mae_tpu_tpu.train.state import TrainState, make_base_rng

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = load_config(RECIPE)
    mesh = create_mesh(MeshConfig(data=1, fsdp=1), devices=list(v5e_chip.device_set))
    model, lm, _ = build_model(cfg)
    tx = make_optimizer(cfg.optim, cfg.run.train_batch_size, num_layers=lm.layers)
    rows, length = cfg.run.train_batch_size, cfg.data.seq_len + 1 + lm.mtp_layers

    def init():
        v = model.init(jax.random.key(0), jnp.zeros((rows, length), jnp.int32))
        state = TrainState.create(apply_fn=model.apply, params=v["params"], tx=tx,
                                  batch_stats=v["batch_stats"], rng=make_base_rng(0))
        return state.replace(step=jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(init)
    assert sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(shapes.params)) \
        == 822_033_344
    sharding = infer_state_sharding(shapes, mesh)
    described = jax.tree_util.tree_map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d), shapes, sharding)
    tokens = jax.ShapeDtypeStruct((rows, length), jnp.int32,
                                  sharding=batch_sharding(mesh, accum=False))
    step = make_train_step(mesh, sharding, mode="lm", guard_nonfinite=True)
    compiled = step.lower(described, {"tokens": tokens}).compile()
    text = compiled.as_text()
    assert " conditional(" not in text and "/guard/" in text
    assert chip_smoke.causal_kernel_calls(text) == {"fwd": 1, "dq": 1, "dkv": 1}
    assert "gmm" in text
    assert chip_smoke.kda_kernel_calls(text) == {"fwd": 2 * lm.kda_layers, "bwd": lm.kda_layers,
                                                 "loops": 0}
    assert lm.kda_layers == 6
    seq, h, e = cfg.data.seq_len, lm.heads, lm.kda_head_dim
    for wide in (f"[{rows},{h},{seq},{e},{e}]", f"[{rows},{h},{seq},{lm.kda_chunk},{e}]",
                 f"[{rows},{h},{seq // lm.kda_chunk},{lm.kda_chunk},{lm.kda_chunk},{e}]"):
        assert wide not in text, wide
    loops = [line for line in text.splitlines()
             if " while(" in line and '/moe/moe_dispatch/while"' in line]
    assert len(loops) == 2 * 6, len(loops)  # forward and backward of six expert layers
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes)
    assert 8.2e9 < held < min(PROGRAM_BYTES * 1.01, CHIP_BYTES), held


LM_TOY = [
    "data.seq_len=24", "run.train_batch_size=8", "run.valid_batch_size=8", "mesh.fsdp=1",
    "optim.learning_rate=3e-3", "optim.init_lr=3e-3", "optim.warmup_steps=1",
    *(f"model.lm.{k}={v}" for k, v in dict(
        vocab_size=512, vocab_rows=[64, 64], dim=32, heads=2, kda_head_dim=16, kda_chunk=8,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        dense_hidden=64, expert_hidden=16, shared_expert_hidden=16, n_routed_experts=16,
        experts_held=[4, 4], n_group=4, topk_group=2, experts_per_token=4,
        dtype="float32").items()),
]


def test_lm_train_phase_rehearsal_on_the_hybrid_recipe(tmp_path, watch, capsys):  # noqa: F811
    """The recipe's own pattern (7 blocks: dense KDA, KDA, KDA, KDA, KDA,
    MLA, KDA) through ``cli.train`` at toy widths: every batch's loss lower
    on its second visit, nothing dropped or skipped, and the
    linear-attention counters logged and published beside the experts'."""
    steps = 10
    overrides = chip_smoke._lm_overrides(steps) + LM_TOY
    assert chip_smoke.run_phase(
        "lm_train",
        lambda: chip_smoke.phase_lm_train(RECIPE, overrides, tmp_path, steps=steps),
        tmp_path, watch,
    )
    (line,) = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    checked = line["checked"]
    assert checked["loss_after_one_cycle"] < checked["loss_first"]
    assert checked["moe_dropped"] == 0 and checked["skipped_steps"] == 0
    assert checked["causal_kernel_calls"] == {"fwd": 0, "dq": 0, "dkv": 0}
    assert checked["kda_kernel_calls"] == {"fwd": 0, "bwd": 0, "loops": 3 * 6}  # off the chip: the scan
    assert 0 < checked["kda_state_absmax_max"] < 10
    low, high = checked["kda_decay_mean_min_max"]
    assert 0.9 < low <= high < 1.0
    from jumbo_mae_tpu_tpu.obs.metrics import get_registry

    snapshot = get_registry().snapshot()
    assert {"state_absmax", "decay_mean", "state_absmax_l0", "decay_mean_l6"} <= set(
        snapshot["train_kda"])
    assert "state_absmax_l5" not in snapshot["train_kda"]  # block 5 is the MLA block
    assert {"imbalance", "rounds_l1", "rounds_l6"} <= set(snapshot["train_moe"])
