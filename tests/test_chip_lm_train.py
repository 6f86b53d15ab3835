"""``chip_smoke``'s ``lm_train`` phase rehearsed on the CPU at toy widths,
once for each language recipe: the recipe's own layer pattern through
``cli.train``, every batch's loss lower on its second visit, nothing dropped,
nothing skipped, the family's counters logged and published. One test, a case
a recipe."""

from __future__ import annotations

import json

import pytest

import chip_smoke
from test_chip_compile import watch  # noqa: F401 - fixture

from jumbo_mae_tpu_tpu.ops.pallas.attention import _sub_tile

RECIPES = chip_smoke.REPO / "recipes"
# every recipe is cut the same way: 8 sequences a step, a 64-row slice of a
# 512-row vocabulary, width 32, 16 experts top-4 of which 4 are held
_COMMON = dict(vocab_size=512, vocab_rows=[64, 64], dim=32, dense_hidden=64, expert_hidden=16,
               n_routed_experts=16, experts_held=[4, 4], experts_per_token=4, dtype="float32")


def _toy(seq: int, **lm) -> list[str]:
    return [f"data.seq_len={seq}", "run.train_batch_size=8", "run.valid_batch_size=8",
            "mesh.fsdp=1", "optim.learning_rate=3e-3", "optim.init_lr=3e-3",
            "optim.warmup_steps=1", *(f"model.lm.{k}={v}" for k, v in (_COMMON | lm).items())]


def _all_mla(checked, published, records):
    """Two blocks + the MTP block of latent attention."""
    assert checked["moe_rounds"] == 1
    assert 0.1 < checked["moe_held_share_min_max"][0] <= checked["moe_held_share_min_max"][1] < 0.5
    assert checked["mfu_trainer_reported"] is None  # a CPU count is not a device rate
    assert checked["kda_kernel_calls"] == {"fwd": 0, "bwd": 0, "loops": 0}
    # 16 tokens are one clamped block of 128: under 256 a masked pair is one strip
    assert checked["attn_pairs"] == {"mla": {"visited": 128 * 128, "needed": 16 * 17 // 2}}
    assert _sub_tile(128) == 128
    assert {"imbalance", "held_share", "dropped", "rounds", "rows_max_l1", "rows_min_mtp",
            "rounds_l1", "rounds_mtp"} <= set(published["train_moe"])
    assert published["train_moe"]["rounds"] == 1


def _hybrid(checked, published, records):
    """7 blocks: dense KDA, KDA, KDA, KDA, KDA, MLA, KDA; the
    linear-attention counters beside the experts'."""
    assert checked["kda_kernel_calls"] == {"fwd": 0, "bwd": 0, "loops": 3 * 6}  # off the chip: the scan
    # ... and the plain filter, SiLU and norm where the chip runs one kernel a tensor
    assert checked["short_conv_kernel_calls"] == {"fwd": 0, "recompute": 0, "bwd": 0}
    assert 0 < checked["kda_state_absmax_max"] < 10
    low, high = checked["kda_decay_mean_min_max"]
    assert 0.9 < low <= high < 1.0
    assert set(checked["attn_pairs"]) == {"mla"}
    assert {"state_absmax", "decay_mean", "state_absmax_l0", "decay_mean_l6"} <= set(
        published["train_kda"])
    assert "state_absmax_l5" not in published["train_kda"]  # block 5 is the MLA block
    assert {"imbalance", "rounds_l1", "rounds_l6"} <= set(published["train_moe"])


def _grouped_query(checked, published, records):
    """8 blocks: (full, sliding, sliding, sliding) twice, 6 and 8 query
    heads over 2 key/value heads, a window of 11 of 24 tokens; the static
    pair counts are in the phase's line and, once, in the trainer's log."""
    assert checked["kda_kernel_calls"] == {"fwd": 0, "bwd": 0, "loops": 0}
    pairs = checked["attn_pairs"]
    assert set(pairs) == {"full_attention", "sliding_attention"}
    assert pairs["full_attention"]["needed"] == 24 * 25 // 2
    assert pairs["sliding_attention"]["needed"] == 11 * 12 // 2 + 13 * 11
    assert all(p["visited"] >= p["needed"] for p in pairs.values())
    (logged,) = [r for r in records if "train/attn_pairs_needed_sliding_attention" in r]
    assert logged["train/attn_pairs_needed_sliding_attention"] == 11 * 12 // 2 + 13 * 11
    assert logged["train/attn_pairs_visited_full_attention"] == pairs["full_attention"]["visited"]
    assert {"imbalance", "rounds_l1", "rounds_l7"} <= set(published["train_moe"])
    assert "rounds_l0" not in published["train_moe"]  # block 0 has the dense MLP


def _linear_and_grouped_query(checked, published, records):
    """4 blocks: rope-free grouped-query, KDA, KDA, KDA; 4 query heads over 2
    key/value heads and 2 KDA heads held of 16 and 8 published; beta runs to
    2 and the counters say how often it passes 1."""
    assert checked["kda_kernel_calls"] == {"fwd": 0, "bwd": 0, "loops": 3 * 3}  # the scan
    assert set(checked["attn_pairs"]) == {"full_attention"}
    assert checked["attn_heads"] == {"full_attention": {"held": 4, "published": 16},
                                     "kda": {"held": 2, "published": 8}}
    assert 1.0 < checked["kda_beta_max"] < 2.0
    low, high = checked["kda_neg_eig_share_min_max"]
    assert 0.2 < low <= high < 0.8
    (logged,) = [r for r in records if "train/attn_heads_held_kda" in r]
    assert (logged["train/attn_heads_held_kda"], logged["train/attn_heads_published_kda"]) == (2, 8)
    assert {"state_absmax", "decay_mean", "beta_max", "neg_eig_share", "neg_eig_share_l1",
            "beta_max_l3"} <= set(published["train_kda"])
    # block 0 is the grouped-query block (the registry outlives a run, the log does not)
    assert not [r for r in records if "train/kda_beta_max_l0" in r]
    assert [r for r in records if "train/kda_beta_max_l1" in r]
    assert {"imbalance", "rounds_l0", "rounds_l3"} <= set(published["train_moe"])


def _window_and_routed_from_the_input(checked, published, records):
    """4 blocks: rope-free full, window x3 (11 of 24 tokens), 7 query heads
    over 1 key/value head, every block sparse, no shared expert; the router
    reads the block's input and the ReLU gate's zeros are logged."""
    assert checked["kda_kernel_calls"] == {"fwd": 0, "bwd": 0, "loops": 0}
    pairs = checked["attn_pairs"]
    assert set(pairs) == {"full_attention", "sliding_attention"}
    assert pairs["sliding_attention"]["needed"] == 11 * 12 // 2 + 13 * 11
    assert checked["attn_heads"] == {kind: {"held": 7, "published": 7} for kind in pairs}
    assert checked["router_input"] == "block_input"
    low, high = checked["moe_act_zero_share_min_max"]
    assert 0.3 < low <= high < 0.7
    assert {"imbalance", "act_zero_share", "act_zero_share_l0", "act_zero_share_l3",
            "rounds_l0"} <= set(published["train_moe"])  # block 0 is sparse too
    assert [r for r in records if "train/moe_act_zero_share_l2" in r]


def _short_conv_and_grouped_query(checked, published, records):
    """9 blocks: dense conv, full attention, conv x3, full attention, conv
    x3; 4 query heads over 2 key/value heads with q/k norms, a tied head: a
    conv block has no heads, no pairs and no rope, and the static counters
    say how many blocks are of each kind."""
    assert checked["kda_kernel_calls"] == {"fwd": 0, "bwd": 0, "loops": 0}
    assert checked["layers_by_kind"] == {"conv": 7, "full_attention": 2}
    assert checked["qk_norm"] is True and checked["tie_embeddings"] is True
    assert set(checked["attn_pairs"]) == {"full_attention"}
    assert checked["attn_pairs"]["full_attention"]["needed"] == 24 * 25 // 2
    assert checked["attn_heads"] == {"full_attention": {"held": 4, "published": 4}}
    assert checked["head_product_calls"]["fwd"] == 3
    (logged,) = [r for r in records if "train/layers_conv" in r]
    assert (logged["train/layers_conv"], logged["train/layers_full_attention"]) == (7, 2)
    assert {"imbalance", "rounds_l1", "rounds_l8"} <= set(published["train_moe"])
    # block 0 has the dense MLP (the registry outlives a run, the log does not)
    assert not [r for r in records if "train/moe_rounds_l0" in r]
    assert [r for r in records if "train/moe_rounds_l8" in r]


def _block_diffusion(checked, published, records):
    """2 blocks of grouped-query attention with q/k norms, 8 query heads over
    1 key/value head, every block sparse, trained on the masked tokens of a
    noisy copy: 24 clean tokens a row in blocks of 4; the objective's counter
    is logged and the static pair count is the pattern's."""
    assert checked["kda_kernel_calls"] == {"fwd": 0, "bwd": 0, "loops": 0}
    assert checked["bd_kernel_calls"] == {"fwd": 0, "bwd": 0}  # off the chip: the einsum form
    assert checked["diffusion_block"] == 4 and checked["qk_norm"] is True
    low, high = checked["bd_masked_share_min_max"]
    assert 0.3 < low <= high < 0.7  # 48 blocks a step about the mean of U[0.001, 1)
    assert checked["attn_pairs"] == {"block_diffusion": {"visited": 3 * 128 * 128,
                                                         "needed": 24 * 24 + 24 * 4}}
    assert checked["attn_heads"] == {"full_attention": {"held": 8, "published": 8}}
    (logged,) = [r for r in records if "train/attn_pairs_needed_block_diffusion" in r]
    assert logged["train/attn_pairs_visited_block_diffusion"] == 3 * 128 * 128
    assert [r for r in records if "train/bd_masked_share" in r]
    assert {"imbalance", "rounds_l0", "rounds_l1"} <= set(published["train_moe"])


CASES = [
    pytest.param("pretrain_joyai_flash_ep16", _toy(
        16, layers=2, heads=2, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16), _all_mla, id="joyai_flash"),
    pytest.param("pretrain_ling3_flash_ep64", _toy(
        24, heads=2, kda_head_dim=16, kda_chunk=8, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, shared_expert_hidden=16, n_group=4, topk_group=2),
        _hybrid, id="ling3_flash"),
    pytest.param("pretrain_laguna_xs2_share", _toy(
        24, heads_per_layer=[6, 8, 8, 8, 6, 8, 8, 8], kv_heads=2, head_dim=16, sliding_window=11,
        shared_expert_hidden=16), _grouped_query, id="laguna_xs2"),
    pytest.param("pretrain_solar_open2_share", _toy(
        24, heads=4, kv_heads=2, head_dim=16, kda_heads=2, kda_head_dim=16, kda_gate_rank=16,
        kda_chunk=8, heads_published="{full_attention: 16, kda: 8}"),
        _linear_and_grouped_query, id="solar_open2"),
    pytest.param("pretrain_smallthinker_21b_share", _toy(
        24, heads=7, kv_heads=1, head_dim=16, sliding_window=11),
        _window_and_routed_from_the_input, id="smallthinker_21b"),
    pytest.param("pretrain_lfm2_24b_share", _toy(24, heads=4, kv_heads=2, head_dim=16),
                 _short_conv_and_grouped_query, id="lfm2_24b"),
    pytest.param("pretrain_sdar_30b_share", _toy(
        24, layers=2, layer_types="[full_attention, full_attention]", heads=8, kv_heads=1,
        head_dim=16), _block_diffusion, id="sdar_30b"),
]


@pytest.mark.parametrize("recipe,toy,family", CASES)
def test_lm_train_phase_rehearsal(recipe, toy, family, tmp_path, watch, capsys):  # noqa: F811
    steps = 10
    path = str(RECIPES / f"{recipe}.yaml")
    overrides = chip_smoke._lm_overrides(steps) + toy
    assert chip_smoke.run_phase(
        "lm_train",
        lambda: chip_smoke.phase_lm_train(path, overrides, tmp_path, steps=steps),
        tmp_path, watch,
    )
    (line,) = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    checked = line["checked"]
    # (a block-diffusion recipe's steps all draw one noise in the smoke: one_noise_draw)
    assert checked["loss_after_one_cycle"] < checked["loss_first"]
    assert checked["moe_dropped"] == 0 and checked["skipped_steps"] == 0
    # on the CPU the core resolves to its einsum form: no kernel in the step
    assert checked["causal_kernel_calls"] == {"fwd": 0, "bwd": 0}
    assert checked["rope_kernel_calls"] == 0  # and rope to its jax.numpy form
    from jumbo_mae_tpu_tpu.obs.metrics import get_registry

    run_name = chip_smoke._load(path, overrides).run.name
    family(checked, get_registry().snapshot(), chip_smoke._read_metrics(tmp_path / run_name))
