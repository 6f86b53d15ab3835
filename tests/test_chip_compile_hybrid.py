"""The hybrid language family's chip path without a chip: the real cut of
``recipes/pretrain_ling3_flash_ep64.yaml`` compiles for a described v5e and
fits (``slow``: minutes), and two of its layers compile in tier-1 under the
same structural assertions. (Its own file: the suite spreads files over its
workers. ``chip_smoke``'s ``lm_train`` phase on this recipe is a case of
``test_chip_lm_train.py``.)"""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import pytest

import chip_smoke
from test_chip_compile import (  # noqa: F401 - fixture
    assert_the_step_is_built_a_block_at_a_time,
    compile_lm_step,
    program_bytes,
    v5e_chip,
)

RECIPE = str(chip_smoke.REPO / "recipes" / "pretrain_ling3_flash_ep64.yaml")
# what one AOT compile of this step read (PERF.md, PR 37: one backward causal
# kernel whose key/value outputs are sequence-long blocks; 15 168 317 440 with
# two, PR 32; 15 875 868 160 with the chunk scan in place of the kernels, PR
# 31), and the chip's own line: 16 GiB less what the runtime keeps
# ... before the head's loss walked its tokens in tiles (PR 41); the step reads
# 14 999 557 632 since, and the bound is the older reading with no slack
PROGRAM_BYTES, CHIP_BYTES = 15_168_285_184, 16.9e9


# tier-1's compile: one linear-attention block (the dense one) and one MLA
# block with experts, the period cut from six to two with the depth
DEPTH_CUT = ["model.lm.layers=2", "model.lm.layer_group_size=2"]
# what the cut's compile read before the short-convolution kernels (PR 45's
# tree; 6 402 869 760 with them, PR 46: the filter's and the norms' float32
# temporaries are gone), the bound with no slack
CUT_PROGRAM_BYTES = 7_628_414_976


def assert_the_hybrid_step(text: str, cfg, lm) -> None:
    """Each MLA block runs each of the two causal kernels once, each
    linear-attention block the forward chunk kernel twice (forward and the
    block's recompute, which keeps every chunk's starting state) and the
    backward kernel once, with no loop left under ``kda_core``
    (``assert_the_step_is_built_a_block_at_a_time``), and nothing sized
    (tokens, d_k, d_v) or (tokens, chunk, d_k) for a whole sequence is built."""
    assert_the_step_is_built_a_block_at_a_time(text, cfg, lm)
    rows, seq, h, e = cfg.run.train_batch_size, cfg.data.seq_len, lm.heads, lm.kda_head_dim
    for wide in (f"[{rows},{h},{seq},{e},{e}]", f"[{rows},{h},{seq},{lm.kda_chunk},{e}]",
                 f"[{rows},{h},{seq // lm.kda_chunk},{lm.kda_chunk},{lm.kda_chunk},{e}]"):
        assert wide not in text, wide


def test_hybrid_language_model_step_compiles_for_v5e_at_cut_depth(v5e_chip, monkeypatch):  # noqa: F811
    """Two of the recipe's seven layers at its published widths, 2 x 8192
    tokens: every structural assertion of the full compile, which is ``slow``."""
    cfg, lm, _, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch, DEPTH_CUT)
    assert (lm.kinds, lm.first_k_dense) == (("kda", "mla"), 1)
    assert_the_hybrid_step(compiled.as_text(), cfg, lm)  # 3 · 3 · 3 short-convolution calls
    assert program_bytes(compiled) <= CUT_PROGRAM_BYTES


# slow: 215 s of one worker; the chip run of every cell covers "fits". By hand
# after a change to the family's program: pytest -m slow tests/test_chip_compile*.py
@pytest.mark.slow
def test_hybrid_language_model_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):  # noqa: F811
    """822 M parameters, 2 x 8192 tokens, through the trainer's own step
    factory: what ``assert_the_hybrid_step`` holds of the one MLA block, the
    six linear-attention blocks and the six expert layers, and what the step
    holds fits the chip."""
    cfg, lm, parameters, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch)
    assert parameters == 822_033_344
    assert (lm.kda_layers, lm.layers, lm.first_k_dense) == (6, 7, 1)
    assert_the_hybrid_step(compiled.as_text(), cfg, lm)
    held = program_bytes(compiled)
    assert 8.2e9 < held <= min(PROGRAM_BYTES, CHIP_BYTES), held
