"""The linear-attention / grouped-query family's chip path without a chip: the
real cut of ``recipes/pretrain_solar_open2_share.yaml`` compiles for a
described v5e and fits under the ladder's line (``slow``: minutes), and two of
its layers compile in tier-1 under the same structural assertions. (Its own
file: the suite spreads files over its workers. ``chip_smoke``'s ``lm_train``
phase on this recipe is a case of ``test_chip_lm_train.py``.)"""

from __future__ import annotations

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import pytest

import chip_smoke
from test_chip_compile import (  # noqa: F401 - fixture
    assert_the_step_is_built_a_block_at_a_time,
    compile_lm_step,
    program_bytes,
    v5e_chip,
)

RECIPE = str(chip_smoke.REPO / "recipes" / "pretrain_solar_open2_share.yaml")
# what one AOT compile of this step read (PERF.md, PR 38; 15 756 047 360 with
# 16 heads held, 16 421 990 912 with 32), the ladder's line (no nearer the
# chip's limit than the fullest accepted cell), and the chip's own
# ... before the head's loss walked its tokens in tiles (PR 41); the step reads
# 14 020 593 664 since, and the bound is the older reading with no slack
PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES = 14_154_523_648, 15.2e9, 16.9e9


# tier-1's compile: the grouped-query block and one of the three
# linear-attention blocks, both with experts
DEPTH_CUT = ["model.lm.layers=2", "model.lm.layer_types=[full_attention, kda]"]
# what the cut's compile read before the short-convolution kernels (PR 45's
# tree; 9 404 760 064 with them, PR 46), the bound with no slack
CUT_PROGRAM_BYTES = 10_697_937_408


def assert_the_linear_and_grouped_query_step(text: str, cfg, lm) -> None:
    """Each linear-attention block runs the forward chunk kernel twice
    (forward and the block's recompute) and the backward kernel once, in the
    form that knows no floor under the decays, with no loop left under
    ``kda_core``; the grouped-query block runs each causal kernel once under
    ``attn_core`` (``assert_the_step_is_built_a_block_at_a_time``); no rope
    kernel is called and no ``rope`` scope exists; nothing sized (seq, seq) a
    head or (tokens, d_k, d_v) is built."""
    assert_the_step_is_built_a_block_at_a_time(text, cfg, lm)
    rows, seq = cfg.run.train_batch_size, cfg.data.seq_len
    assert not re.search(r'op_name="[^"]*/rope[/"]', text)
    assert lm.attn_heads() == {"full_attention": (8, 64), "kda": (8, 64)}
    h, e = lm.kda_heads, lm.kda_head_dim
    for wide in (f"[{rows},{h},{seq},{e},{e}]", f"[{rows},{h},{seq},{lm.kda_chunk},{e}]",
                 f"[{rows},{lm.heads},{seq},{seq}]", f"[{seq},{seq}]"):
        assert wide not in text, wide


def test_linear_and_grouped_query_step_compiles_for_v5e_at_cut_depth(v5e_chip, monkeypatch):  # noqa: F811
    """Two of the recipe's four layers at its published widths, 2 x 8192
    tokens: every structural assertion of the full compile, which is ``slow``."""
    cfg, lm, _, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch, DEPTH_CUT)
    assert (lm.kinds, lm.first_k_dense) == (("full_attention", "kda"), 0)
    assert_the_linear_and_grouped_query_step(compiled.as_text(), cfg, lm)  # 3 · 3 · 3 short-conv calls
    assert program_bytes(compiled) <= CUT_PROGRAM_BYTES


# slow: 154 s of one worker; the chip run of every cell covers "fits". By hand
# after a change to the family's program: pytest -m slow tests/test_chip_compile*.py
@pytest.mark.slow
def test_linear_and_grouped_query_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):  # noqa: F811
    """837 M parameters, 2 x 8192 tokens, through the trainer's own step
    factory: what ``assert_the_linear_and_grouped_query_step`` holds of the
    three linear-attention blocks (6 . 3 . 0 kernel calls), the one
    grouped-query block and the four expert layers, and what the step holds
    fits under the ladder's line."""
    cfg, lm, parameters, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch)
    assert parameters == 836_709_784
    assert lm.kinds == ("full_attention", "kda", "kda", "kda") and lm.first_k_dense == 0
    assert_the_linear_and_grouped_query_step(compiled.as_text(), cfg, lm)
    held = program_bytes(compiled)
    assert 8.4e9 < held <= min(PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES), held
