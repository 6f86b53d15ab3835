"""The window / rope-free-full family's chip path without a chip: the real cut
of ``recipes/pretrain_smallthinker_21b_share.yaml`` compiles for a described
v5e and fits under the ladder's line (``slow``: over a minute), and two of its
layers compile in tier-1 under the same structural assertions. (Its own file:
the suite spreads files over its workers. ``chip_smoke``'s ``lm_train`` phase
on this recipe is a case of ``test_chip_lm_train.py``.)"""

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

RECIPE = str(chip_smoke.REPO / "recipes" / "pretrain_smallthinker_21b_share.yaml")
# what one AOT compile of this step read (PERF.md, PR 40), the ladder's line
# (no nearer the chip's limit than the fullest accepted cell), and the chip's own
# ... before the head's loss walked its tokens in tiles (PR 41); the step reads
# 10 352 944 640 since, and the bound is the older reading with no slack
PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES = 13_045_875_712, 15.2e9, 16.9e9


# tier-1's compile: the rope-free full layer and one of the three window
# layers, both with experts
DEPTH_CUT = ["model.lm.layers=2", "model.lm.layer_types=[full_attention, sliding_attention]"]


def assert_the_window_and_rope_free_full_step(text: str, cfg, lm) -> None:
    """One row of 16 384 tokens: each block runs each causal kernel once, the
    rope-free full layer under ``attn_core``, the window layers under
    ``swa_core``, at a group of 7 and, in the backward kernel, one span of 16
    key blocks; the rope kernel turns the window layers' q and k and nothing
    of the full layer's; no linear-attention kernel runs
    (``assert_the_step_is_built_a_block_at_a_time``); nothing sized (seq, seq)
    a head is built; the expert layers walk their held pairs in a loop under
    ``moe_dispatch`` with the router's ``top_k`` traced inside the block's
    ``moe`` module; no shared expert's product exists."""
    assert_the_step_is_built_a_block_at_a_time(text, cfg, lm)
    rows, seq = cfg.run.train_batch_size, cfg.data.seq_len
    assert (rows, seq) == (1, 16384)
    assert lm.kinds[0] == "full_attention" and lm.rope("full_attention") is None
    assert not re.search(r'op_name="[^"]*block_0/attn/rope[/"]', text)
    # 136 and 70 block pairs walked; the 16 diagonal ones, and the 12 the
    # window's edge cuts, at 10 of their 16 sub-tiles: 130 and 59.5 pairs' worth
    assert lm.attn_pairs(seq) == {"full_attention": (136_314_880, 134_225_920),
                                  "sliding_attention": (62_390_272, 58_722_304)}
    for wide in (f"[{rows},{lm.heads},{seq},{seq}]", f"[{lm.heads},{seq},{seq}]", f"[{seq},{seq}]"):
        assert wide not in text, wide
    assert "/shared_expert/" not in text and "/dense_mlp/" not in text
    assert re.search(r'op_name="[^"]*block_1/moe/router/[^"]*top_k', text)


def test_window_and_rope_free_full_step_compiles_for_v5e_at_cut_depth(v5e_chip, monkeypatch):  # noqa: F811
    """Two of the recipe's four layers at its published widths, one row of
    16 384 tokens: every structural assertion of the full compile, which is
    ``slow``."""
    cfg, lm, _, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch, DEPTH_CUT)
    assert (lm.kinds, lm.first_k_dense) == (("full_attention", "sliding_attention"), 0)
    assert_the_window_and_rope_free_full_step(compiled.as_text(), cfg, lm)


# slow: 85 s of one worker; the chip run of every cell covers "fits". By hand
# after a change to the family's program: pytest -m slow tests/test_chip_compile*.py
@pytest.mark.slow
def test_window_and_rope_free_full_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):  # noqa: F811
    """657 M parameters, one row of 16 384 tokens, through the trainer's own
    step factory: what ``assert_the_window_and_rope_free_full_step`` holds of
    the one full and three window layers (4 + 4 causal kernel calls, 3 x 2 x 3
    of the rope kernel) and the four expert layers; the state has no
    ``batch_stats``; and what the step holds fits under the ladder's line."""
    cfg, lm, parameters, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch)
    assert parameters == 656_529_920
    assert lm.kinds == ("full_attention",) + ("sliding_attention",) * 3 and lm.first_k_dense == 0
    assert_the_window_and_rope_free_full_step(compiled.as_text(), cfg, lm)
    held = program_bytes(compiled)
    assert 8.4e9 < held <= min(PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES), held
