"""The grouped-query language family's chip path without a chip: the real
cut of ``recipes/pretrain_laguna_xs2_share.yaml`` compiles for a described
v5e and fits (``slow``: minutes), and two of its layers compile in tier-1
under the same structural assertions. (Its own file: the suite spreads files
over its workers. ``chip_smoke``'s ``lm_train`` phase on this recipe is a
case of ``test_chip_lm_train.py``.)"""

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

RECIPE = str(chip_smoke.REPO / "recipes" / "pretrain_laguna_xs2_share.yaml")
# what one AOT compile of this step read (PERF.md, PR 37: one backward causal
# kernel; 13 059 776 000 with two, PR 35; 13 096 821 760 before the rope
# kernel, PR 33), and the chip's own line: 16 GiB less what the runtime keeps
# ... before the head's loss walked its tokens in tiles (PR 41); the step reads
# 12 991 600 640 since, and the bound is the older reading with no slack
PROGRAM_BYTES, CHIP_BYTES = 13_058_227_712, 16.9e9


# tier-1's compile: the first two of the recipe's eight layers, one full (the
# dense block) and one window layer with experts, at their own head counts
DEPTH_CUT = ["model.lm.layers=2", "model.lm.layer_types=[full_attention, sliding_attention]",
             "model.lm.heads_per_layer=[48, 64]"]


def assert_the_grouped_query_step(text: str, cfg, lm) -> None:
    """Each block runs the forward and the one backward causal kernel once,
    a full layer's under ``attn_core`` and a window layer's under
    ``swa_core``, and turns its ``q`` and its ``k`` through the rope kernel
    three times (``assert_the_step_is_built_a_block_at_a_time``); no
    ``causal_attention_dq`` / ``_dkv`` is left; no float32 array of q's or
    k's shape is left under the ``rope`` scope; nothing sized (seq, seq) a
    head exists; the window layers' tables walk the band and their kernels
    compute a masked pair by its sub-tiles (1.5 x the entries their mask
    keeps at block 1024; 3.9 x by whole pairs, 8.3 by the triangle)."""
    assert_the_step_is_built_a_block_at_a_time(text, cfg, lm)
    rows, seq = cfg.run.train_batch_size, cfg.data.seq_len
    assert "causal_attention_dq" not in text and "causal_attention_dkv" not in text
    under_rope = [line for line in text.splitlines() if re.search(r'op_name="[^"]*/rope/', line)]
    assert len(under_rope) >= lm.layers * 2 * 3
    for h in sorted({*lm.heads_per_layer, lm.kv_heads}):
        assert not [line for line in under_rope if f"f32[{rows},{h},{seq}," in line], h
    for h in sorted({*lm.heads_per_layer, lm.kv_heads, lm.heads_per_layer[1] // lm.kv_heads}):
        for wide in (f"[{rows},{h},{seq},{seq}]", f"[{h},{seq},{seq}]"):
            assert wide not in text, wide
    assert f"[{seq},{seq}]" not in text
    pairs = lm.attn_pairs(seq)
    # a full layer's 8 diagonal pairs run at 10 of their 16 sub-tiles of 256; a
    # window layer's band at block 1024 is 8 diagonal pairs at 9 sub-tiles
    # (the window ends inside them) and 7 trailing ones at 3
    assert pairs == {"full_attention": (33 * 1024 * 1024, seq * (seq + 1) // 2),
                     "sliding_attention": ((8 * 9 + 7 * 3) * 256 * 256,
                                           512 * 513 // 2 + (seq - 512) * 512)}
    visited, needed = pairs["sliding_attention"]
    assert 1.49 < visited / needed < 1.5


def test_grouped_query_language_model_step_compiles_for_v5e_at_cut_depth(v5e_chip, monkeypatch):  # noqa: F811
    """Two of the recipe's eight layers at its published widths, 2 x 8192
    tokens: every structural assertion of the full compile, which is ``slow``."""
    cfg, lm, _, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch, DEPTH_CUT)
    assert (lm.kinds, lm.first_k_dense) == (("full_attention", "sliding_attention"), 1)
    assert_the_grouped_query_step(compiled.as_text(), cfg, lm)


# slow: 176 s of one worker; the chip run of every cell covers "fits". By hand
# after a change to the family's program: pytest -m slow tests/test_chip_compile*.py
@pytest.mark.slow
def test_grouped_query_language_model_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):  # noqa: F811
    """766 M parameters, 2 x 8192 tokens, through the trainer's own step
    factory: what ``assert_the_grouped_query_step`` holds of the two full and
    six window layers (8 + 8 causal kernel calls, 8 x 2 x 3 of the rope
    kernel) and the seven expert layers, and what the step holds fits the
    chip."""
    cfg, lm, parameters, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch)
    assert parameters == 765_954_048
    assert (lm.layer_types.count("full_attention"), lm.layer_types.count("sliding_attention"),
            lm.first_k_dense) == (2, 6, 1)
    assert_the_grouped_query_step(compiled.as_text(), cfg, lm)
    held = program_bytes(compiled)
    assert 7.6e9 < held <= min(PROGRAM_BYTES, CHIP_BYTES), held
