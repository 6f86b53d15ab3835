"""The grouped-query language family's chip path without a chip: the real
cut of ``recipes/pretrain_laguna_xs2_share.yaml`` compiles for a described
v5e and fits. (Its own file: the compile takes a minute, and the suite
spreads files over its workers. ``chip_smoke``'s ``lm_train`` phase on this
recipe is a case of ``test_chip_lm_train.py``.)"""

from __future__ import annotations

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import chip_smoke
from test_chip_compile import (  # noqa: F401 - fixture
    assert_the_head_walks_its_tokens_in_tiles,
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


def test_grouped_query_language_model_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):  # noqa: F811
    """766 M parameters, 2 x 8192 tokens, through the trainer's own step
    factory: each of the eight blocks runs the forward and the one backward
    causal kernel once (a rematted block keeps the forward kernel's output
    and log-sum-exp; no ``causal_attention_dq`` / ``_dkv`` is left), the
    two full layers' under ``attn_core`` and the six window layers' under
    ``swa_core``; nothing sized (seq, seq) a head exists; the window layers'
    tables walk the band (2.0 x the entries their mask keeps, not the
    triangle's 8.3); each block turns its ``q`` and its ``k`` through the
    rope kernel three times (forward, under the block's rematerialisation,
    transposed: 8 x 2 x 3 calls) and no float32 array of their shapes is
    left under the ``rope`` scope; the expert layers walk their held pairs
    in a loop, the guard adds no ``conditional``, and what the step holds
    fits the chip."""
    cfg, lm, parameters, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch)
    assert parameters == 765_954_048
    rows, seq = cfg.run.train_batch_size, cfg.data.seq_len
    text = compiled.as_text()
    assert " conditional(" not in text and "/guard/" in text
    assert chip_smoke.causal_kernel_calls(text) == {"fwd": 8, "bwd": 8}
    assert_the_head_walks_its_tokens_in_tiles(text, cfg, lm)
    by_kind = {scope: len(re.findall(
        rf'custom-call\([^\n]*/{scope}/causal_attention_\w+/pallas_call"', text))
        for scope in ("attn_core", "swa_core")}
    assert by_kind == {"attn_core": 2 * 2, "swa_core": 2 * 6}
    assert "causal_attention_dq" not in text and "causal_attention_dkv" not in text
    assert (lm.layer_types.count("full_attention"), lm.layer_types.count("sliding_attention")) \
        == (2, 6)
    assert chip_smoke.rope_kernel_calls(text) == 8 * 2 * 3
    under_rope = [line for line in text.splitlines() if re.search(r'op_name="[^"]*/rope/', line)]
    assert len(under_rope) >= 8 * 2 * 3
    for h in sorted({*lm.heads_per_layer, lm.kv_heads}):
        assert not [line for line in under_rope if f"f32[{rows},{h},{seq}," in line], h
    for h in sorted({*lm.heads_per_layer, lm.kv_heads, lm.heads_per_layer[1] // lm.kv_heads}):
        for wide in (f"[{rows},{h},{seq},{seq}]", f"[{h},{seq},{seq}]"):
            assert wide not in text, wide
    assert f"[{seq},{seq}]" not in text
    pairs = lm.attn_pairs(seq)
    assert pairs == {"full_attention": (36 * 1024 * 1024, seq * (seq + 1) // 2),
                     "sliding_attention": (31 * 512 * 512, 512 * 513 // 2 + (seq - 512) * 512)}
    visited, needed = pairs["sliding_attention"]
    assert 1.99 < visited / needed < 2.0
    assert "gmm" in text
    loops = [line for line in text.splitlines()
             if " while(" in line and '/moe/moe_dispatch/while"' in line]
    assert len(loops) == 2 * 7, len(loops)  # forward and backward of seven expert layers
    held = program_bytes(compiled)
    assert 7.6e9 < held <= min(PROGRAM_BYTES, CHIP_BYTES), held
