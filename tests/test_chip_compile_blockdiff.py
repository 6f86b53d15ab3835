"""The block-diffusion family's chip path without a chip: the real cut of
``recipes/pretrain_sdar_30b_share.yaml`` compiles for a described v5e and fits
under the ladder's line (``slow``: minutes), and two of its layers compile in
tier-1 under the same structural assertions. (Its own file: the suite spreads
files over its workers. ``chip_smoke``'s ``lm_train`` phase on this recipe is
a case of ``test_chip_lm_train.py``.)"""

from __future__ import annotations

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import pytest

import chip_smoke
from test_chip_compile import (  # noqa: F401 - fixture
    assert_the_head_walks_its_tokens_in_tiles,
    compile_lm_step,
    program_bytes,
    v5e_chip,
)

RECIPE = str(chip_smoke.REPO / "recipes" / "pretrain_sdar_30b_share.yaml")
# what one AOT compile of this step read (PERF.md, PR 47), the ladder's line
# (no nearer the chip's limit than the fullest accepted cell), and the chip's own
PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES = 12_894_764_544, 15.2e9, 16.9e9

# tier-1's compile: one of the six layers, which are all alike
DEPTH_CUT = ["model.lm.layers=1", "model.lm.layer_types=[full_attention]"]


def assert_the_block_diffusion_step(text: str, cfg, lm) -> None:
    """2 x 8192 clean tokens, 16 384 rows a sequence through the trunk: every
    block runs each of the two causal kernels once, under ``bd_core`` and
    never under ``attn_core`` (a rematted block keeps the forward kernel's
    output and log-sum-exp); turns its q and its k through the rope kernel
    three times each, as twice the heads of half the rows; the q/k norms lie
    under ``gqa_proj``; the noise is drawn once, under ``bd_noise``; the head
    walks the noisy copy's 16 384 rows in tiles; nothing sized (rows, rows) a
    head is built; every expert layer walks its held pairs in one loop each
    way through the grouped-product kernel."""
    rows, seq = cfg.run.train_batch_size, cfg.data.seq_len
    assert (rows, seq, lm.head_dim, lm.heads // lm.kv_heads) == (2, 8192, 128, 8)
    assert (lm.diffusion_block, lm.qk_norm, set(lm.kinds)) == (4, True, {"full_attention"})
    assert " conditional(" not in text and "/guard/" in text
    assert chip_smoke.causal_kernel_calls(text) == {"fwd": lm.layers, "bwd": lm.layers}
    assert chip_smoke.bd_kernel_calls(text) == {"fwd": lm.layers, "bwd": lm.layers}
    assert not re.search(r'custom-call\([^\n]*/(attn_core|swa_core)/', text)
    assert chip_smoke.rope_kernel_calls(text) == lm.layers * 2 * 3
    for i in range(lm.layers):
        assert re.search(rf'op_name="[^"]*block_{i}/attn/gqa_proj/q_norm/', text)
        assert re.search(rf'op_name="[^"]*block_{i}/attn/bd_core/', text)
    assert re.search(r'op_name="[^"]*/bd_noise/', text)
    assert not re.search(r'op_name="[^"]*block_\d+/[^"]*bd_noise', text)
    # 56 whole block pairs, 16 staircase pairs at 10 of their 16 sub-tiles and
    # the 8 noisy blocks' own at 4: 68 blocks' worth for 64.03 needed
    assert lm.attn_pairs(seq) == {"block_diffusion": (68 * 1024 * 1024, seq * seq + seq * 4)}
    for wide in (f"[{rows},{lm.heads},{2 * seq},{2 * seq}]", f"[{lm.heads},{2 * seq},{2 * seq}]"):
        assert wide not in text, wide
    assert_the_head_walks_its_tokens_in_tiles(text, cfg, lm)
    assert "gmm" in text and "/shared_expert/" not in text
    loops = [line for line in text.splitlines()
             if " while(" in line and '/moe/moe_dispatch/while"' in line]
    assert len(loops) == 2 * lm.layers, len(loops)


def test_block_diffusion_step_compiles_for_v5e_at_cut_depth(v5e_chip, monkeypatch):  # noqa: F811
    """One of the recipe's six layers at its published widths, 2 x 8192 clean
    tokens: every structural assertion of the full compile, which is
    ``slow``."""
    cfg, lm, _, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch, DEPTH_CUT)
    assert lm.layers_by_kind == {"full_attention": 1}
    assert_the_block_diffusion_step(compiled.as_text(), cfg, lm)


# slow: minutes of one worker; the chip run of the cell covers "fits". By hand
# after a change to the family's program: pytest -m slow tests/test_chip_compile*.py
@pytest.mark.slow
def test_block_diffusion_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):  # noqa: F811
    """646 M parameters, 2 x 8192 clean tokens, through the trainer's own
    step factory: what ``assert_the_block_diffusion_step`` holds of the six
    layers; and what the step holds fits under the ladder's line."""
    cfg, lm, parameters, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch)
    assert parameters == 645_623_296
    assert lm.layers_by_kind == {"full_attention": 6} and lm.first_k_dense == 0
    assert_the_block_diffusion_step(compiled.as_text(), cfg, lm)
    held = program_bytes(compiled)
    assert 8.4e9 < held <= min(PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES), held
