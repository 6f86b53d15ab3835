"""The short-convolution / grouped-query family's chip path without a chip:
the real cut of ``recipes/pretrain_lfm2_24b_share.yaml`` compiles for a
described v5e and fits under the ladder's line (``slow``: over a minute), and
two of its layers — one ``conv``, one attention — compile in tier-1 under the
same structural assertions. (Its own file: the suite spreads files over its
workers. ``chip_smoke``'s ``lm_train`` phase on this recipe is a case of
``test_chip_lm_train.py``.)"""

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

RECIPE = str(chip_smoke.REPO / "recipes" / "pretrain_lfm2_24b_share.yaml")
# what one AOT compile of this step read (PERF.md, PR 44), the ladder's line
# (no nearer the chip's limit than the fullest accepted cell), and the chip's own
PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES = 13_084_730_368, 15.2e9, 16.9e9


# tier-1's compile: the dense conv layer and the attention layer with experts
DEPTH_CUT = ["model.lm.layers=2", "model.lm.layer_types=[conv, full_attention]"]


def assert_the_short_conv_and_grouped_query_step(text: str, cfg, lm) -> None:
    """2 x 8192 tokens: an attention block runs each causal kernel once under
    ``attn_core`` at heads 64 wide and a group of 4, and turns its q and k
    through the rope kernel (the half-tile form) three times each; a conv
    block runs no kernel of attention's at all and enters none of its scopes
    (``assert_the_step_is_built_a_block_at_a_time`` counts both by
    ``lm.kinds``); its three parts carry their scopes; the q/k norms lie under
    ``gqa_proj``; nothing sized (seq, seq) a head is built; there is no
    ``head`` parameter, and the tied head's three products read the
    embedding's rows (``head_product_calls`` 3 · 0 · 0)."""
    assert_the_step_is_built_a_block_at_a_time(text, cfg, lm)
    rows, seq = cfg.run.train_batch_size, cfg.data.seq_len
    assert (rows, seq, lm.head_dim, lm.heads // lm.kv_heads) == (2, 8192, 64, 4)
    assert lm.qk_norm and lm.tie_embeddings
    conv = [i for i, kind in enumerate(lm.kinds) if kind == "conv"]
    attention = [i for i, kind in enumerate(lm.kinds) if kind == "full_attention"]
    for i in conv:
        for scope in ("sconv_in", "sconv_mix", "sconv_out"):
            assert re.search(rf'op_name="[^"]*block_{i}/conv/{scope}/', text), (i, scope)
        assert not re.search(rf'op_name="[^"]*block_{i}/(attn|conv)/(attn_core|rope|gqa_proj)/',
                             text)
    for i in attention:
        assert re.search(rf'op_name="[^"]*block_{i}/attn/gqa_proj/q_norm/', text)
        assert re.search(rf'op_name="[^"]*block_{i}/attn/rope/', text)
        assert not re.search(rf'op_name="[^"]*block_{i}/[^"]*/sconv_', text)
    assert chip_smoke.causal_kernel_calls(text) == {"fwd": len(attention), "bwd": len(attention)}
    assert chip_smoke.rope_kernel_calls(text) == len(attention) * 2 * 3
    # 28 whole block pairs and the 8 diagonal ones at 10 of their 16 sub-tiles
    assert lm.attn_pairs(seq) == {"full_attention": (33 * 1024 * 1024, 33_558_528)}
    for wide in (f"[{rows},{lm.heads},{seq},{seq}]", f"[{lm.heads},{seq},{seq}]"):
        assert wide not in text, wide
    assert "/shared_expert/" not in text and "/head/" not in text
    calls = chip_smoke.head_product_calls(text, lm.rows[1])
    assert {k: calls[k] for k in ("fwd", "recompute", "bwd")} == {
        "fwd": 3, "recompute": 0, "bwd": 0}


def test_short_conv_and_grouped_query_step_compiles_for_v5e_at_cut_depth(v5e_chip, monkeypatch):  # noqa: F811
    """Two of the recipe's nine layers at its published widths, 2 x 8192
    tokens: every structural assertion of the full compile, which is
    ``slow``."""
    cfg, lm, _, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch, DEPTH_CUT)
    assert (lm.kinds, lm.first_k_dense) == (("conv", "full_attention"), 1)
    assert lm.layers_by_kind == {"conv": 1, "full_attention": 1}
    assert_the_short_conv_and_grouped_query_step(compiled.as_text(), cfg, lm)


# slow: over a minute of one worker; the chip run of the cell covers "fits". By hand
# after a change to the family's program: pytest -m slow tests/test_chip_compile*.py
@pytest.mark.slow
def test_short_conv_and_grouped_query_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):  # noqa: F811
    """833 M parameters, 2 x 8192 tokens, through the trainer's own step
    factory: what ``assert_the_short_conv_and_grouped_query_step`` holds of the
    seven conv and two attention layers (2 + 2 causal kernel calls, 2 x 2 x 3
    of the rope kernel) and the eight expert layers; and what the step holds
    fits under the ladder's line."""
    cfg, lm, parameters, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch)
    assert parameters == 832_651_520
    assert lm.layers_by_kind == {"conv": 7, "full_attention": 2} and lm.first_k_dense == 1
    assert_the_short_conv_and_grouped_query_step(compiled.as_text(), cfg, lm)
    held = program_bytes(compiled)
    assert 8.4e9 < held <= min(PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES), held
