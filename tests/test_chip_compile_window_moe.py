"""The window / rope-free-full family's chip path without a chip: the real cut
of ``recipes/pretrain_smallthinker_21b_share.yaml`` compiles for a described
v5e and fits under the ladder's line. (Its own file: the compile takes over a
minute, and the suite spreads files over its workers. ``chip_smoke``'s
``lm_train`` phase on this recipe is a case of ``test_chip_lm_train.py``.)"""

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

RECIPE = str(chip_smoke.REPO / "recipes" / "pretrain_smallthinker_21b_share.yaml")
# what one AOT compile of this step read (PERF.md, PR 40), the ladder's line
# (no nearer the chip's limit than the fullest accepted cell), and the chip's own
# ... before the head's loss walked its tokens in tiles (PR 41); the step reads
# 10 352 944 640 since, and the bound is the older reading with no slack
PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES = 13_045_875_712, 15.2e9, 16.9e9


# slow: tier-1 stands near its allowance and this compile is 85 s of one worker;
# run it by hand after a change to the family's program (it passes at PR 40)
@pytest.mark.slow
def test_window_and_rope_free_full_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):  # noqa: F811
    """657 M parameters, one row of 16 384 tokens, through the trainer's own
    step factory: each of the four blocks runs each causal kernel once — the
    rope-free full layer under ``attn_core``, the three window layers under
    ``swa_core`` — at a group of 7 and, in the backward kernel, one span of 16
    key blocks; the rope kernel turns the three window layers' q and k and
    nothing of the full layer's; the state has no ``batch_stats``; nothing
    sized (seq, seq) a head is built; the four expert layers walk their held
    pairs in a loop under ``moe_dispatch`` with the router's ``top_k`` traced
    inside the block's ``moe`` module; no shared expert's product exists; the
    guard adds no ``conditional``; and what the step holds fits under the
    ladder's line."""
    cfg, lm, parameters, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch)
    assert parameters == 656_529_920
    rows, seq = cfg.run.train_batch_size, cfg.data.seq_len
    assert (rows, seq) == (1, 16384)
    text = compiled.as_text()
    assert " conditional(" not in text and "/guard/" in text
    assert lm.kinds == ("full_attention",) + ("sliding_attention",) * 3
    assert chip_smoke.causal_kernel_calls(text) == {"fwd": 4, "bwd": 4}
    assert_the_head_walks_its_tokens_in_tiles(text, cfg, lm)
    core = lambda scope: len(re.findall(
        rf'custom-call\([^\n]*/{scope}/causal_attention_\w+/pallas_call"', text))
    assert (core("attn_core"), core("swa_core")) == (2, 6)
    assert chip_smoke.rope_kernel_calls(text) == 3 * 2 * 3  # q and k: forward, rematted, transposed
    assert not re.search(r'op_name="[^"]*block_0/attn/rope[/"]', text)
    assert chip_smoke.kda_kernel_calls(text) == {"fwd": 0, "bwd": 0, "loops": 0}
    assert lm.attn_pairs(seq) == {"full_attention": (142_606_336, 134_225_920),
                                  "sliding_attention": (73_400_320, 58_722_304)}
    for wide in (f"[{rows},{lm.heads},{seq},{seq}]", f"[{lm.heads},{seq},{seq}]", f"[{seq},{seq}]"):
        assert wide not in text, wide
    assert "gmm" in text and "/shared_expert/" not in text and "/dense_mlp/" not in text
    assert re.search(r'op_name="[^"]*block_2/moe/router/[^"]*top_k', text)
    loops = [line for line in text.splitlines()
             if " while(" in line and '/moe/moe_dispatch/while"' in line]
    assert len(loops) == 2 * 4, len(loops)  # forward and backward of four expert layers
    held = program_bytes(compiled)
    assert 8.4e9 < held <= min(PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES), held
