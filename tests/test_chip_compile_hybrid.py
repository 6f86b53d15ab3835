"""The hybrid language family's chip path without a chip: the real cut of
``recipes/pretrain_ling3_flash_ep64.yaml`` compiles for a described v5e and
fits. (Its own file: the compile takes minutes, and the suite spreads files
over its workers. ``chip_smoke``'s ``lm_train`` phase on this recipe is a case
of ``test_chip_lm_train.py``.)"""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import chip_smoke
from test_chip_compile import (  # noqa: F401 - fixture
    assert_the_head_walks_its_tokens_in_tiles,
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


def test_hybrid_language_model_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):  # noqa: F811
    """822 M parameters, 2 x 8192 tokens, through the trainer's own step
    factory: the one MLA block runs each of the two causal kernels once, the six
    linear-attention blocks run the forward chunk kernel twice (forward and
    the block's recompute, which keeps every chunk's starting state) and the
    backward kernel once, with no loop left under ``kda_core``, and build
    nothing sized (tokens, d_k, d_v) or (tokens, chunk, d_k) for a whole
    sequence,
    the expert layers walk their held pairs in a loop, the guard adds no
    ``conditional``, and what the step holds fits the chip."""
    cfg, lm, parameters, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch)
    assert parameters == 822_033_344
    rows = cfg.run.train_batch_size
    text = compiled.as_text()
    assert " conditional(" not in text and "/guard/" in text
    assert chip_smoke.causal_kernel_calls(text) == {"fwd": 1, "bwd": 1}
    assert chip_smoke.rope_kernel_calls(text) == 0  # rope on adjacent pairs: not the kernel's
    assert_the_head_walks_its_tokens_in_tiles(text, cfg, lm)
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
    held = program_bytes(compiled)
    assert 8.2e9 < held <= min(PROGRAM_BYTES, CHIP_BYTES), held
