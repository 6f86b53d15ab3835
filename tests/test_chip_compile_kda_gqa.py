"""The linear-attention / grouped-query family's chip path without a chip: the
real cut of ``recipes/pretrain_solar_open2_share.yaml`` compiles for a
described v5e and fits under the ladder's line. (Its own file: the compile
takes a minute, and the suite spreads files over its workers. ``chip_smoke``'s
``lm_train`` phase on this recipe is a case of ``test_chip_lm_train.py``.)"""

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

RECIPE = str(chip_smoke.REPO / "recipes" / "pretrain_solar_open2_share.yaml")
# what one AOT compile of this step read (PERF.md, PR 38; 15 756 047 360 with
# 16 heads held, 16 421 990 912 with 32), the ladder's line (no nearer the
# chip's limit than the fullest accepted cell), and the chip's own
# ... before the head's loss walked its tokens in tiles (PR 41); the step reads
# 14 020 593 664 since, and the bound is the older reading with no slack
PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES = 14_154_523_648, 15.2e9, 16.9e9


def test_linear_and_grouped_query_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):  # noqa: F811
    """837 M parameters, 2 x 8192 tokens, through the trainer's own step
    factory: the three linear-attention blocks run the forward chunk kernel
    twice (forward and the block's recompute) and the backward kernel once,
    in the form that knows no floor under the decays, with no loop left under
    ``kda_core``; the one grouped-query block runs each causal kernel once
    under ``attn_core``; no rope kernel is called and no ``rope`` scope
    exists; nothing sized (seq, seq) a head or (tokens, d_k, d_v) is built;
    the expert layers walk their held pairs in a loop, the guard adds no
    ``conditional``, and what the step holds fits under the ladder's line."""
    cfg, lm, parameters, compiled = compile_lm_step(RECIPE, v5e_chip, monkeypatch)
    assert parameters == 836_709_784
    rows, seq = cfg.run.train_batch_size, cfg.data.seq_len
    text = compiled.as_text()
    assert " conditional(" not in text and "/guard/" in text
    assert lm.kinds == ("full_attention", "kda", "kda", "kda") and lm.kda_layers == 3
    assert chip_smoke.kda_kernel_calls(text) == {"fwd": 6, "bwd": 3, "loops": 0}
    assert chip_smoke.causal_kernel_calls(text) == {"fwd": 1, "bwd": 1}
    assert len(re.findall(r'custom-call\([^\n]*/attn_core/causal_attention_\w+/pallas_call"',
                          text)) == 2
    assert chip_smoke.rope_kernel_calls(text) == 0
    assert_the_head_walks_its_tokens_in_tiles(text, cfg, lm)
    assert not re.search(r'op_name="[^"]*/rope[/"]', text)
    assert lm.attn_heads() == {"full_attention": (8, 64), "kda": (8, 64)}
    h, e = lm.kda_heads, lm.kda_head_dim
    for wide in (f"[{rows},{h},{seq},{e},{e}]", f"[{rows},{h},{seq},{lm.kda_chunk},{e}]",
                 f"[{rows},{lm.heads},{seq},{seq}]", f"[{seq},{seq}]"):
        assert wide not in text, wide
    assert "gmm" in text
    loops = [line for line in text.splitlines()
             if " while(" in line and '/moe/moe_dispatch/while"' in line]
    assert len(loops) == 2 * 4, len(loops)  # forward and backward of four expert layers
    held = program_bytes(compiled)
    assert 8.4e9 < held <= min(PROGRAM_BYTES, LADDER_BYTES, CHIP_BYTES), held
