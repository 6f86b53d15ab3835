"""Matmul FLOPs and HBM bytes the window / rope-free-full grouped-query
sparse-expert language model requires on one chip's share, from a
configuration file's document (``SmallThinker-21BA3B-Instruct``'s
``config.json`` keys at the top level; ``moe_num_primary_experts``,
``vocab_size`` and ``num_hidden_layers`` hold what the chip holds,
``published`` the model's own counts; ``sliding_window_layout`` is the
published list and this chip's layers its first ``num_hidden_layers``
entries). The benchmark's own arithmetic (2·m·n·k per matmul, elementwise
work, rope and the embedding lookup not counted, backward = 2 x forward,
recomputation not counted), kept here so that no later change to the program
can move the yardstick; a test holds it equal to the program's ``obs/mfu.py``.

A full layer's core counts the lower triangle once; a window layer's the
(query, key) pairs its mask keeps, ``min(i + 1, window)`` keys for query
``i`` (at 16 384 tokens and a 4096-token window 58.7 M of the triangle's
134.2 M, a head): what a kernel computes in blocks beyond them is the
kernel's own. No shared expert, no dense layer, no output gate. The cores'
and the experts' (FLOPs, bytes) are the grouped-query family's and the
all-MLA family's arithmetic under their key names.
"""

from __future__ import annotations

from benchmarks import flops_gqa_lm, flops_lm

KINDS = flops_gqa_lm.KINDS


def _as_gqa(c: dict) -> dict:
    """The document under the names ``flops_gqa_lm``'s cores read."""
    kinds = [KINDS[int(bool(window))] for window in c["sliding_window_layout"]]
    return c | {"layer_types": kinds, "sliding_window": c["sliding_window_size"],
                "num_attention_heads_per_layer": [c["num_attention_heads"]] * len(kinds)}


def needed_pairs(c: dict, kind: str, seq: int) -> int:
    """(query, key) pairs one head of one sequence of ``seq`` tokens needs."""
    return flops_gqa_lm.needed_pairs(_as_gqa(c), kind, seq)


def _attention_layer(c: dict, layer: int, seq: int) -> float:
    d, e = c["hidden_size"], c["head_dim"]
    h, g = c["num_attention_heads"], c["num_key_value_heads"]
    projections = 2 * (d * h * e + 2 * d * g * e + h * e * d)
    keys = needed_pairs(c, _as_gqa(c)["layer_types"][layer], seq) / seq  # mean keys a query sees
    return projections + 2 * keys * h * (e + e)


def token_forward(c: dict, seq: int) -> float:
    """Forward FLOPs of one token at sequence length ``seq``."""
    d, experts = c["hidden_size"], c["published"]["moe_num_primary_experts"]
    layers = c["num_hidden_layers"]
    pairs_here = c["moe_num_active_primary_experts"] * c["moe_num_primary_experts"] / experts
    expert_layer = 2 * d * experts + pairs_here * 2 * 3 * d * c["moe_ffn_hidden_size"]
    return (sum(_attention_layer(c, i, seq) for i in range(layers))
            + layers * expert_layer + 2 * d * c["vocab_size"])


def token_step(c: dict, seq: int) -> float:
    """Forward + backward of one token."""
    return 3.0 * token_forward(c, seq)


def causal_core_step(c: dict, batch: int, seq: int) -> tuple[float, float]:
    """The full-attention layers' kernels (``attn_core_roofline``'s work)."""
    return flops_gqa_lm.core_step(_as_gqa(c), "full_attention", batch, seq)


def swa_core_step(c: dict, batch: int, seq: int) -> tuple[float, float]:
    """The sliding-window layers' kernels, needed pairs only."""
    return flops_gqa_lm.core_step(_as_gqa(c), "sliding_attention", batch, seq)


def experts_step(c: dict, rows: float) -> tuple[float, float]:
    """``flops_lm.experts_step``: ``rows`` (token, expert) pairs landing here
    in each of the layers, three matrices an expert."""
    return flops_lm.experts_step(c | {
        "moe_intermediate_size": c["moe_ffn_hidden_size"],
        "n_routed_experts": c["moe_num_primary_experts"], "first_k_dense_replace": 0,
        "num_nextn_predict_layers": 0}, rows)
