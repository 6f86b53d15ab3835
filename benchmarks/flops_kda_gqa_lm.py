"""Matmul FLOPs and HBM bytes the linear-attention / grouped-query
sparse-expert language model requires on one chip's share, from a
configuration file's document (``Solar-Open2-250B``'s ``config.json`` keys at
the top level; ``num_hidden_layers``, ``n_routed_experts``, ``vocab_size`` and
the three head counts hold what the chip holds, ``published`` the model's own
counts; ``gqa_layers`` is the published list). The benchmark's own arithmetic
(2·m·n·k per matmul, elementwise work, the short convolutions and the
embedding lookup not counted, backward = 2 x forward, recomputation not
counted), kept here so that no later change to the program can move the
yardstick; a test holds it equal to the program's ``obs/mfu.py``.

Three layers in four do not grow with the sequence: a linear-attention core
is counted as its recurrence at the heads held, ``6 · d_k · d_v`` a token a
head (``flops_hybrid_lm``'s rule: what a chunked form adds is the form's own);
the fourth is one grouped-query core over the lower triangle, at the query
heads held. The kernels' work is the two older families' counts over this
family's keys: ``flops_hybrid_lm.kda_core_step``, ``flops_gqa_lm.core_step``,
``flops_lm.experts_step``.
"""

from __future__ import annotations

from benchmarks import flops_gqa_lm, flops_hybrid_lm, flops_lm


def linear_layers(c: dict) -> int:
    """This chip's layers of the linear-attention kind: those not in ``gqa_layers``."""
    return sum(i not in c["gqa_layers"] for i in range(c["num_hidden_layers"]))


def _linear_layer(c: dict) -> float:
    lin = c["linear_attn_config"]
    d, h, e = c["hidden_size"], lin["num_heads"], lin["head_dim"]
    through = d * e + e * h * e  # kda_use_full_proj false: hidden -> head_dim -> heads x head_dim
    projections = 2 * (4 * d * h * e + d * h + 2 * through)  # q k v W_o; beta; f, output gate
    return projections + 6 * h * e * e


def _attention_layer(c: dict, seq: int) -> float:
    d, e = c["hidden_size"], c["head_dim"]
    h, g = c["num_attention_heads"], c["num_key_value_heads"]
    projections = 2 * (d * h * e + 2 * d * g * e + d * h + h * e * d)  # q; k, v; gate; W_o
    return projections + 2 * ((seq + 1) / 2) * h * (e + e)  # the lower triangle, once


def _gated(c: dict, hidden: int) -> float:
    return 2 * 3 * c["hidden_size"] * hidden


def token_forward(c: dict, seq: int) -> float:
    """Forward FLOPs of one token at sequence length ``seq``."""
    experts, w = c["published"]["n_routed_experts"], c["moe_intermediate_size"]
    layers, linear = c["num_hidden_layers"], linear_layers(c)
    dense = min(c["first_k_dense_replace"], layers)
    pairs_here = c["num_experts_per_tok"] * c["n_routed_experts"] / experts
    expert_layer = (2 * c["hidden_size"] * experts + _gated(c, c["n_shared_experts"] * w)
                    + pairs_here * _gated(c, w))
    return ((layers - linear) * _attention_layer(c, seq) + linear * _linear_layer(c)
            + dense * _gated(c, c["intermediate_size"]) + (layers - dense) * expert_layer
            + 2 * c["hidden_size"] * c["vocab_size"])


def token_step(c: dict, seq: int) -> float:
    """Forward + backward of one token."""
    return 3.0 * token_forward(c, seq)


def kda_core_step(c: dict, batch: int, seq: int) -> tuple[float, float]:
    """``flops_hybrid_lm.kda_core_step`` at the heads held (``kda_core_roofline``'s
    work): that family's count of one linear layer, under its key names, times
    this family's linear layers (whose pattern is a list, not a period)."""
    lin = c["linear_attn_config"]
    one = {"num_attention_heads": lin["num_heads"], "head_dim": lin["head_dim"],
           "num_hidden_layers": 1, "layer_group_size": 2}  # one layer, of the linear kind
    flops, moved = flops_hybrid_lm.kda_core_step(one, batch, seq)
    return linear_layers(c) * flops, linear_layers(c) * moved


def causal_core_step(c: dict, batch: int, seq: int) -> tuple[float, float]:
    """``flops_gqa_lm.core_step`` over this chip's grouped-query layers
    (``attn_core_roofline``'s work): its per-layer lists filled from
    ``gqa_layers`` and the one head count."""
    layers = c["num_hidden_layers"]
    as_lists = {"layer_types": ["kda" if i not in c["gqa_layers"] else "full_attention"
                                for i in range(layers)],
                "num_attention_heads_per_layer": [c["num_attention_heads"]] * layers}
    return flops_gqa_lm.core_step(c | as_lists, "full_attention", batch, seq)


def experts_step(c: dict, rows: float) -> tuple[float, float]:
    """``flops_lm.experts_step``: the expert layers go by that family's key
    names here too; no MTP module."""
    return flops_lm.experts_step(c | {"num_nextn_predict_layers": 0}, rows)
