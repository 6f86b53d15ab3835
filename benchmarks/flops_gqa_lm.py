"""Matmul FLOPs and HBM bytes the grouped-query sparse-expert language model
requires on one chip's share, from a configuration file's document
(``Laguna-XS.2``'s ``config.json`` keys at the top level; ``num_experts``,
``vocab_size`` and ``num_hidden_layers`` hold what the chip holds,
``published`` the model's own counts; the per-layer lists are the published
ones and this chip's layers their first ``num_hidden_layers`` entries). The
benchmark's own arithmetic (2·m·n·k per matmul, elementwise work, rope and
the embedding lookup not counted, backward = 2 x forward, recomputation not
counted), kept here so that no later change to the program can move the
yardstick; a test holds it equal to the program's ``obs/mfu.py``.

A full-attention core counts the lower triangle once; a sliding-window core
counts the (query, key) pairs its mask keeps, ``min(i + 1, window)`` keys for
query ``i``: what a kernel computes in blocks beyond them is the kernel's
own, so a share of the roofline computed from this understates by exactly
that waste.
"""

from __future__ import annotations

from benchmarks import flops_lm
from benchmarks.reference.gqa_lm_params import dense_layers

KINDS = ("full_attention", "sliding_attention")


def layers_of(c: dict, kind: str) -> list[int]:
    """This chip's layers of one attention kind."""
    return [i for i in range(c["num_hidden_layers"]) if c["layer_types"][i] == kind]


def needed_pairs(c: dict, kind: str, seq: int) -> int:
    """(query, key) pairs one head of one sequence of ``seq`` tokens needs:
    ``Σ_i min(i + 1, window)``, the window the whole sequence for a full layer."""
    w = min(c["sliding_window"], seq) if kind == "sliding_attention" else seq
    return w * (w + 1) // 2 + (seq - w) * w


def _attention_layer(c: dict, layer: int, seq: int) -> float:
    d, e, g = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    h = c["num_attention_heads_per_layer"][layer]
    gate = d * h if c["gating"] else 0
    projections = 2 * (d * h * e + 2 * d * g * e + gate + h * e * d)
    keys = needed_pairs(c, c["layer_types"][layer], seq) / seq  # mean keys a query sees
    return projections + 2 * keys * h * (e + e)


def _gated(c: dict, hidden: int) -> float:
    return 2 * 3 * c["hidden_size"] * hidden


def token_forward(c: dict, seq: int) -> float:
    """Forward FLOPs of one token at sequence length ``seq``."""
    experts = c["published"]["num_experts"]
    layers, dense = c["num_hidden_layers"], dense_layers(c)
    pairs_here = c["num_experts_per_tok"] * c["num_experts"] / experts
    expert_layer = (2 * c["hidden_size"] * experts
                    + _gated(c, c["shared_expert_intermediate_size"])
                    + pairs_here * _gated(c, c["moe_intermediate_size"]))
    return (sum(_attention_layer(c, i, seq) for i in range(layers))
            + dense * _gated(c, c["intermediate_size"])
            + (layers - dense) * expert_layer
            + 2 * c["hidden_size"] * c["vocab_size"])


def token_step(c: dict, seq: int) -> float:
    """Forward + backward of one token."""
    return 3.0 * token_forward(c, seq)


def core_step(c: dict, kind: str, batch: int, seq: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) the causal kernels of one training step require by
    the algorithm in the layers of ``kind``: the needed pairs once; forward
    two products (q·kᵀ, p·v), backward four (dV, dP, dQ, dK), each ``2 · d``
    a (query head, pair). Bytes: every operand read once and every result
    written once per kernel (forward; dQ; dK/dV), the key/value heads once a
    group and not once a query head."""
    e, g = c["head_dim"], c["num_key_value_heads"]
    flops = moved = 0.0
    for layer in layers_of(c, kind):
        h = c["num_attention_heads_per_layer"][layer]
        flops += 6 * 2 * e * h * needed_pairs(c, kind, seq)
        q, kv = h * seq * e * itemsize, g * seq * e * itemsize  # one (seq, d) array a head
        forward = q + 2 * kv + q  # q, k, v in; o out
        backward = 2 * (q + 2 * kv + q) + q + 2 * kv  # both kernels read q, k, v, dO; dQ, dK, dV out
        moved += forward + backward
    return batch * flops, batch * moved


def causal_core_step(c: dict, batch: int, seq: int) -> tuple[float, float]:
    """The full-attention layers' kernels (``attn_core_roofline``'s work)."""
    return core_step(c, "full_attention", batch, seq)


def swa_core_step(c: dict, batch: int, seq: int) -> tuple[float, float]:
    """The sliding-window layers' kernels, needed pairs only."""
    return core_step(c, "sliding_attention", batch, seq)


def experts_step(c: dict, rows: float) -> tuple[float, float]:
    """``flops_lm.experts_step``: the expert layers are counted by the same
    arithmetic under that family's key names."""
    return flops_lm.experts_step(c | {
        "n_routed_experts": c["num_experts"], "first_k_dense_replace": dense_layers(c),
        "num_nextn_predict_layers": 0}, rows)
