"""Matmul FLOPs and HBM bytes the latent-attention sparse-expert language
model requires on one chip's share, from a configuration file's document
(``config.json``'s keys at the top level; ``n_routed_experts`` and
``vocab_size`` hold what the chip holds, ``published`` the model's own
counts). The benchmark's own arithmetic (2·m·n·k per matmul, elementwise work
and the embedding lookup not counted, backward = 2 x forward, recomputation
not counted), kept here so that no later change to the program can move the
yardstick; a test holds it equal to the program's ``obs/mfu.py``.
"""

from __future__ import annotations


def _attention_layer(c: dict, seq: int) -> float:
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    latent = 2 * (d * c["q_lora_rank"] + c["q_lora_rank"] * h * (nope + rope)
                  + d * (c["kv_lora_rank"] + rope) + c["kv_lora_rank"] * h * (nope + v)
                  + h * v * d)
    core = 2 * (seq / 2) * h * (nope + rope + v)  # the lower triangle, once
    return latent + core


def _gated(c: dict, hidden: int) -> float:
    return 2 * 3 * c["hidden_size"] * hidden


def token_forward(c: dict, seq: int) -> float:
    """Forward FLOPs of one token at sequence length ``seq``."""
    experts = c["published"]["n_routed_experts"]
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    mtp = c["num_nextn_predict_layers"]
    sparse = c["num_hidden_layers"] - dense + mtp
    pairs_here = c["num_experts_per_tok"] * c["n_routed_experts"] / experts
    expert_layer = (2 * c["hidden_size"] * experts
                    + _gated(c, c["n_shared_experts"] * c["moe_intermediate_size"])
                    + pairs_here * _gated(c, c["moe_intermediate_size"]))
    return ((c["num_hidden_layers"] + mtp) * _attention_layer(c, seq)
            + dense * _gated(c, c["intermediate_size"])
            + sparse * expert_layer
            + (1 + mtp) * 2 * c["hidden_size"] * c["vocab_size"]
            + mtp * 2 * (2 * c["hidden_size"]) * c["hidden_size"])


def token_step(c: dict, seq: int) -> float:
    """Forward + backward of one token."""
    return 3.0 * token_forward(c, seq)


def attention_layers(c: dict) -> int:
    return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def expert_layers(c: dict) -> int:
    return (c["num_hidden_layers"] - min(c["first_k_dense_replace"], c["num_hidden_layers"])
            + c["num_nextn_predict_layers"])


def causal_core_step(c: dict, batch: int, seq: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) the causal attention kernels of one training step
    require by the algorithm: the lower triangle once; forward two products
    (q·kᵀ over nope + rope, p·v), backward four (dV, dP, dQ, dK); what a
    kernel or the step's rematerialisation computes again is not counted, so
    a share of the roofline computed from this can only understate. Bytes:
    every operand read once and every result written once per kernel
    (forward; dQ; dK/dV), the shared rope key once a batch row."""
    h = c["num_attention_heads"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    pairs = seq * seq / 2
    flops = 2 * pairs * h * ((nope + rope + v) + (v + v + 2 * (nope + rope)))
    row = h * seq * itemsize
    shared = seq * rope * itemsize
    qkv = row * (nope + rope + nope + v) + shared
    forward = qkv + row * v
    backward = 2 * (qkv + 2 * row * v) + row * (2 * (nope + rope) + nope + v)
    layers = attention_layers(c)
    return layers * batch * flops, layers * batch * (forward + backward)


def experts_step(c: dict, rows: float, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one training step's grouped products over the
    held experts, ``rows`` (token, expert) pairs landing here in each expert
    layer: three matrices an expert, forward once and backward twice (the
    rows' and the matrices' gradients). Bytes: each pass reads the held
    matrices once and reads or writes each row's operands once."""
    d, w, held = c["hidden_size"], c["moe_intermediate_size"], c["n_routed_experts"]
    flops = 3 * rows * _gated(c, w)
    weights = held * 3 * d * w * itemsize
    row_bytes = rows * (d + 2 * w + w + d) * itemsize
    return expert_layers(c) * flops, expert_layers(c) * 3 * (weights + row_bytes)
