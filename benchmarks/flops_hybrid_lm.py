"""Matmul FLOPs and HBM bytes the hybrid linear-attention / latent-attention
sparse-expert language model requires on one chip's share, from a
configuration file's document (``Ling-3.0-flash``'s ``config.json`` keys at
the top level; ``num_experts``, ``vocab_size`` and ``num_hidden_layers`` hold
what the chip holds, ``published`` the model's own counts). The benchmark's
own arithmetic (2·m·n·k per matmul, elementwise work, the short convolutions
and the embedding lookup not counted, backward = 2 x forward, recomputation
not counted), kept here so that no later change to the program can move the
yardstick; a test holds it equal to the program's ``obs/mfu.py``.

A linear-attention core is counted as its recurrence: three products with
the (d_k, d_v) state a token a head — the decayed state against ``k``, the
outer product that corrects it, the state against ``q`` — ``6 · d_k · d_v``
forward, whatever the sequence length and however a program chunks it. What
a chunked form adds (the intra-chunk products and the triangular inverse) is
the form's own and is not counted, so a share of the roofline computed from
this can only understate.
"""

from __future__ import annotations

from benchmarks import flops_lm


def linear_layers(c: dict) -> int:
    """Trunk layers of the linear-attention kind: every layer ``i`` whose
    ``i + 1`` is no multiple of ``layer_group_size``."""
    return sum((i + 1) % c["layer_group_size"] != 0 for i in range(c["num_hidden_layers"]))


def latent_layers(c: dict) -> int:
    """Layers of the latent-attention kind: the rest of the trunk, and the
    MTP module's block (``mtp_use_kda: false``)."""
    return c["num_hidden_layers"] - linear_layers(c) + c["num_nextn_predict_layers"]


def _latent_layer(c: dict, seq: int) -> float:
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    latent = 2 * (d * h * (nope + rope)  # no query latent
                  + d * (c["kv_lora_rank"] + rope) + c["kv_lora_rank"] * h * (nope + v)
                  + h * v * d + d * h)  # the head-wise gate
    core = 2 * (seq / 2) * h * (nope + rope + v)  # the lower triangle, once
    return latent + core


def _linear_layer(c: dict) -> float:
    d, h, e = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    projections = 2 * (4 * d * h * e + 2 * d * h + h * e * d)  # q k v f; beta, gate; W_o
    return projections + 6 * h * e * e


def _gated(c: dict, hidden: int) -> float:
    return 2 * 3 * c["hidden_size"] * hidden


def token_forward(c: dict, seq: int) -> float:
    """Forward FLOPs of one token at sequence length ``seq``."""
    experts = c["published"]["num_experts"]
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    mtp = c["num_nextn_predict_layers"]
    sparse = c["num_hidden_layers"] - dense + mtp
    pairs_here = c["num_experts_per_tok"] * c["num_experts"] / experts
    shared = c["num_shared_experts"] * c["moe_shared_expert_intermediate_size"]
    expert_layer = (2 * c["hidden_size"] * experts + _gated(c, shared)
                    + pairs_here * _gated(c, c["moe_intermediate_size"]))
    return (latent_layers(c) * _latent_layer(c, seq)
            + linear_layers(c) * _linear_layer(c)
            + dense * _gated(c, c["intermediate_size"])
            + sparse * expert_layer
            + (1 + mtp) * 2 * c["hidden_size"] * c["vocab_size"]
            + mtp * 2 * (2 * c["hidden_size"]) * c["hidden_size"])


def token_step(c: dict, seq: int) -> float:
    """Forward + backward of one token."""
    return 3.0 * token_forward(c, seq)


def kda_core_step(c: dict, batch: int, seq: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) the linear-attention cores of one training step
    require by the algorithm, from ``(q, k, v, g, beta)`` to ``o`` and back.
    FLOPs: the recurrence's three products forward, twice that backward.
    Bytes: forward reads q, k, v (``itemsize`` each), the float32 log-decay
    ``g`` and ``beta`` once and writes ``o`` once; backward reads the same
    operands and ``o``'s gradient once and writes the five gradients once.
    The state never leaves the chip's fast memory in this count."""
    h, e = c["num_attention_heads"], c["head_dim"]
    positions = batch * seq * h
    flops = 3 * 6 * e * e * positions
    operands = 3 * e * itemsize + e * 4 + 4
    out = e * itemsize
    layers = linear_layers(c)
    return layers * flops, layers * positions * ((operands + out) + (operands + out) + operands)


def causal_core_step(c: dict, batch: int, seq: int) -> tuple[float, float]:
    """``flops_lm.causal_core_step`` over the latent layers only."""
    trunk = c["num_hidden_layers"] - linear_layers(c)  # it adds the MTP block itself
    return flops_lm.causal_core_step(c | {"num_hidden_layers": trunk}, batch, seq)


def experts_step(c: dict, rows: float) -> tuple[float, float]:
    """``flops_lm.experts_step``: the expert layers are counted by the same
    keys; only the count of the experts held goes by another name."""
    return flops_lm.experts_step(c | {"n_routed_experts": c["num_experts"]}, rows)
