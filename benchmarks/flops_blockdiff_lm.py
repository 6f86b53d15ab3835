"""Matmul FLOPs and HBM bytes the block-diffusion grouped-query sparse-expert
language model requires on one chip's share, from a configuration file's
document (``SDAR-30B-A3B-Chat``'s ``config.json`` keys at the top level;
``num_experts``, ``vocab_size`` and ``num_hidden_layers`` hold what the chip
holds, ``published`` the model's own counts). The benchmark's own arithmetic
(2·m·n·k per matmul, elementwise work, rope, the q/k norms, the noise and the
embedding lookup not counted, backward = 2 x forward, recomputation not
counted), kept here so that no later change to the program can move the
yardstick; a test holds it equal to the program's ``obs/mfu.py``.

A token is a clean token: a sequence has ``seq`` of them and the trunk runs
``2 · seq`` rows, the clean copy and the noisy one, so every token-wise
product (projections, router, experts) is counted twice a token and the head
once (it reads the noisy copy alone). The core counts the (query, key) pairs
the block-diffusion pattern shows, both copies' queries together: ``seq² +
seq · B`` a head and sequence (a clean query its ``(b + 1) · B`` clean keys, a
noisy one its ``b · B`` clean and ``B`` noisy: at 8192 tokens in blocks of 4,
67.1 M of the 268.4 M a (2 seq, 2 seq) score matrix holds): what a kernel
computes in blocks beyond them is the kernel's own. No shared expert, no
dense layer, no output gate.
"""

from __future__ import annotations

from benchmarks import flops_lm


def needed_pairs(c: dict, seq: int) -> int:
    """(query, key) pairs one head needs for one sequence of ``seq`` clean
    tokens, the two copies together."""
    return seq * seq + seq * c["diffusion_block_length"]


def token_forward(c: dict, seq: int) -> float:
    """Forward FLOPs of one clean token at ``seq`` clean tokens a sequence."""
    d, e = c["hidden_size"], c["head_dim"]
    h, g = c["num_attention_heads"], c["num_key_value_heads"]
    experts = c["published"]["num_experts"]
    projections = 2 * (d * h * e + 2 * d * g * e + h * e * d)
    core = 2 * (needed_pairs(c, seq) / seq) * h * (e + e)
    pairs_here = c["num_experts_per_tok"] * c["num_experts"] / experts
    expert_layer = 2 * d * experts + pairs_here * 2 * 3 * d * c["moe_intermediate_size"]
    return (c["num_hidden_layers"] * (2 * projections + core + 2 * expert_layer)
            + 2 * d * c["vocab_size"])


def token_step(c: dict, seq: int) -> float:
    """Forward + backward of one clean token."""
    return 3.0 * token_forward(c, seq)


def core_step(c: dict, batch: int, seq: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) the block-diffusion core of one training step
    requires by the algorithm (``bd_core_roofline``'s work): the needed pairs
    once; forward two products (q·kᵀ, p·v), backward four (dV, dP, dQ, dK),
    each ``2 · e`` a (query head, pair). Bytes: over the ``2 · seq`` rows,
    forward q, k, v read and o written; backward q, k, v and dO read and dQ,
    dK, dV written, the key/value heads once a group."""
    e, h, g = c["head_dim"], c["num_attention_heads"], c["num_key_value_heads"]
    flops = c["num_hidden_layers"] * 6 * 2 * e * h * needed_pairs(c, seq)
    q, kv = h * 2 * seq * e * itemsize, g * 2 * seq * e * itemsize
    moved = c["num_hidden_layers"] * ((q + 2 * kv + q) + (q + 2 * kv + q) + (q + 2 * kv))
    return batch * flops, batch * moved


def experts_step(c: dict, rows: float) -> tuple[float, float]:
    """``flops_lm.experts_step``: ``rows`` (row, expert) pairs landing here in
    each of the layers, three matrices an expert."""
    return flops_lm.experts_step(c | {
        "n_routed_experts": c["num_experts"], "first_k_dense_replace": 0,
        "num_nextn_predict_layers": 0}, rows)
