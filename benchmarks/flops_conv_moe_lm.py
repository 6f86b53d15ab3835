"""Matmul FLOPs and HBM bytes the short-convolution / grouped-query
sparse-expert language model requires on one chip's share, from a
configuration file's document (``LFM2-24B-A2B``'s ``config.json`` keys at the
top level; ``num_hidden_layers``, ``num_dense_layers``, ``num_experts`` and
``vocab_size`` hold what the chip holds, ``published`` the model's own
counts; ``layer_types`` is the published list and this chip's layers its
entries from ``first_layer``). The benchmark's own arithmetic (2·m·n·k per
matmul, elementwise work, rope, the q/k norms, the filters' taps and the
embedding lookup not counted, backward = 2 x forward, recomputation not
counted), kept here so that no later change to the program can move the
yardstick; a test holds it equal to the program's ``obs/mfu.py``.

A ``conv`` layer counts its two projections (``d → 3d``, ``d → d``) and has no
term that grows with the sequence; a ``full_attention`` layer's core counts
the lower triangle once at heads 64 wide; the tied head's product is counted
as an untied one's. The causal kernels' and the experts' (FLOPs, bytes) are
the grouped-query family's and the all-MLA family's arithmetic under their
key names; ``sconv_mix_step`` is this family's own: the gated convolution's
elementwise part, which is bound by bytes.
"""

from __future__ import annotations

from benchmarks import flops_gqa_lm, flops_lm

KINDS = ("conv", "full_attention")


def kinds(c: dict) -> list[str]:
    """The mixer kind of each layer held."""
    first = c["first_layer"]
    return c["layer_types"][first : first + c["num_hidden_layers"]]


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def _as_gqa(c: dict) -> dict:
    """The document under the names ``flops_gqa_lm``'s cores read: the held
    layers' kinds (a ``conv`` layer is of neither of its kinds and adds
    nothing there), one head count, no window."""
    held = kinds(c)
    return c | {"layer_types": held, "head_dim": head_dim(c), "sliding_window": 0,
                "num_attention_heads_per_layer": [c["num_attention_heads"]] * len(held)}


def needed_pairs(c: dict, kind: str, seq: int) -> int:
    """(query, key) pairs one head of one sequence of ``seq`` tokens needs in
    a layer of ``kind``: the lower triangle for ``full_attention``, none for
    ``conv``."""
    return 0 if kind == "conv" else flops_gqa_lm.needed_pairs(_as_gqa(c), kind, seq)


def _mixer(c: dict, kind: str, seq: int) -> float:
    d = c["hidden_size"]
    if kind == "conv":
        return 2 * (d * 3 * d + d * d)  # W_in, W_out
    e, h, g = head_dim(c), c["num_attention_heads"], c["num_key_value_heads"]
    keys = needed_pairs(c, kind, seq) / seq  # mean keys a query sees
    return 2 * (d * h * e + 2 * d * g * e + h * e * d) + 2 * keys * h * (e + e)


def token_forward(c: dict, seq: int) -> float:
    """Forward FLOPs of one token at sequence length ``seq``."""
    d, experts = c["hidden_size"], c["published"]["num_experts"]
    layers, dense = c["num_hidden_layers"], c["num_dense_layers"]
    pairs_here = c["num_experts_per_tok"] * c["num_experts"] / experts
    expert_layer = 2 * d * experts + pairs_here * 2 * 3 * d * c["moe_intermediate_size"]
    return (sum(_mixer(c, kind, seq) for kind in kinds(c))
            + dense * 2 * 3 * d * c["intermediate_size"]
            + (layers - dense) * expert_layer
            + 2 * d * c["vocab_size"])


def token_step(c: dict, seq: int) -> float:
    """Forward + backward of one token."""
    return 3.0 * token_forward(c, seq)


def causal_core_step(c: dict, batch: int, seq: int) -> tuple[float, float]:
    """The full-attention layers' kernels (``attn_core_roofline``'s work) at
    their own width of 64: a product whose 64-wide side fills half of a
    128-wide pass is counted at 64, so the half-filled passes read as lost
    share."""
    return flops_gqa_lm.core_step(_as_gqa(c), "full_attention", batch, seq)


def sconv_mix_step(c: dict, batch: int, seq: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) the ``conv`` layers' elementwise part of one
    training step requires by the algorithm, from ``(B, C, x̃)`` to ``y`` and
    back: forward reads the three gates' inputs and writes ``y`` (four
    (tokens, d) arrays); backward reads the three and ``dy`` and writes their
    three gradients (seven). FLOPs, a channel and token: ``B ⊙ x̃``, ``K``
    taps (``K`` products, ``K − 1`` sums) and ``C ⊙`` forward; about three
    times that backward (the taps run backwards over ``dy ⊙ C``, the filter's
    gradient is ``K`` more products and sums, and each gate's gradient one
    product). The bytes bind: 22 bytes against 40-odd operations an element."""
    elements = batch * seq * c["hidden_size"]
    layers = kinds(c).count("conv")
    taps = c["conv_L_cache"]
    forward = 2 * taps + 1
    return layers * elements * 4 * forward, layers * elements * (4 + 7) * itemsize


def experts_step(c: dict, rows: float) -> tuple[float, float]:
    """``flops_lm.experts_step``: ``rows`` (token, expert) pairs landing here
    in each of the expert layers, three matrices an expert."""
    return flops_lm.experts_step(c | {
        "n_routed_experts": c["num_experts"], "first_k_dense_replace": c["num_dense_layers"],
        "num_nextn_predict_layers": 0}, rows)
