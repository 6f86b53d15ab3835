"""A decoder-only language model with grouped-query softmax attention of two
kinds — full, and over a sliding window — in a published pattern, a head-wise
output gate, and sigmoid-routed sparse experts beside a shared expert, in
plain ``jax.numpy``: loss, gradients and the router-bias rule of one training
step on one chip's share of the experts and the vocabulary.

Written from ``Laguna-XS.2``'s ``config.json`` (``model_type: laguna``).
float32 throughout, every contraction at precision "highest"; no kernels, no
blocks: the (seq, seq) scores exist, a few heads at a time so that 8192
tokens fit, and both masks are comparisons of positions. It imports nothing
of the program; what it shares with the all-MLA family's reference
(``lm_model.py``: RMSNorm, the gated MLP, the expert layer on a chip's share,
the head, the bias rule) it takes from there. ``rounding`` rounds the two
operands of every contraction to a narrower type first: the lower-precision
control, never the reference. One sequence at a time, each block
checkpointed.

Block ``i``: ``x += A_i(norm(x))``; ``x += F_i(norm(x))``; RMSNorm eps
``rms_norm_eps``, no bias, after the last block a final norm and the untied
head. ``F_i`` is the dense SwiGLU MLP where ``mlp_layer_types[i]`` is
``dense`` and the expert layer (``lm_model.expert_layer``: one shared expert
+ the top ``num_experts_per_tok`` of the routed ones, their outputs weighted,
``moe_apply_router_weight_on_input`` false) where it is ``sparse``.

``A_i``, kind ``layer_types[i]``, ``H = num_attention_heads_per_layer[i]``
query heads, ``G = num_key_value_heads``, ``d = head_dim``: ``q = x W_q`` ->
(H, d), ``k = x W_k``, ``v = x W_v`` -> (G, d); query head ``h`` reads
key/value head ``h // (H / G)``. Rotary embedding by
``rope_parameters[kind]`` on the first ``r = partial_rotary_factor · d``
dimensions of ``q`` and ``k``, dimension ``j`` paired with ``j + r/2``
(rotate-half), the rest passed through: ``default`` turns pair ``j`` by
``position · θ^(−2j/r)``; ``yarn`` by ``position · ((1 − γ_j) f_j + γ_j f_j /
factor)``, ``f_j = θ^(−2j/r)``, ``γ_j = clip((j − low) / (high − low), 0,
1)``, ``low = ⌊r ln(L₀ / (2π β_fast)) / (2 ln θ)⌋``, ``high = ⌈r ln(L₀ / (2π
β_slow)) / (2 ln θ)⌉`` clipped to ``[0, r − 1]``, and ``cos``, ``sin`` times
``attention_factor``. ``s = q kᵀ / sqrt(d)``; key ``j`` is visible to query
``i`` iff ``j <= i`` and, in a ``sliding_attention`` layer, ``i − j <
sliding_window``; ``z = softmax(s) v``; ``z_h ← sigmoid(x W_γ)_h z_h``;
``y = concat_h(z_h) W_o``. Loss = mean cross-entropy of the next token over
the vocabulary rows held.

Departures and assumptions (each also in the configuration file's
``assumed``): noted at their lines below.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.gqa_lm_params import dense_layers
from benchmarks.reference.lm_model import (HEADS_AT_ONCE, expert_layer, gated_mlp, head_logits,
                                           next_biases, rms_norm)
from benchmarks.reference.model import Ops

__all__ = ["batch_loss", "next_biases", "sequence_loss"]


def pair_frequencies(rope: dict, r: int) -> np.ndarray:
    """The ``r / 2`` rotary frequencies of one attention kind, in float64
    from the configuration's numbers."""
    theta = rope["rope_theta"]
    j = np.arange(r // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / r)
    if rope["rope_type"] == "default":
        return f
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not described here")
    origin = rope["original_max_position_embeddings"]
    turn = lambda beta: r * math.log(origin / (2 * math.pi * beta)) / (2 * math.log(theta))
    low = min(max(math.floor(turn(rope["beta_fast"])), 0), r - 1)
    high = min(max(math.ceil(turn(rope["beta_slow"])), 0), r - 1)
    blend = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)  # low == high: a step
    return (1.0 - blend) * f + blend * f / rope["factor"]


def rotary(x, rope: dict):
    """``x`` (heads, seq, d): the first ``r`` dimensions turned, dimension
    ``j`` with ``j + r/2`` — assumed: the config has no interleave key, and
    rotate-half is the pairing of the family's published code — the rest
    passed through."""
    seq, d = x.shape[-2:]
    r = int(d * rope["partial_rotary_factor"])
    freq = jnp.asarray(pair_frequencies(rope, r), jnp.float32)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq
    scale = rope.get("attention_factor", 1.0)
    cos, sin = scale * jnp.cos(angle), scale * jnp.sin(angle)
    first, second, rest = x[..., : r // 2], x[..., r // 2 : r], x[..., r:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin, rest],
                           axis=-1)


def attention(ops: Ops, x, p, c: dict, layer: int):
    """``x`` (seq, hidden) -> (seq, hidden)."""
    kind, d = c["layer_types"][layer], c["head_dim"]
    rope = c["rope_parameters"][kind]
    # no q/k norm: the config has no key for one (assumed)
    q = rotary(ops.einsum("sd,dhe->hse", x, p["q"]["kernel"]) * d ** -0.5, rope)
    k = rotary(ops.einsum("sd,dhe->hse", x, p["k"]["kernel"]), rope)
    v = ops.einsum("sd,dhe->hse", x, p["v"]["kernel"])
    heads, kv_heads = q.shape[0], k.shape[0]
    group = heads // kv_heads
    at = jnp.arange(x.shape[0])
    visible = at[None, :] <= at[:, None]
    if kind == "sliding_attention":
        visible = visible & (at[:, None] - at[None, :] < c["sliding_window"])

    @jax.checkpoint
    def some_heads(args):
        qq, kv_head = args  # a few query heads of one group, and their key/value head
        s = ops.einsum("hqe,ke->hqk", qq, k[kv_head])
        probs = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return ops.einsum("hqk,ke->hqe", probs, v[kv_head])

    n = HEADS_AT_ONCE if group % HEADS_AT_ONCE == 0 else 1
    first_head = jnp.arange(0, heads, n)
    z = jax.lax.map(some_heads, (q.reshape(heads // n, n, *q.shape[1:]), first_head // group))
    z = z.reshape(heads, *z.shape[2:])
    # "gating": true — assumed to be a head-wise sigmoid gate on the core's output
    z = z * jax.nn.sigmoid(ops.einsum("sd,dh->hs", x, p["gate"]["kernel"]))[..., None]
    return ops.einsum("hse,hed->sd", z, p["out"]["kernel"])


def _routing(c: dict) -> dict:
    """The document under the names ``lm_model``'s expert layer reads. The
    scoring rule is assumed (the config names none): float32 sigmoid scores,
    a balancing bias outside the gradient, weights normalised over the chosen
    and times ``moe_routed_scaling_factor``."""
    return c | {"routed_scaling_factor": c["moe_routed_scaling_factor"]}


def block(ops: Ops, x, p, bias, c: dict, layer: int):
    eps = c["rms_norm_eps"]
    x = x + attention(ops, rms_norm(x, p["ln1"], eps), p["attn"], c, layer)
    inner = rms_norm(x, p["ln2"], eps)
    if "moe" in p:
        y, counts = expert_layer(ops, inner, p["moe"], bias["moe"]["router_bias"], _routing(c))
        return x + y, counts
    return x + gated_mlp(ops, inner, p["mlp"]), None


def hidden_states(ops: Ops, params, biases, ids, c: dict):
    """``ids`` (seq + 1,) row indices into the embedding held -> ``(the last
    hidden state, {block name: routing counts})``."""
    x, counts = params["embedding"][ids[:-1]], {}
    for i in range(c["num_hidden_layers"]):
        name = f"block_{i}"
        run = jax.checkpoint(lambda x, p, b, i=i: block(ops, x, p, b, c, i))
        x, n = run(x, params[name], biases.get(name))
        if n is not None:
            counts[name] = n
    assert len(counts) == c["num_hidden_layers"] - dense_layers(c)
    return x, counts


def sequence_loss(params, biases, tokens, c: dict, rounding: str = "float32"):
    """One sequence's ``(loss, counts)``; ``tokens`` (seq + 1,) ids from the
    vocabulary rows held."""
    ops = Ops(rounding)
    ids = tokens - c["vocab_rows"][0]
    hidden, counts = hidden_states(ops, params, biases, ids, c)

    @jax.checkpoint
    def cross_entropy(h, targets):
        logits = head_logits(ops, params, h, c)
        hit = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
        return (jax.nn.logsumexp(logits, axis=1) - hit).mean()

    return cross_entropy(hidden, ids[1:]), counts


def batch_loss(params, biases, tokens, c: dict, rounding: str = "float32"):
    """Mean over the sequences of ``tokens`` (batch, seq + 1), one sequence
    after another: ``(loss, counts summed over the batch)``."""
    def one(total, row):
        loss, counts = sequence_loss(params, biases, row, c, rounding)
        return (total[0] + loss, jax.tree_util.tree_map(jnp.add, total[1], counts)), None

    e = c["published"]["num_experts"]
    zero = {name: jnp.zeros((e,), jnp.float32) for name in biases}
    (loss, counts), _ = jax.lax.scan(one, (jnp.zeros(()), zero), tokens)
    return loss / tokens.shape[0], counts
