"""A decoder-only language model of grouped-query softmax attention in two
kinds — full without rotary embedding, and over a sliding window with it —
whose sparse-expert layer is routed from the block's input, before attention,
by a softmax over the chosen experts' logits, with ReGLU experts and no
shared expert, in plain ``jax.numpy``: loss and gradients of one training step
on one chip's share of the experts and the vocabulary.

Written from ``SmallThinker-21BA3B-Instruct``'s ``config.json``
(``model_name: smallthinker_21b_instruct``) and the family's description
("router placed before attention", "sparse ReGLU", "0 shared", "SWA(4096);
NoPE global"). float32 throughout, every contraction at precision "highest";
no kernels: every visible (query, key) score exists, both masks are
comparisons of positions, and so that 16 384 tokens fit, one group of query
heads and one block of query rows at a time, the held experts one after
another, and the logits a block of rows at a time. It imports nothing of the
program; what it shares with the other families' references (RMSNorm, the
head, the rotate-half rotation, ``Ops``) it takes from them. ``rounding``
rounds the two operands of every contraction to a narrower type first: the
lower-precision control, never the reference; ``<type>@<l>`` rounds in block
``l`` alone, a one-layer fault for the limits to catch. One sequence at a
time, each block checkpointed.

Block ``l`` with input ``x`` (``d = hidden_size``, RMSNorm eps
``rms_norm_eps``, no bias anywhere)::

    ℓ   = x W_r                        W_r (d, 64): the block's input as it arrives
    C   = the moe_num_active_primary_experts largest of ℓ
    w_i = exp(ℓ_i) / Σ_{j∈C} exp(ℓ_j)  i ∈ C   (moe_primary_router_apply_softmax,
                                                norm_topk_prob)
    x'  = x + A_l(RMSNorm_1(x))
    u   = RMSNorm_2(x')
    out = x' + Σ_{i∈C, held} w_i · W_d,i (relu(W_g,i u) ⊙ W_u,i u)

``A_l(n)``: ``q = n W_q`` -> (H, e), ``k = n W_k``, ``v = n W_v`` -> (G, e),
``H = num_attention_heads``, ``G = num_key_value_heads``, ``e = head_dim``;
query head ``h`` reads key/value head ``h // (H / G)``; ``s = q kᵀ e^-½``,
causal. ``sliding_window_layout[l] = 0``: every earlier key visible;
``= 1``: key ``j`` visible to query ``i`` iff ``0 <= i − j <
sliding_window_size``. ``rope_layout[l] = 1``: rotary embedding of ``q`` and
``k`` by ``position · rope_theta^(−2j/e)`` on all ``e`` dimensions, dimension
``j`` paired with ``j + e/2``; ``= 0``: none. ``z = softmax(s) v``; ``y =
concat_h(z_h) W_o``. After the last block a final RMSNorm and the untied
head; loss = mean cross-entropy of the next token over the vocabulary rows
held. The chip's share: the router keeps its 64 outputs and its 6 a token,
the weights are normalised over all six chosen, and only the held experts'
terms are added.

Departures and assumptions (each also in the configuration file's
``assumed``): noted at their lines below.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.gqa_lm_model import rotary
from benchmarks.reference.lm_model import head_logits, rms_norm
from benchmarks.reference.model import Ops
from benchmarks.reference.window_moe_lm_params import has_rope, is_window

__all__ = ["batch_loss", "sequence_loss"]

ROWS_AT_ONCE = 1024  # query rows of a block of scores; rows of a block of logits


def _row_blocks(seq: int) -> int:
    """The largest block of at most ``ROWS_AT_ONCE`` rows that divides ``seq``."""
    return next(r for r in range(min(seq, ROWS_AT_ONCE), 0, -1) if seq % r == 0)


def attention(ops: Ops, x, p, c: dict, layer: int):
    """``x`` (seq, hidden) -> (seq, hidden)."""
    e, seq = c["head_dim"], x.shape[0]
    # no attention bias, no q/k norm: the config has no key for either (assumed)
    q = ops.einsum("sd,dhe->hse", x, p["q"]["kernel"]) * e ** -0.5
    k = ops.einsum("sd,dhe->hse", x, p["k"]["kernel"])
    v = ops.einsum("sd,dhe->hse", x, p["v"]["kernel"])
    if has_rope(c, layer):
        # rope_scaling null: the default type, on every dimension; the pairing
        # (j with j + e/2) is assumed: the config has no interleave key
        rope = {"rope_type": "default", "rope_theta": c["rope_theta"], "partial_rotary_factor": 1}
        q, k = rotary(q, rope), rotary(k, rope)
    heads, kv_heads = q.shape[0], k.shape[0]
    group, rows = heads // kv_heads, _row_blocks(seq)
    window = c["sliding_window_size"] if is_window(c, layer) else None
    key_at = jnp.arange(seq)

    @jax.checkpoint
    def some_rows(args):
        qq, kv_head, first = args  # one group's query heads, a block of their rows
        at = first + jnp.arange(rows)
        visible = key_at[None, :] <= at[:, None]
        if window is not None:
            visible = visible & (at[:, None] - key_at[None, :] < window)
        s = ops.einsum("hqe,ke->hqk", qq, k[kv_head])
        probs = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return ops.einsum("hqk,ke->hqe", probs, v[kv_head])

    blocks = seq // rows
    # (key/value head, row block) -> that group's query heads' rows
    qq = q.reshape(kv_heads, group, blocks, rows, e).transpose(0, 2, 1, 3, 4)
    qq = qq.reshape(kv_heads * blocks, group, rows, e)
    kv_head = jnp.repeat(jnp.arange(kv_heads), blocks)
    first = jnp.tile(jnp.arange(blocks) * rows, kv_heads)
    z = jax.lax.map(some_rows, (qq, kv_head, first))
    z = z.reshape(kv_heads, blocks, group, rows, e).transpose(0, 2, 1, 3, 4).reshape(heads, seq, e)
    return ops.einsum("hse,hed->sd", z, p["out"]["kernel"])  # no output gate


def route(ops: Ops, x, p, c: dict):
    """``x`` (seq, hidden), the block's input as it arrives — un-normalised:
    assumed; the other reading is after the input norm — -> (the chosen
    experts (seq, k), their weights (seq, k))."""
    logits = ops.einsum("sd,de->se", x, p["router"]["kernel"])
    picked, chosen = jax.lax.top_k(logits, c["moe_num_active_primary_experts"])
    # moe_primary_router_apply_softmax + norm_topk_prob: softmax over the chosen
    return chosen, jax.nn.softmax(picked, axis=1)


def reglu(ops: Ops, u, w):
    gate = ops.einsum("sd,dh->sh", u, w["gate"]["kernel"])
    up = ops.einsum("sd,dh->sh", u, w["up"]["kernel"])
    return ops.einsum("sh,hd->sd", jax.nn.relu(gate) * up, w["down"]["kernel"])


def expert_layer(ops: Ops, u, router_x, p, c: dict, first: int | None = None):
    """The layer's output on a chip that holds the experts ``first .. first +
    held`` (``p``'s stacked matrices): the experts read ``u``, the router
    ``router_x``. No shared expert (the description's "0 shared"), and the
    "secondary" experts the description mentions have no key in the config
    and are not built (assumed)."""
    first = c["experts_held"][0] if first is None else first
    chosen, weights = route(ops, router_x, p, c)

    def one_expert(total, xs):
        e, w = xs
        # the weight a token gives this expert: zero where it did not choose it
        mine = jnp.where(chosen == first + e, weights, 0.0).sum(axis=1)
        return total + mine[:, None] * reglu(ops, u, w), None

    stacked = {k: p[k] for k in ("gate", "up", "down")}
    held = stacked["gate"]["kernel"].shape[0]
    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(u),
                        (jnp.arange(held), stacked))
    return y


def block(ops: Ops, x, p, c: dict, layer: int):
    eps = c["rms_norm_eps"]
    after = x + attention(ops, rms_norm(x, p["ln1"], eps), p["attn"], c, layer)
    # every layer is sparse: the config gives no dense width (assumed)
    return after + expert_layer(ops, rms_norm(after, p["ln2"], eps), x, p["moe"], c)


def sequence_loss(params, tokens, c: dict, rounding: str = "float32"):
    """One sequence's loss; ``tokens`` (seq + 1,) ids from the vocabulary
    rows held."""
    low, _, only = rounding.partition("@")
    ops = Ops("float32" if only else low)  # the head's
    ids = tokens - c["vocab_rows"][0]
    x = params["embedding"][ids[:-1]]
    for i in range(c["num_hidden_layers"]):
        here = Ops(low) if only in ("", str(i)) else ops
        run = jax.checkpoint(lambda x, p, i=i, here=here: block(here, x, p, c, i))
        x = run(x, params[f"block_{i}"])
    seq = x.shape[0]
    rows = _row_blocks(seq)

    @jax.checkpoint
    def cross_entropy(args):  # summed over a block of rows
        h, targets = args
        logits = head_logits(ops, params, h, c)
        hit = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
        return (jax.nn.logsumexp(logits, axis=1) - hit).sum()

    parts = jax.lax.map(cross_entropy, (x.reshape(seq // rows, rows, -1),
                                        ids[1:].reshape(seq // rows, rows)))
    return parts.sum() / seq


def batch_loss(params, tokens, c: dict, rounding: str = "float32"):
    """Mean over the sequences of ``tokens`` (batch, seq + 1), one sequence
    after another. The family has no router bias to move between steps, so
    the loss is all a step hands on."""
    total, _ = jax.lax.scan(
        lambda total, row: (total + sequence_loss(params, row, c, rounding), None),
        jnp.zeros(()), tokens)
    return total / tokens.shape[0]
