"""A decoder-only language model with multi-head latent attention, sigmoid-
routed sparse experts beside a shared expert and one multi-token-prediction
module, in plain ``jax.numpy``: loss, gradients and the router-bias rule of
one training step on one chip's share of the experts and the vocabulary.

Written from the published description of the DeepSeek-V3 family as
``JoyAI-LLM-Flash``'s ``config.json`` sizes it. float32 throughout, every
contraction at precision "highest"; no kernels. It imports nothing of the
program (``Ops``, contractions at one rounding, is the ViT reference's). ``rounding`` rounds the two operands of every contraction to a
narrower type first (accumulation stays float32): the lower-precision
control, never the reference. One sequence at a time, each block
checkpointed, attention a few heads at a time and the held experts one
after another, so that float32 at 8192 tokens fits beside the optimizer's
state.

Pre-norm residual blocks, RMSNorm, no bias. Attention: ``c_q = norm(x
W_qa)``, ``q = c_q W_qb`` (heads x (nope ‖ rope)); ``[c_kv ‖ k_pe] = x
W_kva``; ``[k_nope ‖ v] = norm(c_kv) W_kvb``; rotary embedding on adjacent
pairs of the rope columns, the one ``k_pe`` a token shared by all heads;
causal softmax of ``q·k / sqrt(nope + rope)``. MLP ``W_d(silu(W_g x) ⊙ W_u
x)``. Router in float32: ``s = sigmoid(x W_r)``, the top k of ``s + b``,
weights ``factor · s_i / Σ_chosen s`` (normalised over all k chosen); the
layer gives ``shared(x) + Σ w_i E_i(x)`` over the chosen experts held here —
the absent experts' part is left out (the chip's share, as in the program).
MTP: ``h' = W_eh [norm(Emb(t_{i+1})) ‖ norm(h_i)]`` -> one expert block ->
the trunk's final norm and head, predicting ``t_{i+2}``. Loss = CE(trunk) +
λ·CE(MTP), each the mean over tokens, over the vocabulary rows held.

Departures from the published description, each also in the configuration
file's ``assumed``: λ, the bias rule's rate and the initial scale are not in
``config.json``; ``n_group`` and ``topk_group`` are 1, so there is no group
limit to implement.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.model import Ops

HEADS_AT_ONCE = 2


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def rotary(x, theta: float):
    """Adjacent pairs (2i, 2i+1) of the last axis turn by position x
    theta^(-2i/d); positions run along the axis before the last."""
    seq, d = x.shape[-2:]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        even * jnp.sin(angle) + odd * jnp.cos(angle)], axis=-1)
    return turned.reshape(x.shape)


def attention(ops: Ops, x, p, c: dict):
    """``x`` (seq, hidden) -> (seq, hidden)."""
    nope, rope, eps = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["rms_norm_eps"]
    rank = c["kv_lora_rank"]
    c_q = rms_norm(ops.einsum("sd,dr->sr", x, p["q_a"]["kernel"]), p["q_norm"], eps)
    q = ops.einsum("sr,rhe->hse", c_q, p["q_b"]["kernel"]) * (nope + rope) ** -0.5
    kv = ops.einsum("sd,dr->sr", x, p["kv_a"]["kernel"])
    k_pe = rotary(kv[:, rank:], c["rope_theta"])
    kv = ops.einsum("sr,rhe->hse", rms_norm(kv[:, :rank], p["kv_norm"], eps), p["kv_b"]["kernel"])
    q_nope, q_pe = q[..., :nope], rotary(q[..., nope:], c["rope_theta"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    causal = jnp.tril(jnp.ones((x.shape[0], x.shape[0]), bool))

    @jax.checkpoint
    def some_heads(args):
        qn, qp, kn, vv = args
        s = ops.einsum("hqe,hke->hqk", qn, kn) + ops.einsum("hqe,ke->hqk", qp, k_pe)
        probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return ops.einsum("hqk,hke->hqe", probs, vv)

    h = q.shape[0]
    g = HEADS_AT_ONCE if h % HEADS_AT_ONCE == 0 else 1
    groups = lambda t: t.reshape(h // g, g, *t.shape[1:])
    z = jax.lax.map(some_heads, tuple(map(groups, (q_nope, q_pe, k_nope, v))))
    return ops.einsum("hse,hed->sd", z.reshape(h, *z.shape[2:]), p["out"]["kernel"])


def gated_mlp(ops: Ops, x, p):
    gate = ops.einsum("sd,dh->sh", x, p["gate"]["kernel"])
    up = ops.einsum("sd,dh->sh", x, p["up"]["kernel"])
    return ops.einsum("sh,hd->sd", jax.nn.silu(gate) * up, p["down"]["kernel"])


def route(ops: Ops, x, p, bias, c: dict):
    """(chosen experts (seq, k), their weights (seq, k), counts over all
    experts)."""
    s = jax.nn.sigmoid(ops.einsum("sd,de->se", x, p["router"]["kernel"]))
    _, chosen = jax.lax.top_k(s + bias, c["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    weights = c["routed_scaling_factor"] * picked / picked.sum(axis=1, keepdims=True)
    counts = (chosen[..., None] == jnp.arange(s.shape[1])).sum(axis=(0, 1))
    return chosen, weights, counts.astype(jnp.float32)


def expert_layer(ops: Ops, x, p, bias, c: dict, first: int | None = None,
                 shared: bool = True):
    """The layer's output on a chip that holds the experts ``first ..
    first + held`` (``p``'s stacked matrices), and the routing counts.
    ``shared=False`` leaves the shared expert out (for adding shares up)."""
    first = c["experts_held"][0] if first is None else first
    chosen, weights, counts = route(ops, x, p, bias, c)

    def one_expert(total, xs):
        e, w = xs
        # the weight a token gives this expert: zero where it did not choose it
        mine = jnp.where(chosen == first + e, weights, 0.0).sum(axis=1)
        return total + mine[:, None] * gated_mlp(ops, x, w), None

    stacked = {k: p[k] for k in ("gate", "up", "down")}
    held = stacked["gate"]["kernel"].shape[0]
    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                        (jnp.arange(held), stacked))
    if shared:
        y = y + gated_mlp(ops, x, p["shared"])
    return y, counts


def block(ops: Ops, x, p, bias, c: dict):
    eps = c["rms_norm_eps"]
    x = x + attention(ops, rms_norm(x, p["ln1"], eps), p["attn"], c)
    inner = rms_norm(x, p["ln2"], eps)
    if "moe" in p:
        y, counts = expert_layer(ops, inner, p["moe"], bias["moe"]["router_bias"], c)
        return x + y, counts
    return x + gated_mlp(ops, inner, p["mlp"]), None


def hidden_states(ops: Ops, params, biases, ids, c: dict):
    """``ids`` (seq + 1 + mtp,) row indices into the embedding held ->
    ``([trunk hidden, mtp hidden?], {block name: counts})``."""
    mtp = c["num_nextn_predict_layers"]
    seq = ids.shape[0] - 1 - mtp
    run = jax.checkpoint(lambda x, p, b: block(ops, x, p, b, c))
    x, counts = params["embedding"][ids[:seq]], {}
    for i in range(c["num_hidden_layers"]):
        name = f"block_{i}"
        x, n = run(x, params[name], biases.get(name))
        if n is not None:
            counts[name] = n
    hidden = [x]
    if mtp:
        eps = c["rms_norm_eps"]
        nxt = rms_norm(params["embedding"][ids[1 : seq + 1]], params["mtp_embed_norm"], eps)
        both = jnp.concatenate([nxt, rms_norm(x, params["mtp_hidden_norm"], eps)], axis=-1)
        merged = ops.einsum("sd,dm->sm", both, params["mtp_merge"]["kernel"])
        y, counts["mtp_block"] = run(merged, params["mtp_block"], biases["mtp_block"])
        hidden.append(y)
    return hidden, counts


def head_logits(ops: Ops, params, h, c: dict):
    return ops.einsum("sd,dv->sv", rms_norm(h, params["ln"], c["rms_norm_eps"]),
                      params["head"]["kernel"])


def sequence_loss(params, biases, tokens, c: dict, rounding: str = "float32"):
    """One sequence's ``(loss, (trunk CE, mtp CE, counts))``; ``tokens``
    (seq + 1 + mtp,) ids from the vocabulary rows held."""
    ops = Ops(rounding)
    ids = tokens - c["vocab_rows"][0]
    seq = ids.shape[0] - 1 - c["num_nextn_predict_layers"]
    hidden, counts = hidden_states(ops, params, biases, ids, c)

    @jax.checkpoint
    def cross_entropy(h, targets):
        logits = head_logits(ops, params, h, c)
        hit = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
        return (jax.nn.logsumexp(logits, axis=1) - hit).mean()

    ces = [cross_entropy(h, ids[1 + i : seq + 1 + i]) for i, h in enumerate(hidden)]
    loss = ces[0] + (c["mtp_loss_weight"] * ces[1] if len(ces) > 1 else 0.0)
    return loss, (ces[0], ces[-1], counts)


def batch_loss(params, biases, tokens, c: dict, rounding: str = "float32"):
    """Mean over the sequences of ``tokens`` (batch, seq + 1 + mtp), one
    sequence after another: ``(loss, counts summed over the batch)``."""
    def one(total, row):
        loss, (_, _, counts) = sequence_loss(params, biases, row, c, rounding)
        return (total[0] + loss, jax.tree_util.tree_map(jnp.add, total[1], counts)), None

    e = c["published"]["n_routed_experts"]
    zero = {name: jnp.zeros((e,), jnp.float32) for name in biases}
    (loss, counts), _ = jax.lax.scan(one, (jnp.zeros(()), zero), tokens)
    return loss / tokens.shape[0], counts


def next_biases(biases, counts, rate: float):
    """``b += rate · sign(mean(c) − c)`` after an applied step."""
    return {name: {"moe": {"router_bias": b["moe"]["router_bias"]
                           + rate * jnp.sign(counts[name].mean() - counts[name])}}
            for name, b in biases.items()}
