"""A decoder-only language model whose layers are Kimi delta attention (KDA,
a linear attention with a per-channel decay) five times out of six and
multi-head latent attention (MLA) the sixth, with group-limited sigmoid-
routed sparse experts beside a shared expert, in plain ``jax.numpy``: loss,
gradients and the router-bias rule of one training step on one chip's share
of the experts and the vocabulary.

Written from ``Ling-3.0-flash``'s ``config.json`` (``model_type:
bailing_hybrid``), the Kimi Linear report (arXiv:2510.26692) and the
argument names of flash-linear-attention's ``KimiDeltaAttention``. float32
throughout, every contraction at precision "highest"; no kernels, no chunks.
It imports nothing of the program; what it shares with the all-MLA family's
reference (``lm_model.py``: RMSNorm, the rotary embedding, the gated MLP,
the head, the bias rule) it takes from there. ``rounding`` rounds the two
operands of every contraction to a narrower type first: the lower-precision
control, never the reference. One sequence at a time, each block
checkpointed.

Block ``i``: ``x += A_i(norm(x))``; ``x += F_i(norm(x))``. ``A_i`` is MLA
when ``(i + 1) % layer_group_size == 0``, KDA otherwise; ``F_i`` the dense
SwiGLU MLP for ``i < first_k_dense_replace``, the expert layer after.

KDA, per head (d_k = d_v = ``head_dim``): ``q, k, v = x W_q, x W_k, x W_v``;
each through a causal depthwise convolution of ``short_conv_kernel_size``
taps (zero history before the sequence) and SiLU; ``q, k`` to unit L2 norm
(eps 1e-6), ``q`` times ``d_k^-½``; log-decay ``g_t = lower_bound ·
sigmoid(exp(A_log) · (x W_f + dt_bias))``, ``α_t = exp(g_t)``; ``β_t =
sigmoid(x W_b)``; **the recurrence, position by position**: ``S_t = (I − β_t
k_t k_tᵀ) Diag(α_t) S_{t−1} + β_t k_t v_tᵀ`` from ``S_0 = 0``, ``o_t = S_tᵀ
q_t``; RMSNorm of ``o_t`` over d_v with one learned scale; times the
head-wise gate ``sigmoid(x W_γ)``; ``W_o``.

MLA as in ``lm_model.py`` but ``q = x W_q`` with no query latent, and the
same head-wise gate on the core's output before ``W_o``.

Router in float32: ``s = sigmoid(x W_r)``, ``t = s + b``; the experts lie in
``n_group`` equal groups, a group's score is the sum of its two largest
``t``, the best ``topk_group`` groups stay, and the top k of their experts
by ``t`` are chosen; weights ``factor · s_i / Σ_chosen s``; the layer gives
``shared(x) + Σ w_i E_i(x)`` over the chosen experts held here.

MTP (``mtp_use_kda: false``): ``lm_model.py``'s module, its block of the MLA
kind with experts. Loss = CE(trunk) + ``mtp_loss_scaling_factor`` · CE(MTP).

Departures and assumptions: the configuration file's ``assumed``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.lm_model import (HEADS_AT_ONCE, gated_mlp, head_logits, next_biases,
                                           rms_norm, rotary)
from benchmarks.reference.model import Ops

__all__ = ["batch_loss", "next_biases", "sequence_loss"]
POSITIONS_A_BLOCK = 64  # the recurrence is checkpointed in blocks of positions
UNIT_EPS = 1e-6


def delta_rule(ops: Ops, q, k, v, g, beta):
    """The gated delta rule, literally: ``q``, ``k``, ``g`` (heads, seq,
    d_k), ``v`` (heads, seq, d_v), ``beta`` (heads, seq) -> ``(o (heads, seq,
    d_v), the state after the last position)``. One position after another;
    blocks of positions are checkpointed so that the gradient keeps a state
    a block and not a state a position."""
    heads, seq, d_k = k.shape
    pad = -seq % POSITIONS_A_BLOCK  # a position with k = 0 and beta = 0 changes nothing

    def by_block(x):  # (heads, seq, ...) -> (blocks, positions, heads, ...)
        x = jnp.pad(jnp.moveaxis(x, 1, 0), [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape(-1, POSITIONS_A_BLOCK, *x.shape[1:])

    def position(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[:, :, None] * state
        miss = v_t - ops.einsum("hkv,hk->hv", state, k_t)
        state = state + ops.einsum("hk,hv->hkv", b_t[:, None] * k_t, miss)
        return state, ops.einsum("hkv,hk->hv", state, q_t)

    block = jax.checkpoint(lambda state, xs: jax.lax.scan(position, state, xs))
    start = jnp.zeros((heads, d_k, v.shape[-1]), jnp.float32)
    state, o = jax.lax.scan(block, start, tuple(map(by_block, (q, k, v, g, beta))))
    o = o.reshape(-1, heads, v.shape[-1])[:seq]
    return jnp.moveaxis(o, 0, 1), state


def short_conv(x, taps):
    """``silu(Σ_j taps[j] ⊙ x_{t − K + 1 + j})``: ``x`` (heads, seq, width),
    ``taps`` (K, heads, width); positions before the sequence are zero."""
    width = taps.shape[0]
    total = jnp.zeros_like(x)
    for j in range(width):
        back = width - 1 - j
        moved = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, : x.shape[1]]
        total = total + taps[j][:, None, :] * moved
    return jax.nn.silu(total)


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + UNIT_EPS)


def linear_attention(ops: Ops, x, p, c: dict):
    """KDA: ``x`` (seq, hidden) -> ``((seq, hidden), the final state)``."""
    wide = lambda name: ops.einsum("sd,dhe->hse", x, p[name]["kernel"])
    thin = lambda name: ops.einsum("sd,dh->hs", x, p[name]["kernel"])
    q = short_conv(wide("q"), p["q_conv"]["kernel"])
    k = short_conv(wide("k"), p["k_conv"]["kernel"])
    v = short_conv(wide("v"), p["v_conv"]["kernel"])
    q = unit(q) * c["head_dim"] ** -0.5
    k = unit(k)
    rate = jnp.exp(p["A_log"])[:, None, None]
    g = c["kda_lower_bound"] * jax.nn.sigmoid(rate * (wide("f") + p["dt_bias"][:, None, :]))
    beta = jax.nn.sigmoid(thin("b"))
    o, state = delta_rule(ops, q, k, v, g, beta)
    o = rms_norm(o, p["o_norm"], c["rms_norm_eps"]) * jax.nn.sigmoid(thin("gate"))[..., None]
    return ops.einsum("hse,hed->sd", o, p["out"]["kernel"]), state


def latent_attention(ops: Ops, x, p, c: dict):
    """MLA with no query latent and a head-wise output gate."""
    nope, rope, eps = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["rms_norm_eps"]
    rank = c["kv_lora_rank"]
    q = ops.einsum("sd,dhe->hse", x, p["q"]["kernel"]) * (nope + rope) ** -0.5
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
    n = HEADS_AT_ONCE if h % HEADS_AT_ONCE == 0 else 1
    groups = lambda t: t.reshape(h // n, n, *t.shape[1:])
    z = jax.lax.map(some_heads, tuple(map(groups, (q_nope, q_pe, k_nope, v))))
    z = z.reshape(h, *z.shape[2:])
    z = z * jax.nn.sigmoid(ops.einsum("sd,dh->hs", x, p["gate"]["kernel"]))[..., None]
    return ops.einsum("hse,hed->sd", z, p["out"]["kernel"])


def route(ops: Ops, x, p, bias, c: dict):
    """(chosen experts (seq, k), their weights (seq, k), counts over all
    experts): the top k by ``s + b`` among the experts of the ``topk_group``
    groups whose two largest ``s + b`` sum highest."""
    s = jax.nn.sigmoid(ops.einsum("sd,de->se", x, p["router"]["kernel"]))
    biased = s + bias
    seq, experts = s.shape
    groups, kept = c["n_group"], c["topk_group"]
    by_group = biased.reshape(seq, groups, experts // groups)
    score = jnp.sort(by_group, axis=-1)[..., -2:].sum(axis=-1)  # (seq, groups)
    # a group stays if fewer than ``kept`` groups score higher (or, at a tie,
    # come before it)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    stays = jnp.repeat(rank < kept, experts // groups, axis=1)
    _, chosen = jax.lax.top_k(jnp.where(stays, biased, -jnp.inf), c["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    weights = c["routed_scaling_factor"] * picked / picked.sum(axis=1, keepdims=True)
    counts = (chosen[..., None] == jnp.arange(experts)).sum(axis=(0, 1))
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
    """-> ``(x, routing counts or None, the linear layer's final state or None)``."""
    eps = c["rms_norm_eps"]
    inner, state = rms_norm(x, p["ln1"], eps), None
    if "A_log" in p["attn"]:
        y, state = linear_attention(ops, inner, p["attn"], c)
    else:
        y = latent_attention(ops, inner, p["attn"], c)
    x = x + y
    inner = rms_norm(x, p["ln2"], eps)
    if "moe" in p:
        y, counts = expert_layer(ops, inner, p["moe"], bias["moe"]["router_bias"], c)
        return x + y, counts, state
    return x + gated_mlp(ops, inner, p["mlp"]), None, state


def hidden_states(ops: Ops, params, biases, ids, c: dict):
    """``ids`` (seq + 1 + mtp,) row indices into the embedding held ->
    ``([trunk hidden, mtp hidden?], {block name: counts}, {block name: the
    linear-attention layer's final state})``."""
    mtp = c["num_nextn_predict_layers"]
    seq = ids.shape[0] - 1 - mtp
    run = jax.checkpoint(lambda x, p, b: block(ops, x, p, b, c))
    x, counts, states = params["embedding"][ids[:seq]], {}, {}
    for i in range(c["num_hidden_layers"]):
        name = f"block_{i}"
        x, n, state = run(x, params[name], biases.get(name))
        if n is not None:
            counts[name] = n
        if state is not None:
            states[name] = state
    hidden = [x]
    if mtp:
        eps = c["rms_norm_eps"]
        nxt = rms_norm(params["embedding"][ids[1 : seq + 1]], params["mtp_embed_norm"], eps)
        both = jnp.concatenate([nxt, rms_norm(x, params["mtp_hidden_norm"], eps)], axis=-1)
        merged = ops.einsum("sd,dm->sm", both, params["mtp_merge"]["kernel"])
        y, counts["mtp_block"], _ = run(merged, params["mtp_block"], biases["mtp_block"])
        hidden.append(y)
    return hidden, counts, states


def sequence_loss(params, biases, tokens, c: dict, rounding: str = "float32"):
    """One sequence's ``(loss, (trunk CE, mtp CE, counts))``; ``tokens``
    (seq + 1 + mtp,) ids from the vocabulary rows held."""
    ops = Ops(rounding)
    ids = tokens - c["vocab_rows"][0]
    seq = ids.shape[0] - 1 - c["num_nextn_predict_layers"]
    hidden, counts, _ = hidden_states(ops, params, biases, ids, c)

    @jax.checkpoint
    def cross_entropy(h, targets):
        logits = head_logits(ops, params, h, c)
        hit = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
        return (jax.nn.logsumexp(logits, axis=1) - hit).mean()

    ces = [cross_entropy(h, ids[1 + i : seq + 1 + i]) for i, h in enumerate(hidden)]
    loss = ces[0] + (c["mtp_loss_scaling_factor"] * ces[1] if len(ces) > 1 else 0.0)
    return loss, (ces[0], ces[-1], counts)


def batch_loss(params, biases, tokens, c: dict, rounding: str = "float32"):
    """Mean over the sequences of ``tokens`` (batch, seq + 1 + mtp), one
    sequence after another: ``(loss, counts summed over the batch)``."""
    def one(total, row):
        loss, (_, _, counts) = sequence_loss(params, biases, row, c, rounding)
        return (total[0] + loss, jax.tree_util.tree_map(jnp.add, total[1], counts)), None

    e = c["published"]["num_experts"]
    zero = {name: jnp.zeros((e,), jnp.float32) for name in biases}
    (loss, counts), _ = jax.lax.scan(one, (jnp.zeros(()), zero), tokens)
    return loss / tokens.shape[0], counts
