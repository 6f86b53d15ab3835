"""A decoder-only language model whose token mixer is a gated short
convolution three layers in four and grouped-query softmax attention with
per-head q/k norms in the fourth, with sigmoid-routed sparse experts, no
shared expert and a head tied to the embedding, in plain ``jax.numpy``: loss,
gradients and the router-bias rule of one training step on one chip's share
of the experts and the vocabulary.

Written from ``LFM2-24B-A2B``'s ``config.json`` (``model_type: lfm2_moe``).
float32 throughout, every contraction at precision "highest"; no kernels: the
convolution is three shifted products, every visible (query, key) score
exists — one group of query heads and one block of query rows at a time, so
that 8192 tokens fit — the held experts one after another, the logits a block
of rows at a time. It imports nothing of the program; what it shares with the
other families' references (RMSNorm, the gated MLP, the expert layer on a
chip's share, the bias rule, the rotate-half rotation, ``Ops``) it takes from
them. ``rounding`` rounds the two operands of every contraction to a narrower
type first: the lower-precision control, never the reference; ``<type>@<l>``
rounds in block ``l`` alone, a one-layer fault for the limits to catch. One
sequence at a time, each block checkpointed.

``d = hidden_size``, no bias anywhere, RMSNorm with ``norm_eps``. Block ``i``
of the layers held (published layer ``first_layer + i``)::

    h   = x + mixer_i(RMSNorm_op(x))          (ln1)
    out = h + ffn_i(RMSNorm_ffn(h))           (ln2)

``ffn_i`` is the dense SwiGLU of ``intermediate_size`` for ``i <
num_dense_layers`` and the expert layer after; after the last block one
RMSNorm (the source's ``embedding_norm``), then the head.

``conv`` mixer of ``u``: ``z = u W_in``, ``W_in`` (d, 3d); ``B, C, x̃`` = the
three d-wide thirds of ``z`` in that order; ``c_t = Σ_{j=0..K−1} w_j ⊙ (B ⊙
x̃)_{t−K+1+j}``, ``K = conv_L_cache`` = 3, zero history before the sequence's
first position, ``w`` (K, d) one filter a channel, no activation and no bias
(``conv_bias`` false); ``y = (C ⊙ c) W_out``, ``W_out`` (d, d).

``full_attention`` mixer of ``u``: ``q = u W_q`` -> (H, e), ``k = u W_k``,
``v = u W_v`` -> (G, e), ``H = num_attention_heads``, ``G =
num_key_value_heads``, ``e = d / H``; ``q ← RMSNorm_e(q)``, ``k ←
RMSNorm_e(k)`` per head, each with its own learnt scale of ``e``; rotary
embedding on all ``e`` dimensions, dimension ``j`` paired with ``j + e/2``,
by ``position · rope_theta^(−2j/e)`` (``rope_type`` default); causal softmax
of ``q kᵀ / sqrt(e)``, query head ``h`` reading key/value head ``h // (H /
G)``; ``y = concat_h(z_h) W_o``. No gate.

Expert layer (``lm_model.expert_layer`` without its shared expert): ``s =
sigmoid(u W_r)``, the top ``num_experts_per_tok`` of ``s + b`` (``b`` the
balancing bias, ``use_expert_bias``, outside the gradient), weights
``routed_scaling_factor · s_i / Σ_chosen s`` (``norm_topk_prob``), experts
``W_d(silu(W_g u) ⊙ W_u u)``; only the held experts' terms are added. Head:
logits ``= RMSNorm(h) Eᵀ`` over the embedding's rows held. Loss = mean
cross-entropy of the next token over those rows.

Departures and assumptions (each also in the configuration file's
``assumed``): noted at their lines below.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.conv_moe_lm_params import head_dim, kinds
from benchmarks.reference.gqa_lm_model import rotary
from benchmarks.reference.lm_model import expert_layer, gated_mlp, next_biases, rms_norm
from benchmarks.reference.model import Ops

__all__ = ["batch_loss", "head_logits", "hidden_states", "next_biases", "sequence_loss"]

ROWS_AT_ONCE = 1024  # query rows of a block of scores; rows of a block of logits


def _row_blocks(seq: int) -> int:
    """The largest block of at most ``ROWS_AT_ONCE`` rows that divides ``seq``."""
    return next(r for r in range(min(seq, ROWS_AT_ONCE), 0, -1) if seq % r == 0)


def short_conv(ops: Ops, u, p):
    """``u`` (seq, hidden) -> (seq, hidden): the convolution as ``K`` shifted
    products."""
    seq, d = u.shape
    z = ops.einsum("sd,de->se", u, p["in_proj"]["kernel"])
    gate_in, gate_out, x = z[:, :d], z[:, d : 2 * d], z[:, 2 * d :]
    gated = gate_in * x
    taps = p["conv"]["kernel"]  # (K, d): tap j meets the token K − 1 − j positions back
    k = taps.shape[0]
    mixed = sum(taps[j] * jnp.pad(gated, ((k - 1 - j, 0), (0, 0)))[:seq] for j in range(k))
    return ops.einsum("sd,de->se", gate_out * mixed, p["out_proj"]["kernel"])


def attention(ops: Ops, u, p, c: dict):
    """``u`` (seq, hidden) -> (seq, hidden)."""
    e, seq, eps = head_dim(c), u.shape[0], c["norm_eps"]
    q = rms_norm(ops.einsum("sd,dhe->hse", u, p["q"]["kernel"]), p["q_norm"], eps) * e ** -0.5
    k = rms_norm(ops.einsum("sd,dhe->hse", u, p["k"]["kernel"]), p["k_norm"], eps)
    v = ops.einsum("sd,dhe->hse", u, p["v"]["kernel"])
    # the pairing (j with j + e/2) is assumed: the config has no interleave key
    rope = c["rope_parameters"] | {"partial_rotary_factor": 1}
    q, k = rotary(q, rope), rotary(k, rope)
    heads, kv_heads = q.shape[0], k.shape[0]
    group, rows = heads // kv_heads, _row_blocks(seq)
    key_at = jnp.arange(seq)

    @jax.checkpoint
    def some_rows(args):
        qq, kv_head, first = args  # one group's query heads, a block of their rows
        visible = key_at[None, :] <= (first + jnp.arange(rows))[:, None]
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


def block(ops: Ops, x, p, bias, c: dict):
    eps = c["norm_eps"]
    u = rms_norm(x, p["ln1"], eps)
    x = x + (short_conv(ops, u, p["conv"]) if "conv" in p else attention(ops, u, p["attn"], c))
    inner = rms_norm(x, p["ln2"], eps)
    if "moe" in p:
        # use_expert_bias: the bias moves by the repository's sign rule (assumed);
        # the source divides by the chosen scores' sum + 1e-6, this by the sum
        y, counts = expert_layer(ops, inner, p["moe"], bias["moe"]["router_bias"], c,
                                 shared=False)
        return x + y, counts
    return x + gated_mlp(ops, inner, p["mlp"]), None


def head_logits(ops: Ops, params, h, c: dict):
    """Tied (assumed: the config has no ``tie_word_embeddings`` key and the
    published siblings tie): the embedding's rows held are the head's."""
    return ops.einsum("sd,vd->sv", rms_norm(h, params["ln"], c["norm_eps"]),
                      params["embedding"])


def hidden_states(params, biases, ids, c: dict, rounding: str = "float32"):
    """``ids`` (seq + 1,) row indices into the embedding held -> ``(the last
    hidden state, {block name: routing counts}, the head's Ops)``."""
    low, _, only = rounding.partition("@")
    ops = Ops("float32" if only else low)  # the head's, and every block's but ``only``
    x, counts = params["embedding"][ids[:-1]], {}
    for i in range(len(kinds(c))):
        name = f"block_{i}"
        here = Ops(low) if only in ("", str(i)) else ops
        run = jax.checkpoint(lambda x, p, b, here=here: block(here, x, p, b, c))
        x, n = run(x, params[name], biases.get(name))
        if n is not None:
            counts[name] = n
    assert len(counts) == c["num_hidden_layers"] - c["num_dense_layers"]
    return x, counts, ops


def sequence_loss(params, biases, tokens, c: dict, rounding: str = "float32"):
    """One sequence's ``(loss, counts)``; ``tokens`` (seq + 1,) ids from the
    vocabulary rows held."""
    ids = tokens - c["vocab_rows"][0]
    x, counts, ops = hidden_states(params, biases, ids, c, rounding)
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
    return parts.sum() / seq, counts


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
