"""A decoder-only language model whose layers are Kimi delta attention (KDA,
a linear attention with a per-channel decay) three times out of four and
rope-free gated grouped-query softmax attention the fourth, with
sigmoid-routed sparse experts beside a shared expert, in plain ``jax.numpy``:
loss, gradients and the router-bias rule of one training step on one chip's
share of the experts, **the heads** and the vocabulary.

Written from ``Solar-Open2-250B``'s ``config.json`` (``model_type:
solar_open2``) and, for what its ``kda_*`` / ``linear_attn_config`` keys
name, flash-linear-attention's ``KimiDeltaAttention`` at its defaults.
float32 throughout, every contraction at precision "highest"; no kernels, no
chunks, no blocks: the delta rule position by position, the (seq, seq)
scores whole. It imports nothing of the program; the recurrence, the
convolution and the unit norm are the hybrid family's reference's
(``hybrid_lm_model.py``), RMSNorm, the gated MLP, the expert layer on a
chip's share, the head and the bias rule the all-MLA family's
(``lm_model.py``): what this file holds is what is new. ``rounding`` rounds
the two operands of every contraction to a narrower type first: the
lower-precision control, never the reference. One sequence at a time, each
block checkpointed.

Block ``i``: ``x += A_i(norm(x))``; ``x += F_i(norm(x))``; RMSNorm eps
``rms_norm_eps``, no bias, a final norm and the untied head. ``A_i`` is
grouped-query attention where ``i`` is in ``gqa_layers``, KDA otherwise;
``F_i`` is the expert layer (``first_k_dense_replace`` 0; a leading dense
layer would be the SwiGLU MLP): one shared expert + the top
``num_experts_per_tok`` of the routed ones by ``sigmoid(x W_r) + b``, weights
``routed_scaling_factor · s_i / Σ_chosen s`` (``norm_topk_prob``).

Grouped-query layer, ``H`` query heads over ``G`` key/value heads of ``d =
head_dim`` (query head ``h`` reads key/value head ``h // (H / G)``): ``q = x
W_q``, ``k = x W_k``, ``v = x W_v``, **no rotary embedding** (``use_rope``
false) and no q/k norm; ``s = q kᵀ / sqrt(d)``, key ``j`` visible to query
``i`` iff ``j <= i``; ``z = softmax(s) v``; ``z_h ← sigmoid(x W_γ)_h z_h``
(``use_gqa_gate``); ``W_o``.

KDA layer, per head (d_k = d_v = ``linear_attn_config.head_dim``): ``q, k, v
= x W_q, x W_k, x W_v``, each through a causal depthwise convolution of
``short_conv_kernel_size`` taps (zero history before the sequence) and SiLU;
``q, k`` to unit L2 norm (eps 1e-6), ``q`` times ``d_k^-½``; the log-decay
``g_t = −exp(A_log_h) · softplus(x W_fa W_fb + dt_bias)`` with **no floor**
(no ``kda_safe_gate`` / ``kda_lower_bound`` key), ``W_f`` through a rank of
``head_dim`` (``kda_use_full_proj`` false); ``β_t = 2 · sigmoid(x W_b)``
(``kda_allow_neg_eigval``: the transition's eigenvalue along ``k_t``, ``1 −
β_t``, lies in (−1, 1)); the recurrence, position by position: ``S_t = (I −
β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ`` from ``S_0 = 0``, ``o_t =
S_tᵀ q_t``; RMSNorm of ``o_t`` over d_v with one learned scale; times the
element-wise gate ``sigmoid(x W_γa W_γb)``; ``W_o``.

**A share of the heads.** The parameters hold the heads of this chip's
slice: ``W_q``, ``W_k``, ``W_v``, the second factors ``W_fb`` / ``W_γb``,
``W_b``, ``W_γ``, the filters, ``A_log``, ``dt_bias`` and ``W_o``'s rows for
those heads; the first factors ``W_fa`` / ``W_γa`` and the output norm's
scale are what every chip holds alike. What the absent heads would add to
``A_i``'s output is left out, as the absent experts' part is: a sum over the
slices gives the uncut layer (a test adds them up).

Departures and assumptions (each also in the configuration file's
``assumed``): noted at their lines below.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.hybrid_lm_model import delta_rule, short_conv, unit
from benchmarks.reference.kda_gqa_lm_params import is_linear
from benchmarks.reference.lm_model import (HEADS_AT_ONCE, expert_layer, gated_mlp, head_logits,
                                           next_biases, rms_norm)
from benchmarks.reference.model import Ops

__all__ = ["batch_loss", "next_biases", "sequence_loss"]


def linear_attention(ops: Ops, x, p, c: dict):
    """KDA over the heads ``p`` holds: ``x`` (seq, hidden) -> ``((seq,
    hidden), the final state, beta)``."""
    wide = lambda name: ops.einsum("sd,dhe->hse", x, p[name]["kernel"])
    # W_f and W_gamma through a rank (kda_use_full_proj false); flash-linear-
    # attention's second gate factor has a bias, left out: no layer here has one
    low = lambda name: ops.einsum("sr,rhe->hse", ops.einsum("sd,dr->sr", x, p[f"{name}_a"]["kernel"]),
                                  p[f"{name}_b"]["kernel"])
    q = short_conv(wide("q"), p["q_conv"]["kernel"])
    k = short_conv(wide("k"), p["k_conv"]["kernel"])
    v = short_conv(wide("v"), p["v_conv"]["kernel"])
    q = unit(q) * c["linear_attn_config"]["head_dim"] ** -0.5
    k = unit(k)
    # the softplus gate: assumed from the absence of a safe-gate key
    g = -jnp.exp(p["A_log"])[:, None, None] * jax.nn.softplus(low("f") + p["dt_bias"][:, None, :])
    beta = 2.0 * jax.nn.sigmoid(ops.einsum("sd,dh->hs", x, p["b"]["kernel"]))
    o, state = delta_rule(ops, q, k, v, g, beta)
    o = rms_norm(o, p["o_norm"], c["rms_norm_eps"]) * jax.nn.sigmoid(low("gate"))
    return ops.einsum("hse,hed->sd", o, p["out"]["kernel"]), state, beta


def attention(ops: Ops, x, p, c: dict):
    """Grouped-query attention over the heads ``p`` holds, no rotation:
    ``x`` (seq, hidden) -> (seq, hidden)."""
    q = ops.einsum("sd,dhe->hse", x, p["q"]["kernel"]) * c["head_dim"] ** -0.5
    k = ops.einsum("sd,dhe->hse", x, p["k"]["kernel"])
    v = ops.einsum("sd,dhe->hse", x, p["v"]["kernel"])
    heads, group = q.shape[0], q.shape[0] // k.shape[0]
    at = jnp.arange(x.shape[0])
    visible = at[None, :] <= at[:, None]

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
    # use_gqa_gate — assumed to be a head-wise sigmoid gate on the core's output
    z = z * jax.nn.sigmoid(ops.einsum("sd,dh->hs", x, p["gate"]["kernel"]))[..., None]
    return ops.einsum("hse,hed->sd", z, p["out"]["kernel"])


def block(ops: Ops, x, p, bias, c: dict, layer: int):
    """-> ``(x, routing counts or None)``."""
    eps = c["rms_norm_eps"]
    inner = rms_norm(x, p["ln1"], eps)
    if is_linear(c, layer):
        x = x + linear_attention(ops, inner, p["attn"], c)[0]
    else:
        x = x + attention(ops, inner, p["attn"], c)
    inner = rms_norm(x, p["ln2"], eps)
    if "moe" in p:
        # the scoring rule is assumed (the config names none): lm_model's
        y, counts = expert_layer(ops, inner, p["moe"], bias["moe"]["router_bias"], c)
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

    e = c["published"]["n_routed_experts"]
    zero = {name: jnp.zeros((e,), jnp.float32) for name in biases}
    (loss, counts), _ = jax.lax.scan(one, (jnp.zeros(()), zero), tokens)
    return loss / tokens.shape[0], counts
