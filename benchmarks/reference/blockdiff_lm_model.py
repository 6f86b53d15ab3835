"""A decoder-only grouped-query sparse-expert language model trained as a
block-diffusion model, in plain ``jax.numpy``: loss and gradients of one
training step on one chip's share of the experts and the vocabulary.

Written from ``SDAR-30B-A3B-Chat``'s ``config.json`` (``model_type:
sdar_moe``) and the training form of BD3-LMs (arXiv:2503.09573, "vectorized
training") that SDAR (arXiv:2510.06303) uses. float32 throughout, every
contraction at precision "highest"; no kernels: the (2 L, 2 L) visibility
mask is built from each position's diffusion block literally, every score of
a slab of query rows exists and goes through a dense softmax, the held
experts are summed one after another over every token, and the logits are
formed a slab of rows at a time. It imports nothing of the program; what it
shares with the other families' references (RMSNorm, the head, the
rotate-half rotation, ``Ops``) it takes from them. ``rounding`` rounds the two
operands of every contraction to a narrower type first: the lower-precision
control, never the reference; ``<type>@<l>`` rounds in block ``l`` alone. One
sequence at a time, each block checkpointed.

**The objective.** A sequence ``x`` of ``L`` tokens lies in blocks of ``B =
diffusion_block_length``. A step draws, for each (sequence, block), ``t ~
U[eps, 1]`` (``eps = diffusion_noise_eps``), and masks each token of the block
independently with probability ``t``: its id becomes ``mask_token_id``. The
trunk reads ``[x ; x_t]``, the clean copy and then the noisy one, ``2 L``
rows, both copies at rope positions ``0 .. L − 1``. With ``b(i) = (i mod L) //
B``, key ``j`` is visible to query ``i`` iff

- ``i`` clean and ``j`` clean and ``b(j) <= b(i)``; or
- ``i`` noisy and ``j`` clean and ``b(j) < b(i)``; or
- ``i`` noisy and ``j`` noisy and ``b(j) == b(i)``

(a clean query never sees a noisy key). Loss ``= 1 / (batch · L) · Σ over the
masked positions i of the noisy copy of (1 / t_{b(i)}) · (−log
softmax(head(h_i))[x_i])``: the logits at a position predict that position's
own token, no shift (assumed); the clean copy carries no loss.

Block ``l`` with input ``x`` (2 L, d) (``d = hidden_size``, RMSNorm eps
``rms_norm_eps``, no bias anywhere)::

    x'  = x + A(RMSNorm_1(x))
    u   = RMSNorm_2(x')
    p   = softmax(u W_r)                 W_r (d, 128), over all 128
    C   = the num_experts_per_tok largest of p
    w_i = p_i / Σ_{j∈C} p_j    i ∈ C     (norm_topk_prob)
    out = x' + Σ_{i∈C, held} w_i · W_d,i (silu(W_g,i u) ⊙ W_u,i u)

``A(n)``: ``q = n W_q`` -> (H, e), ``k = n W_k``, ``v = n W_v`` -> (G, e), ``H =
num_attention_heads``, ``G = num_key_value_heads``, ``e = head_dim``; a
per-head RMSNorm over the ``e`` dimensions of ``q`` and of ``k``, each with its
own learnt scale (assumed: the Qwen3-MoE line); rotary embedding of both by
``position · rope_theta^(−2j/e)`` on all ``e`` dimensions, dimension ``j``
paired with ``j + e/2`` (assumed); query head ``h`` reads key/value head ``h
// (H / G)``; ``s = q kᵀ e^-½``, softmax over the visible keys, ``z =
softmax(s) v``; ``y = concat_h(z_h) W_o``. After the last block a final
RMSNorm and the untied head, over the vocabulary rows held. The chip's
share: the router keeps its 128 outputs and its 8 a token, the weights are
normalised over all eight chosen, and only the held experts' terms are added.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.gqa_lm_model import rotary
from benchmarks.reference.lm_model import head_logits, rms_norm
from benchmarks.reference.model import Ops

__all__ = ["batch_loss", "core_probe", "draw_noise", "sequence_logits", "sequence_loss",
           "visible"]

ROWS_AT_ONCE = 1024  # query rows of a slab of scores; rows of a slab of logits


def _slab(rows: int) -> int:
    """The largest slab of at most ``ROWS_AT_ONCE`` rows that divides ``rows``."""
    return next(r for r in range(min(rows, ROWS_AT_ONCE), 0, -1) if rows % r == 0)


def draw_noise(key, batch: int, seq: int, c: dict):
    """One step's noise from its key: ``(t, masked)``, both (batch, seq): the
    level of each position's block, and whether the position is masked. The
    key is split in two; the first half draws one level a (sequence, block),
    uniform over [eps, 1), the second one uniform a position, and a position
    is masked where its uniform is below its block's level."""
    block, eps = c["diffusion_block_length"], c["diffusion_noise_eps"]
    for_levels, for_positions = jax.random.split(key)
    levels = jax.random.uniform(for_levels, (batch, seq // block), jnp.float32, eps, 1.0)
    t = jnp.repeat(levels, block, axis=1)
    return t, jax.random.uniform(for_positions, (batch, seq), jnp.float32) < t


def visible(queries, seq: int, block: int):
    """Rows ``queries`` of the (2 seq, 2 seq) mask, from each position's copy
    and diffusion block (module docstring)."""
    i, j = queries[:, None], jnp.arange(2 * seq)[None, :]
    i_noisy, j_noisy = i >= seq, j >= seq
    b_i, b_j = (i % seq) // block, (j % seq) // block
    return ((~i_noisy & ~j_noisy & (b_j <= b_i))
            | (i_noisy & ~j_noisy & (b_j < b_i))
            | (i_noisy & j_noisy & (b_j == b_i)))


def attention(ops: Ops, x, p, c: dict):
    """``x`` (2 seq, hidden), the clean copy's rows first -> (2 seq, hidden)."""
    e, rows = c["head_dim"], x.shape[0]
    seq, eps = rows // 2, c["rms_norm_eps"]
    q = ops.einsum("sd,dhe->hse", x, p["q"]["kernel"])
    k = ops.einsum("sd,dhe->hse", x, p["k"]["kernel"])
    v = ops.einsum("sd,dhe->hse", x, p["v"]["kernel"])
    # a per-head RMSNorm of q and of k before the rope (assumed: config.json has no key)
    q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    # rope_scaling null: the default type, on every dimension; both copies at
    # positions 0 .. seq − 1; the pairing (j with j + e/2) is assumed
    rope = {"rope_type": "default", "rope_theta": c["rope_theta"], "partial_rotary_factor": 1}
    turn = lambda a: rotary(a.reshape(a.shape[0], 2, seq, e), rope).reshape(a.shape)
    q, k = turn(q) * e ** -0.5, turn(k)
    heads, kv_heads = q.shape[0], k.shape[0]
    group, slab = heads // kv_heads, _slab(rows)

    @jax.checkpoint
    def some_rows(args):
        qq, kv_head, first = args  # one group's query heads, a slab of their rows
        see = visible(first + jnp.arange(slab), seq, c["diffusion_block_length"])
        s = ops.einsum("hqe,ke->hqk", qq, k[kv_head])
        probs = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return ops.einsum("hqk,ke->hqe", probs, v[kv_head])

    slabs = rows // slab
    # (key/value head, slab) -> that group's query heads' rows
    qq = q.reshape(kv_heads, group, slabs, slab, e).transpose(0, 2, 1, 3, 4)
    qq = qq.reshape(kv_heads * slabs, group, slab, e)
    kv_head = jnp.repeat(jnp.arange(kv_heads), slabs)
    first = jnp.tile(jnp.arange(slabs) * slab, kv_heads)
    z = jax.lax.map(some_rows, (qq, kv_head, first))
    z = z.reshape(kv_heads, slabs, group, slab, e).transpose(0, 2, 1, 3, 4).reshape(heads, rows, e)
    return ops.einsum("hse,hed->sd", z, p["out"]["kernel"])


def core_probe(q, k, v, w, rows, block: int, rounding: str = "float32"):
    """The core alone on given operands, for the rows ``rows`` (n,) of one
    sequence's pair of copies: ``q`` (heads, 2 seq, e) already scaled, ``k``
    and ``v`` (key/value heads, 2 seq, e), ``w`` (heads, n, e) the weights of
    ``Σ w ⊙ o`` over those rows. Returns that sum's ``(o, dq)`` at the rows
    and ``(dk, dv)`` at every key, which only those rows reach: each row's
    mask from its copy and diffusion block literally (``visible``), a dense
    softmax, one key/value head's group of query heads at a time."""
    ops = Ops(rounding)
    (heads, both, e), kv_heads, n = q.shape, k.shape[0], rows.shape[0]
    see = visible(rows, both // 2, block)

    def one_group(args):
        def out(qq, kk, vv):
            probs = jax.nn.softmax(jnp.where(see, ops.einsum("hqe,ke->hqk", qq, kk), -jnp.inf),
                                   axis=-1)
            return ops.einsum("hqk,ke->hqe", probs, vv)

        *operands, ww = args
        o, pull = jax.vjp(out, *operands)
        return (o, *pull(ww))

    group = lambda a: a.reshape(kv_heads, heads // kv_heads, n, e)
    o, dq, dk, dv = jax.lax.map(one_group, (group(q[:, rows]), k, v, group(w)))
    return o.reshape(heads, n, e), dq.reshape(heads, n, e), dk, dv


def route(ops: Ops, u, p, c: dict):
    """``u`` (rows, hidden) -> (the chosen experts (rows, k), their weights)."""
    probs = jax.nn.softmax(ops.einsum("sd,de->se", u, p["router"]["kernel"]), axis=1)
    top, chosen = jax.lax.top_k(probs, c["num_experts_per_tok"])
    return chosen, top / top.sum(axis=1, keepdims=True)  # norm_topk_prob


def swiglu(ops: Ops, u, w):
    gate = ops.einsum("sd,dh->sh", u, w["gate"]["kernel"])
    up = ops.einsum("sd,dh->sh", u, w["up"]["kernel"])
    return ops.einsum("sh,hd->sd", jax.nn.silu(gate) * up, w["down"]["kernel"])


def expert_layer(ops: Ops, u, p, c: dict, first: int | None = None):
    """The layer's output on a chip that holds the experts ``first .. first +
    held`` (``p``'s stacked matrices): a dense sum over the held experts, each
    over every row, weighted by what the row's router gave it (zero where the
    row did not choose it). No shared expert."""
    first = c["experts_held"][0] if first is None else first
    chosen, weights = route(ops, u, p, c)

    def one_expert(total, xs):
        e, w = xs
        mine = jnp.where(chosen == first + e, weights, 0.0).sum(axis=1)
        return total + mine[:, None] * swiglu(ops, u, w), None

    stacked = {k: p[k] for k in ("gate", "up", "down")}
    held = stacked["gate"]["kernel"].shape[0]
    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(u),
                        (jnp.arange(held), stacked))
    return y


def block(ops: Ops, x, p, c: dict):
    eps = c["rms_norm_eps"]
    after = x + attention(ops, rms_norm(x, p["ln1"], eps), p["attn"], c)
    # decoder_sparse_step 1, mlp_only_layers []: every layer is sparse
    return after + expert_layer(ops, rms_norm(after, p["ln2"], eps), p["moe"], c)


def _trunk(params, ids, c: dict, rounding: str):
    """The last hidden states (2 seq, hidden) of the rows ``ids`` (2 seq,),
    and the ``Ops`` the head computes in."""
    low, _, only = rounding.partition("@")
    ops = Ops("float32" if only else low)  # the head's
    x = params["embedding"][ids]
    for i in range(c["num_hidden_layers"]):
        here = Ops(low) if only in ("", str(i)) else ops
        run = jax.checkpoint(lambda x, p, here=here: block(here, x, p, c))
        x = run(x, params[f"block_{i}"])
    return x, ops


def _two_copies(tokens, masked, c: dict):
    """``[x ; x_t]`` as rows of the embedding held."""
    first, rows = c["vocab_rows"]
    if c["mask_token_id"] != first + rows - 1:
        raise ValueError("the mask id is the last vocabulary row held")
    return jnp.concatenate([tokens, jnp.where(masked, c["mask_token_id"], tokens)]) - first


def sequence_logits(params, tokens, masked, c: dict, rounding: str = "float32"):
    """One sequence's logits at all 2 seq rows, the clean copy's first:
    ``tokens`` (seq,) clean ids from the vocabulary rows held, ``masked``
    (seq,) bool. For the tests; the loss below never holds them all."""
    x, ops = _trunk(params, _two_copies(tokens, masked, c), c, rounding)
    return head_logits(ops, params, x, c)


def sequence_loss(params, tokens, t, masked, c: dict, rounding: str = "float32"):
    """One sequence's ``Σ_masked (1 / t) · CE / seq``."""
    seq = tokens.shape[0]
    x, ops = _trunk(params, _two_copies(tokens, masked, c), c, rounding)
    noisy, targets = x[seq:], tokens - c["vocab_rows"][0]  # no shift (assumed)
    rows = _slab(seq)

    @jax.checkpoint
    def weighted_cross_entropy(args):  # summed over a slab of rows
        h, target, weight = args
        logits = head_logits(ops, params, h, c)
        hit = jnp.take_along_axis(logits, target[:, None], axis=1)[:, 0]
        return (weight * (jax.nn.logsumexp(logits, axis=1) - hit)).sum()

    cut = lambda a: a.reshape(seq // rows, rows, *a.shape[1:])
    parts = jax.lax.map(weighted_cross_entropy,
                        (cut(noisy), cut(targets), cut(masked / t)))
    return parts.sum() / seq


def batch_loss(params, tokens, key, c: dict, rounding: str = "float32"):
    """Mean over the sequences of ``tokens`` (batch, seq) clean ids, under
    the noise ``key`` draws for the whole batch, one sequence after another.
    The family has no router bias to move between steps, so the loss is all a
    step hands on."""
    t, masked = draw_noise(key, *tokens.shape, c)
    total, _ = jax.lax.scan(
        lambda total, row: (total + sequence_loss(params, *row, c, rounding), None),
        jnp.zeros(()), (tokens, t, masked))
    return total / tokens.shape[0]
