"""Decoder-only language model with multi-head latent attention (MLA),
sigmoid-routed sparse experts beside a shared expert, and one multi-token
prediction (MTP) module: the DeepSeek-V3 family's block, as
``JoyAI-LLM-Flash``'s ``config.json`` sizes it.

Pre-norm residual blocks with RMSNorm. No bias anywhere.

MLA: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> heads x (nope ‖ rope);
``[c_kv ‖ k_pe] = x W_kva``, ``[k_nope ‖ v] = RMSNorm(c_kv) W_kvb`` -> heads x
(nope ‖ v); ``k = [k_nope ‖ rope(k_pe)]`` with the one ``k_pe`` a token shared
by every head; RoPE on adjacent pairs; causal
``softmax(q kᵀ (nope + rope)^-½) v``; ``W_o`` over heads x v.

MLP: ``W_d(silu(W_g x) ⊙ W_u x)``.

Router, in float32: ``s = sigmoid(x W_r)``; the top ``k`` of ``s + b``;
weights ``factor · s_i / Σ_chosen s``. ``b`` is no parameter: it lives in the
``batch_stats`` collection and moves after each applied step by
``b += rate · sign(mean(c) − c)``, ``c`` the step's counts over all outputs.

**The chip's share.** ``experts_held = (e0, n)`` says which routed experts
this chip holds. The router keeps its full width and its ``k``; the weights
are normalised over all ``k`` chosen; the layer's output is
``shared(x) + Σ_{chosen i, e0 <= i < e0 + n} w_i E_i(x)`` — what the absent
experts would add is left out, and nothing stands in for them or for their
exchange. ``vocab_rows = (v0, n)`` likewise: embedding and head hold rows
``v0 .. v0 + n`` of the vocabulary, token ids come from that range, and
logits and loss are over the slice.

The expert layer sorts the (token, expert) pairs so that those on held
experts come first, expert by expert, and walks the held ones in rounds of a
fixed chunk of rows (``routed_experts``): a round gathers its rows, runs the
grouped matrix products over them (``ops/grouped_matmul.py``) and adds their
gate-weighted outputs to the tokens. The chunk is twice the share of the
pairs that uniform routing sends to the experts held (``chunk_rows``) and the
number of rounds follows the routing, so the buffers follow the rows this
chip holds, and no routing, however uneven, drops a token: it takes more
rounds.

MTP: ``h'_i = W_eh [RMSNorm(Emb(t_{i+1})) ‖ RMSNorm(h_i)]`` -> one block of
the expert kind -> the trunk's final norm and head, predicting ``t_{i+2}``.
Loss = CE(trunk) + ``mtp_loss_weight`` · CE(MTP), mean over tokens.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from jumbo_mae_tpu_tpu.models.config import AttnImpl, RematPolicy, maybe_remat
from jumbo_mae_tpu_tpu.models.layers import resolve_attn_impl
from jumbo_mae_tpu_tpu.obs.trace import (
    SCOPE_ATTN_CORE,
    SCOPE_ATTN_OUT,
    SCOPE_DENSE_MLP,
    SCOPE_EMBED,
    SCOPE_EXPERTS,
    SCOPE_LM_HEAD,
    SCOPE_MLA_LATENT,
    SCOPE_MOE_DISPATCH,
    SCOPE_MTP_MERGE,
    SCOPE_ROPE,
    SCOPE_ROUTER,
    SCOPE_SHARED_EXPERT,
)
from jumbo_mae_tpu_tpu.ops.flash_attention import causal_attention
from jumbo_mae_tpu_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul, grouped_outer

# the counters an expert layer reports, in the order of its stats vector
MOE_COUNTERS = ("rows_min", "rows_mean", "rows_max", "imbalance", "held_share", "dropped",
                "rounds")


@dataclass(frozen=True)
class MlaMoeConfig:
    """Sizes as ``config.json`` names them (``JoyAI-LLM-Flash`` defaults),
    plus what this chip holds of them."""

    vocab_size: int = 129280
    vocab_rows: tuple[int, int] | None = None  # (first row, rows held); None = all
    dim: int = 2048  # hidden_size
    layers: int = 40  # num_hidden_layers: trunk blocks, the dense ones first
    first_k_dense: int = 1  # first_k_dense_replace
    heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    dense_hidden: int = 7168  # intermediate_size
    expert_hidden: int = 768  # moe_intermediate_size
    n_routed_experts: int = 256
    experts_held: tuple[int, int] | None = None  # (first expert, experts held); None = all
    n_shared_experts: int = 1
    experts_per_token: int = 8
    routed_scaling_factor: float = 2.5
    router_bias_rate: float = 0.001  # assumed: the noaux_tc rule's step
    mtp_layers: int = 1  # num_nextn_predict_layers (0 or 1)
    mtp_loss_weight: float = 0.3  # assumed
    rope_theta: float = 32e6
    rms_eps: float = 1e-6
    init_std: float = 0.02  # assumed

    grad_ckpt: bool = True
    remat_policy: RematPolicy = "none"
    dtype: str = "bfloat16"
    attn_impl: AttnImpl = "auto"

    def __post_init__(self):
        for name in ("vocab_rows", "experts_held"):  # a recipe gives lists
            value = getattr(self, name)
            if value is not None:
                object.__setattr__(self, name, tuple(int(v) for v in value))
        if self.mtp_layers not in (0, 1):
            raise ValueError("mtp_layers must be 0 or 1")
        e0, n = self.held
        if not (0 <= e0 and n > 0 and e0 + n <= self.n_routed_experts):
            raise ValueError(f"experts_held {self.experts_held} outside the "
                             f"{self.n_routed_experts} routed experts")
        v0, rows = self.rows
        if not (0 <= v0 and rows > 0 and v0 + rows <= self.vocab_size):
            raise ValueError(f"vocab_rows {self.vocab_rows} outside the vocabulary")

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def rows(self) -> tuple[int, int]:
        return self.vocab_rows or (0, self.vocab_size)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def replace(self, **kw) -> "MlaMoeConfig":
        return dataclasses.replace(self, **kw)


def _normal(cfg: MlaMoeConfig):
    return nn.initializers.normal(cfg.init_std)


class RMSNorm(nn.Module):
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return (x * scale).astype(self.dtype)


class Proj(nn.Module):
    """A bias-free linear map, ``kernel`` of shape ``shape``, applied by the
    einsum ``spec`` (so that a projection can write the head-major layout
    the attention kernels read)."""

    shape: tuple[int, ...]
    spec: str
    cfg: MlaMoeConfig

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _normal(self.cfg), self.shape, jnp.float32)
        dtype = self.cfg.compute_dtype
        return jnp.einsum(self.spec, x.astype(dtype), kernel.astype(dtype))


def rope_interleaved(x, theta: float):
    """Rotary embedding on adjacent pairs (``rope_interleave``) of the last
    axis; positions run along the axis before it. Float32 inside."""
    seq, d = x.shape[-2:]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Module):
    cfg: MlaMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, dn, dr, dv = cfg.heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.compute_dtype, name=name)
        with jax.named_scope(SCOPE_MLA_LATENT):
            c_q = norm("q_norm")(Proj((cfg.dim, cfg.q_lora_rank), "bsd,dr->bsr", cfg, name="q_a")(x))
            q = Proj((cfg.q_lora_rank, h, dn + dr), "bsr,rhd->bhsd", cfg, name="q_b")(c_q)
            q = q * cfg.qk_head_dim**-0.5
            kv = Proj((cfg.dim, cfg.kv_lora_rank + dr), "bsd,dr->bsr", cfg, name="kv_a")(x)
            c_kv, k_pe = kv[..., : cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank:]
            kv = Proj((cfg.kv_lora_rank, h, dn + dv), "bsr,rhd->bhsd", cfg,
                      name="kv_b")(norm("kv_norm")(c_kv))
        with jax.named_scope(SCOPE_ROPE):
            q_pe = rope_interleaved(q[..., dn:], cfg.rope_theta)
            k_pe = rope_interleaved(k_pe, cfg.rope_theta)
        impl = resolve_attn_impl(cfg.attn_impl, backend=jax.default_backend(),
                                 seq_len=x.shape[1], dropout=0.0, deterministic=True)
        with jax.named_scope(SCOPE_ATTN_CORE):
            z = causal_attention(q[..., :dn], q_pe, kv[..., :dn], k_pe, kv[..., dn:], impl=impl)
        with jax.named_scope(SCOPE_ATTN_OUT):
            return Proj((h, dv, cfg.dim), "bhsd,hdm->bsm", cfg, name="out")(z)


class GatedMlp(nn.Module):
    hidden: int
    cfg: MlaMoeConfig

    @nn.compact
    def __call__(self, x):
        d = self.cfg.dim
        gate = Proj((d, self.hidden), "...d,dh->...h", self.cfg, name="gate")(x)
        up = Proj((d, self.hidden), "...d,dh->...h", self.cfg, name="up")(x)
        return Proj((self.hidden, d), "...h,hd->...d", self.cfg, name="down")(nn.silu(gate) * up)


def chunk_rows(pairs: int, held: int, experts: int) -> int:
    """Rows one round of the expert layer takes: twice the share of the
    ``pairs`` that uniform routing sends to ``held`` of ``experts``, in whole
    row tiles; all of them where that is fewer."""
    return min(pairs, -(-2 * pairs * held // (experts * ROW_TILE)) * ROW_TILE)


def _round_rows(r, chunk, row_to_pair, pair_to_row, gate, group_sizes):
    """Round ``r`` takes the sorted rows ``r chunk .. (r + 1) chunk``. From
    the rows' side: each row's token and gate weight, and the experts' sizes
    clipped to the round. From the pairs' side (tokens, slots): the pair's
    row within the round, and whether it lies there at all."""
    lo = r * chunk
    pair = row_to_pair.at[lo + jnp.arange(chunk, dtype=jnp.int32)].get(mode="clip")
    ends = jnp.cumsum(group_sizes)
    sizes = jnp.clip(ends, lo, lo + chunk) - jnp.clip(ends - group_sizes, lo, lo + chunk)
    local = pair_to_row - lo
    mine = (local >= 0) & (local < chunk)
    return (pair // gate.shape[1], gate.reshape(-1)[pair], sizes,
            jnp.clip(local, 0, chunk - 1), mine)


def _sum_slots(rows, row_of_pair, weight):
    """Each token's sum over its slots of ``weight · rows[row_of_pair]``, in
    float32: (tokens, width). One gather a slot, each of the tokens' height:
    nothing a row wide is built for the pairs, and nothing is scattered."""
    return sum(rows[row_of_pair[:, slot]].astype(jnp.float32) * weight[:, slot, None]
               for slot in range(row_of_pair.shape[1]))


def _swiglu(gu):
    hidden = gu.shape[1] // 2
    return nn.silu(gu[:, :hidden]) * gu[:, hidden:]


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def routed_experts(x, w_gu, w_down, gate, row_to_pair, pair_to_row, group_sizes, rounds,
                   chunk, impl="auto", interpret=False):
    """``Σ_slots gate · E(x)`` over the pairs on held experts: ``x`` (tokens,
    dim), the held experts' stacked matrices (gate ‖ up, and down), ``gate``
    (tokens, slots) float32 and zero for a pair held elsewhere, the sort's
    two permutations (sorted row -> pair; (tokens, slots) -> sorted row), the
    held experts' ``group_sizes`` and ``rounds = ceil(sum(group_sizes) /
    chunk)`` -> (tokens, dim) in ``x``'s dtype, the slots summed in float32.

    Forward and backward walk the sorted rows ``chunk`` at a time, ``rounds``
    times: a loop whose trip count follows the routing. The backward pass
    keeps the arguments and nothing sized by the routing: it gathers each
    round's rows again, recomputes their gate and up products, and sums the
    matrices' gradients over the rounds in the matrices' dtype."""
    return _routed_fwd(x, w_gu, w_down, gate, row_to_pair, pair_to_row, group_sizes, rounds,
                       chunk, impl, interpret)[0]


def _routed_fwd(x, w_gu, w_down, gate, row_to_pair, pair_to_row, group_sizes, rounds,
                chunk, impl, interpret):
    product = functools.partial(grouped_matmul, impl=impl, interpret=interpret)

    def one_round(r, y):
        token, _, sizes, row_of_pair, mine = _round_rows(
            r, chunk, row_to_pair, pair_to_row, gate, group_sizes)
        rows = x[token]
        with jax.named_scope(SCOPE_EXPERTS):
            out = product(_swiglu(product(rows, w_gu, sizes)), w_down, sizes)
        return y + _sum_slots(out, row_of_pair, jnp.where(mine, gate, 0.0))

    with jax.named_scope(SCOPE_MOE_DISPATCH):
        y = jax.lax.fori_loop(0, rounds, one_round, jnp.zeros(x.shape, jnp.float32))
        y = y.astype(x.dtype)
    return y, (x, w_gu, w_down, gate, row_to_pair, pair_to_row, group_sizes, rounds)


def _routed_bwd(chunk, impl, interpret, residuals, dy):
    x, w_gu, w_down, gate, row_to_pair, pair_to_row, group_sizes, rounds = residuals
    product = functools.partial(grouped_matmul, impl=impl, interpret=interpret)
    outer = functools.partial(grouped_outer, impl=impl, interpret=interpret)

    def one_round(r, carry):
        d_x, d_gate, d_w_gu, d_w_down = carry
        token, gate_of_row, sizes, row_of_pair, mine = _round_rows(
            r, chunk, row_to_pair, pair_to_row, gate, group_sizes)
        rows, d_out = x[token], dy[token]
        with jax.named_scope(SCOPE_EXPERTS):
            act, swiglu_vjp = jax.vjp(_swiglu, product(rows, w_gu, sizes))
            # out = gate · (act W_down), so the gate's gradient is
            # act · (d_out W_downᵀ) and the activation's is gate times it
            d_act = product(d_out, w_down, sizes, transpose_rhs=True)
            d_gate_of_row = (act.astype(jnp.float32) * d_act.astype(jnp.float32)).sum(axis=1)
            scale = gate_of_row[:, None].astype(act.dtype)
            d_w_down = outer(act * scale, d_out, sizes, d_w_down)
            (d_gu,) = swiglu_vjp(d_act * scale)
            d_rows = product(d_gu, w_gu, sizes, transpose_rhs=True)
            d_w_gu = outer(rows, d_gu, sizes, d_w_gu)
        d_x = d_x + _sum_slots(d_rows, row_of_pair, mine.astype(jnp.float32))
        d_gate = d_gate + jnp.where(mine, d_gate_of_row[row_of_pair], 0.0)
        return d_x, d_gate, d_w_gu, d_w_down

    with jax.named_scope(SCOPE_MOE_DISPATCH):
        d_x, d_gate, d_w_gu, d_w_down = jax.lax.fori_loop(
            0, rounds, one_round,
            (jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(gate),
             jnp.zeros_like(w_gu), jnp.zeros_like(w_down)))
    return d_x.astype(x.dtype), d_w_gu, d_w_down, d_gate, None, None, None, None


routed_experts.defvjp(_routed_fwd, _routed_bwd)


class SparseExperts(nn.Module):
    """Router over all experts, grouped products over those held here, and
    the shared expert. Returns ``(y, stats)``, ``stats`` in ``MOE_COUNTERS``
    order."""

    cfg: MlaMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        n, k, e = b * s, cfg.experts_per_token, cfg.n_routed_experts
        e0, held = cfg.held
        flat = x.reshape(n, d)

        def kernel(name, *shape):  # a leaf <name>/kernel, as a Proj's
            make = lambda key: {"kernel": _normal(cfg)(key, shape, jnp.float32)}
            return self.param(name, make)["kernel"]

        with jax.named_scope(SCOPE_ROUTER):
            w_r = kernel("router", d, e)
            scores = jax.nn.sigmoid(jnp.dot(flat.astype(jnp.float32), w_r,
                                            precision=jax.lax.Precision.HIGHEST))
            bias = self.variable(
                "batch_stats", "router_bias",
                lambda: 0.01 * jax.random.normal(self.make_rng("params"), (e,), jnp.float32))
            _, chosen = jax.lax.top_k(scores + bias.value, k)  # (n, k)
            picked = jnp.take_along_axis(scores, chosen, axis=1)
            weights = cfg.routed_scaling_factor * picked / picked.sum(axis=1, keepdims=True)
            counts = (chosen[..., None] == jnp.arange(e)).sum(axis=(0, 1)).astype(jnp.float32)
            if not self.is_initializing() and self.is_mutable_collection("batch_stats"):
                bias.value = bias.value + cfg.router_bias_rate * jnp.sign(counts.mean() - counts)
        with jax.named_scope(SCOPE_MOE_DISPATCH):
            local = chosen - e0
            here = (local >= 0) & (local < held)
            key = jnp.where(here, local, held).reshape(n * k)  # pairs elsewhere sort last
            row_to_pair = jnp.argsort(key, stable=True).astype(jnp.int32)
            pair_to_row = jnp.argsort(row_to_pair).astype(jnp.int32).reshape(n, k)
            group_sizes = (key[:, None] == jnp.arange(held)).sum(axis=0).astype(jnp.int32)
            total = group_sizes.sum()
            chunk = chunk_rows(n * k, held, e)
            rounds = (total + chunk - 1) // chunk
            gate = jnp.where(here, weights, 0.0)
        with jax.named_scope(SCOPE_EXPERTS):
            # one matrix per held expert, stacked
            stacked = lambda name, *shape: kernel(name, held, *shape).astype(cfg.compute_dtype)
            w_gu = jnp.concatenate([stacked("gate", d, cfg.expert_hidden),
                                    stacked("up", d, cfg.expert_hidden)], axis=-1)
            w_down = stacked("down", cfg.expert_hidden, d)
        routed = routed_experts(flat, w_gu, w_down, gate, row_to_pair, pair_to_row,
                                group_sizes, rounds, chunk)
        with jax.named_scope(SCOPE_ROUTER):
            per_expert = group_sizes.astype(jnp.float32)
            mean = per_expert.mean()
            stats = jnp.stack([
                per_expert.min(), mean, per_expert.max(),
                per_expert.max() / jnp.maximum(mean, 1.0),
                total / (n * k),
                # the rounds take ``chunk`` rows each: what they did not reach
                (total - jnp.minimum(total, rounds * chunk)).astype(jnp.float32),
                rounds.astype(jnp.float32),
            ])
        with jax.named_scope(SCOPE_SHARED_EXPERT):
            shared = GatedMlp(cfg.n_shared_experts * cfg.expert_hidden, cfg, name="shared")(x)
        return shared + routed.reshape(b, s, d), jax.lax.stop_gradient(stats)


class Block(nn.Module):
    """One pre-norm residual block: latent attention, then the dense MLP
    (``sparse=False``) or the expert layer. Returns ``(x, stats)``."""

    cfg: MlaMoeConfig
    sparse: bool

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        del deterministic  # no dropout; the argument keeps maybe_remat's signature
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.compute_dtype, name=name)
        x = x + LatentAttention(cfg, name="attn")(norm("ln1")(x))
        if self.sparse:
            y, stats = SparseExperts(cfg, name="moe")(norm("ln2")(x))
        else:
            with jax.named_scope(SCOPE_DENSE_MLP):
                y = GatedMlp(cfg.dense_hidden, cfg, name="mlp")(norm("ln2")(x))
            stats = jnp.zeros((len(MOE_COUNTERS),), jnp.float32)
        return x + y, stats


class MlaMoeLM(nn.Module):
    """``__call__(tokens)`` with ``tokens`` (batch, seq + 1 + mtp_layers)
    int32 ids from the vocabulary rows held: the training loss and the
    step's counters. ``logits(tokens)`` returns both heads' logits."""

    cfg: MlaMoeConfig

    def setup(self):
        cfg = self.cfg
        block = maybe_remat(Block, cfg)
        self.embedding = self.param("embedding", _normal(cfg), (cfg.rows[1], cfg.dim),
                                    jnp.float32)
        self.blocks = [block(cfg, sparse=i >= cfg.first_k_dense, name=f"block_{i}")
                       for i in range(cfg.layers)]
        self.ln = RMSNorm(cfg.rms_eps, cfg.compute_dtype, name="ln")
        self.head = Proj((cfg.dim, cfg.rows[1]), "bsd,dv->bsv", cfg, name="head")
        if cfg.mtp_layers:
            self.mtp_embed_norm = RMSNorm(cfg.rms_eps, cfg.compute_dtype, name="mtp_embed_norm")
            self.mtp_hidden_norm = RMSNorm(cfg.rms_eps, cfg.compute_dtype, name="mtp_hidden_norm")
            self.mtp_merge = Proj((2 * cfg.dim, cfg.dim), "bsd,dm->bsm", cfg, name="mtp_merge")
            self.mtp_block = block(cfg, sparse=True, name="mtp_block")

    def _embed(self, ids):
        with jax.named_scope(SCOPE_EMBED):
            return self.embedding[ids].astype(self.cfg.compute_dtype)

    def _hidden(self, tokens, deterministic: bool):
        """Both heads' last hidden states ``[trunk, mtp?]`` and the expert
        layers' stats ``{name: vector}``."""
        cfg = self.cfg
        seq = tokens.shape[1] - 1 - cfg.mtp_layers
        ids = tokens - cfg.rows[0]
        x = self._embed(ids[:, :seq])
        stats = {}
        for i, blk in enumerate(self.blocks):
            x, st = blk(x, deterministic)
            if i >= cfg.first_k_dense:
                stats[f"l{i}"] = st
        hidden = [x]
        if cfg.mtp_layers:
            with jax.named_scope(SCOPE_MTP_MERGE):
                nxt = self.mtp_embed_norm(self._embed(ids[:, 1 : seq + 1]))
                merged = self.mtp_merge(jnp.concatenate([nxt, self.mtp_hidden_norm(x)], axis=-1))
            y, stats["mtp"] = self.mtp_block(merged, deterministic)
            hidden.append(y)
        return hidden, stats

    def _logits(self, h):
        return self.head(self.ln(h)).astype(jnp.float32)

    def logits(self, tokens, deterministic: bool = True):
        with jax.named_scope(SCOPE_LM_HEAD):
            return [self._logits(h) for h in self._hidden(tokens, deterministic)[0]]

    def __call__(self, tokens, deterministic: bool = True):
        cfg = self.cfg
        seq = tokens.shape[1] - 1 - cfg.mtp_layers
        hidden, stats = self._hidden(tokens, deterministic)
        ids = tokens - cfg.rows[0]

        def cross_entropy(mdl, h, targets):
            logits = mdl._logits(h)
            lse = jax.nn.logsumexp(logits, axis=-1)
            hit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
            return (lse - hit).mean(axis=-1)  # per sequence

        # the logits are the step's largest arrays: recompute them in the
        # backward pass rather than keep two (tokens, rows) float32 arrays
        if cfg.grad_ckpt:
            cross_entropy = nn.remat(cross_entropy)
        with jax.named_scope(SCOPE_LM_HEAD):
            losses = [cross_entropy(self, h, ids[:, 1 + i : seq + 1 + i])
                      for i, h in enumerate(hidden)]
        per_sample = losses[0]
        out = {"loss_trunk": losses[0].mean()}
        if cfg.mtp_layers:
            per_sample = per_sample + cfg.mtp_loss_weight * losses[1]
            out["loss_mtp"] = losses[1].mean()
        out |= {"loss": per_sample.mean(), "loss_per_sample": per_sample}
        table = jnp.stack(list(stats.values()))  # (expert layers, counters)
        for name, st in stats.items():
            out |= {f"moe_{c}_{name}": st[j] for j, c in enumerate(MOE_COUNTERS)}
        col = {c: table[:, j] for j, c in enumerate(MOE_COUNTERS)}
        out |= {"moe_imbalance": col["imbalance"].max(), "moe_held_share": col["held_share"].mean(),
                "moe_dropped": col["dropped"].sum(), "moe_rounds": col["rounds"].max()}
        return out
