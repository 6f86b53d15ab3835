"""Decoder-only sparse-expert language models, seven families from one set of
blocks; each trunk block's token mixer is one of five kinds — four of
attention and the gated short convolution — and ``MlaMoeConfig.kinds`` is the
one list that says which (a family is a way to fill it). **All-MLA** (the
DeepSeek-V3 family's block, as ``JoyAI-LLM-Flash``'s
``config.json`` sizes it): multi-head latent attention in every block,
sigmoid-routed experts beside a shared expert, one multi-token prediction
(MTP) module. **Hybrid** (``Ling-3.0-flash``, ``model_type: bailing_hybrid``):
with ``layer_group_size = p > 0`` block ``i`` is MLA when ``(i + 1) % p == 0``
and Kimi delta attention (KDA, a linear attention) otherwise; MLA has no
query latent, both kinds end in a head-wise gate, and the router's choice is
limited to a token's best groups of experts. **Grouped-query**
(``Laguna-XS.2``, ``model_type: laguna``): with ``layer_types`` given, block
``i`` is grouped-query softmax attention of the kind ``layer_types[i]`` —
``full_attention`` or ``sliding_attention`` — with
``heads_per_layer[i]`` query heads over ``kv_heads`` key/value heads, its own
rotary embedding a kind, and a head-wise gate; no latent, no MTP module.
**Linear beside grouped-query** (``Solar-Open2-250B``, ``model_type:
solar_open2``): ``layer_types`` may name ``"kda"`` (and ``"mla"``) beside the
grouped-query kinds — here one rope-free ``full_attention`` block to three
KDA blocks whose gates are the other variants below. **Window beside
rope-free full, routed from the block's input**
(``SmallThinker-21BA3B-Instruct``, ``model_name:
smallthinker_21b_instruct``): grouped-query blocks again, one rope-free
``full_attention`` block to three ``sliding_attention`` blocks with rope, no
gate on the attention output, and an expert layer of the other variants
below (``router_input``, ``router_scoring``, ``expert_act``, no shared
expert, no dense layer). **Short convolution beside grouped-query**
(``LFM2-24B-A2B``, ``model_type: lfm2_moe``): ``layer_types`` names
``"conv"`` three layers in four — a mixer that is neither attention nor a
recurrence with a matrix state, with no heads, no rope and no score — and
``full_attention`` the fourth, with 64-wide heads and a per-head RMSNorm on
``q`` and ``k`` (``qk_norm``); sigmoid-and-bias routing, no shared expert,
and a head that is the embedding's rows (``tie_embeddings``). **Block
diffusion** (``SDAR-30B-A3B-Chat``, ``model_type: sdar_moe``): a grouped-query
trunk of ``full_attention`` blocks with ``qk_norm`` and softmax-routed experts,
trained with ``diffusion_block`` = ``B`` > 0 not on the next token but as a
block-diffusion model (below). The
defaults are the first family's; its parameter tree, scopes and program do
not depend on the others' fields.

**Block diffusion** (BD3-LMs' vectorised training, as SDAR uses it). A
sequence ``x`` of ``L`` tokens lies in blocks of ``B``; a step draws ``t ~
U[eps, 1]`` a (sequence, block) (``eps`` is ``ops/masking.BLOCK_NOISE_EPS``, a
constant of the objective and no option) and masks each token of the
block independently with probability ``t``: its id becomes the mask id, the
last vocabulary row held, which no document holds (``ops/masking.block_noise``,
from the step's ``noise`` stream; evaluation draws from a fixed key). The
trunk runs once over ``[x ; x_t]``, ``2 L`` rows a sequence, the clean copy
first: every token-wise part (embedding, norms, projections, router, experts)
sees ``2 L`` rows; the rope sees positions ``0 .. L − 1`` twice (the head-major
(batch, heads, 2 L, e) tensor read as (batch, 2 · heads, L, e): a reshape of
the same bytes); the core sees the pair under the block-diffusion pattern
(``ops/attention.block_diffusion_visible``): with ``b(i) = i // B``, a clean
query sees the clean keys of ``b(j) <= b(i)``, a noisy query the clean keys of
``b(j) < b(i)`` and the noisy keys of its own block, and a clean query never
a noisy key. The head reads the noisy copy only, and the logits at a position
predict that position's own token (no shift): loss ``= 1 / (batch · L) ·
Σ_{masked i} (1 / t_{b(i)}) · (−log softmax(head(h_i))[x_i])``, through
``ops/head_loss.head_loss`` with the weights ``masked / (t · batch · L)``. A
batch row holds the ``L`` clean ids and nothing else.

Pre-norm residual blocks with RMSNorm (eps ``rms_eps``): ``x += A_i(norm(x))``,
``x += F_i(norm(x))``. No bias anywhere.

The LFM2 family whole (``d = dim``, every projection without bias, RMSNorm
with ``rms_eps`` = the source's ``norm_eps``). Block ``i``: ``h = x +
mixer_i(RMSNorm_op(x))``, ``out = h + ffn_i(RMSNorm_ffn(h))`` (``ln1``,
``ln2``); ``ffn_i`` is the dense SwiGLU of ``dense_hidden`` for ``i <
first_k_dense`` (``num_dense_layers``), the expert layer after. After the
last block one RMSNorm (``ln``; the source's ``embedding_norm``), then the
head. **``conv`` mixer** (``ShortConv``): ``z = u W_in`` with ``W_in`` (d,
3d); ``B, C, x̃`` = the three d-wide thirds of ``z`` in that order; ``c_t =
Σ_{j=0..K−1} w_j ⊙ (B ⊙ x̃)_{t−K+1+j}`` with zero history before the row's
first position (one document a sequence), ``w`` (K, d) one filter a channel,
``K = conv_taps`` (the source's ``conv_L_cache``, 3), no activation; ``y =
(C ⊙ c) W_out``, ``W_out`` (d, d). The filter is ``ops/kda.causal_conv`` with
no activation: float32 inside, its gradient written out from ``B ⊙ x̃`` and
``w`` alone. **``full_attention`` mixer**: the grouped-query attention below
with ``qk_norm``: ``q ← RMSNorm_e(q)``, ``k ← RMSNorm_e(k)`` per head over
``e = head_dim``, each with its own learnt scale of ``e``, before the rope
and before the ``e^-½``; rotate-half rope on all ``e`` dimensions; no gate.
**Expert layer**: the ``sigmoid_bias`` rule below, ``n_group`` 1, weights
``factor · s_i / Σ_chosen s`` (the source divides by ``Σ_chosen s + 1e-6``:
the sum of four sigmoids is above 1e-2 wherever float32 can tell, and the
code here adds nothing), no shared expert. **Head**: logits ``=
RMSNorm(h) Eᵀ`` over the rows of the embedding ``E`` held: with
``tie_embeddings`` there is no ``head`` parameter, and ``E``'s gradient is
the lookup's scatter plus the head's ``dW`` transposed.

MLA: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> heads x (nope ‖ rope) — or
``q = x W_q`` where ``q_lora_rank`` is None; ``[c_kv ‖ k_pe] = x W_kva``,
``[k_nope ‖ v] = RMSNorm(c_kv) W_kvb`` -> heads x (nope ‖ v); ``k = [k_nope ‖
rope(k_pe)]`` with the one ``k_pe`` a token shared by every head; RoPE on
adjacent pairs; causal ``z = softmax(q kᵀ (nope + rope)^-½) v``; with
``attn_gate``, ``z_h ← sigmoid(x W_γ)_h · z_h``; ``W_o`` over heads x v.

Grouped-query attention, block ``i`` with ``H = heads_per_layer[i]``, ``G =
kv_heads``, ``d = head_dim``: ``q = x W_q`` -> (H, d), ``k = x W_k``, ``v = x
W_v`` -> (G, d); query head ``h`` reads key/value head ``h // (H / G)``. No
q/k norm unless ``qk_norm`` (then a per-head RMSNorm of ``q`` and of ``k``,
a learnt scale of ``d`` each, before the rope). A kind whose
``rope_parameters`` entry is None has no rotary
embedding: ``q`` and ``k`` go to the core as projected. Otherwise, rotary
embedding with the **rotate-half pairing** (dimension ``j``
with ``j + r/2``) on the first ``r = partial_rotary_factor · d`` dimensions
of ``q`` and ``k``, the rest passed through, by the kind's
``rope_parameters``: ``rope_type: default`` turns pair ``j`` by ``position ·
θ^(−2j/r)``; ``yarn`` blends each frequency ``f_j = θ^(−2j/r)`` with ``f_j /
factor`` by ``γ_j = clip((j − low) / (high − low), 0, 1)``, ``low = ⌊r
ln(L₀ / (2π β_fast)) / (2 ln θ)⌋``, ``high = ⌈r ln(L₀ / (2π β_slow)) / (2 ln
θ)⌉`` clipped to ``[0, r − 1]``, ``L₀ = original_max_position_embeddings``:
``inv_freq_j = (1 − γ_j) f_j + γ_j f_j / factor``, and multiplies ``cos`` and
``sin`` by ``attention_factor``. In float32, rounded once to the compute
dtype (``rope_half``): on the TPU, where ``q`` and ``k`` are head-major with
``d`` a whole number of 128-lane tiles (or the half tile of a 64-wide head)
and a sequence that cuts into blocks
of 16 rows, one Pallas pass that reads a block, turns it in VMEM and writes
it, and is its own transpose with the sines negated
(``ops/pallas/rope.py``); anywhere else the same formula in ``jax.numpy``.
``s = q kᵀ d^-½``; key ``j`` is visible to
query ``i`` iff ``j <= i`` and, in a ``sliding_attention`` layer, ``i − j <
sliding_window``; ``z = softmax(s) v``; ``z_h ← sigmoid(x W_γ)_h · z_h``;
``W_o`` over heads x d. The core is ``ops/attention.causal_attention``,
the latent family's, with one score part, grouped heads and a window.

KDA, per head ``h`` of ``kda_heads`` (``heads`` where that is None; the field
stands for the source's own ``linear_attn_config.num_heads`` key, and no
configuration or recipe here sets the two apart: only the test cuts do) with
``d_k = d_v = kda_head_dim`` (as many key and value heads as query heads): ``q̃, k̃, ṽ = x W_q, x W_k, x W_v``; each through a
causal depthwise convolution of ``kda_conv`` taps, one filter a channel, zero
history before the sequence (one document a sequence), then SiLU:
``u_t = silu(Σ_j c_j ⊙ ũ_{t−K+1+j})``; ``q̂ = q / ‖q‖₂ · d_k^-½``, ``k̂ = k /
‖k‖₂`` (eps 1e-6; no norm of ``v``; filter, SiLU and norm are one function,
``ops/kda.short_conv``, one Pallas pass a tensor on the TPU); the per-channel log-decay in its safe
form, ``g_t = lower_bound · sigmoid(exp(A_log_h) · (x W_f + dt_bias))`` with
one ``A_log`` a head and one ``dt_bias`` a channel, so ``g`` lies in
``(lower_bound, 0)`` (``kda_gate: "safe"``), or with no floor ``g_t =
−exp(A_log_h) · softplus(x W_f + dt_bias)`` (``"softplus"``);
``α_t = exp(g_t)``; ``β_t = kda_beta_scale · sigmoid(x W_b)``, one a head: at
scale 2 the transition's eigenvalue along ``k̂_t``, ``1 − β_t``, lies in (−1,
1). With ``kda_gate_rank = r`` the decay gate's ``W_f`` and the output
gate's ``W_γ`` are two factors each, ``d → r → heads · d_k`` (the first is
what every chip that shares the layer's heads computes alike). The state
``S`` (d_k, d_v) starts at zero:

    S_t = (I − β_t k̂_t k̂_tᵀ) Diag(α_t) S_{t−1} + β_t k̂_t v_tᵀ,   o_t = S_tᵀ q̂_t

computed in chunks of ``kda_chunk`` positions (``ops/kda.py``: the
triangular form inside a chunk, a scan that carries ``S`` between chunks,
``g`` and ``S`` in float32; the scan is told the gate's floor, or that it has
none, and builds its decay ratios accordingly). ``y = concat_h(sigmoid(x
W_γ)_h · RMSNorm(o_h)) W_o``, the norm over ``d_v`` with one learned scale
shared by the heads, the gate one logit a head (``kda_out_gate: "head"``) or
one a value channel (``"element"``). A layer reports the largest ``|S|`` at
the end of the sequences, the mean ``α``, the largest ``β`` and the share of
(token, head) with ``β > 1`` (``KDA_COUNTERS``).

MLP: ``W_d(silu(W_g x) ⊙ W_u x)``; a routed expert's gate is a ReLU where
``expert_act`` is ``"relu"`` (ReGLU: ``W_d(relu(W_g x) ⊙ W_u x)``), and the
layer then reports the share of its held rows' ``relu(W_g x) ⊙ W_u x``
entries that are exactly zero (``ACT_ZERO_COUNTER``). The clamped SwiGLU (a
non-zero ``*_swiglu_limit``) is not implemented and is refused.

Router, in float32, of the expert layer's input ``x`` — the block's
post-attention norm — or, with ``router_input: "block_input"``, of the
block's input as it arrives, before the input norm and before attention
(the experts still read the post-attention norm: the layer has two inputs,
and the routing does not wait for the attention sublayer). With
``router_scoring: "softmax_topk"``: ``ℓ = x W_r``, the top ``k`` by ``ℓ``,
weights ``factor · softmax(ℓ over the k chosen)``, no bias and no
``batch_stats`` variable. Else (``"sigmoid_bias"``): ``s = sigmoid(x W_r)``,
``t = s + b``; with ``n_group > 1`` the experts lie in ``n_group`` equal groups, a group's score is the sum
of its two largest ``t``, and only the best ``topk_group`` groups' experts
stay eligible; the top ``k`` eligible by ``t``; weights ``factor · s_i /
Σ_chosen s``. ``b`` is no parameter: it lives in the ``batch_stats``
collection and moves after each applied step by ``b += rate · sign(mean(c) −
c)``, ``c`` the step's counts over all outputs.

**The chip's share.** ``experts_held = (e0, n)`` says which routed experts
this chip holds. The router keeps its full width, its groups and its ``k``;
the weights are normalised over all ``k`` chosen; the layer's output is
``shared(x) + Σ_{chosen i, e0 <= i < e0 + n} w_i E_i(x)`` (no ``shared`` term
and no such module where ``n_shared_experts`` is 0) — what the absent
experts would add is left out, and nothing stands in for them or for their
exchange. ``vocab_rows = (v0, n)`` likewise: embedding and head hold rows
``v0 .. v0 + n`` of the vocabulary, token ids come from that range, and
logits and loss are over the slice. **Heads** likewise: ``heads``,
``kda_heads``, ``heads_per_layer`` and ``kv_heads`` count what this chip
holds of a layer whose heads are divided, ``heads_published`` states the
model's own counts a kind (``attn_heads``), and ``W_o`` holds the held
heads' rows: the block adds the partial attention output, and nothing stands
in for the other chips' parts or their all-reduce.

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
the MLA kind with experts -> the trunk's final norm and head, predicting
``t_{i+2}``. Loss = CE(trunk) + ``mtp_loss_weight`` · CE(MTP), mean over
tokens.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from jumbo_mae_tpu_tpu.models.config import RematPolicy, maybe_remat
from jumbo_mae_tpu_tpu.obs.trace import (
    SCOPE_ATTN_CORE,
    SCOPE_ATTN_OUT,
    SCOPE_BD_CORE,
    SCOPE_BD_NOISE,
    SCOPE_DENSE_MLP,
    SCOPE_EMBED,
    SCOPE_EXPERTS,
    SCOPE_GQA_PROJ,
    SCOPE_KDA_CONV,
    SCOPE_KDA_CORE,
    SCOPE_KDA_GATE,
    SCOPE_KDA_OUT,
    SCOPE_KDA_PROJ,
    SCOPE_LM_HEAD,
    SCOPE_MLA_LATENT,
    SCOPE_MOE_DISPATCH,
    SCOPE_MTP_MERGE,
    SCOPE_ROPE,
    SCOPE_ROUTER,
    SCOPE_SCONV_IN,
    SCOPE_SCONV_MIX,
    SCOPE_SCONV_OUT,
    SCOPE_SHARED_EXPERT,
    SCOPE_SWA_CORE,
)
from jumbo_mae_tpu_tpu.ops.attention import causal_attention
from jumbo_mae_tpu_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul, grouped_outer
from jumbo_mae_tpu_tpu.ops.head_loss import head_loss
from jumbo_mae_tpu_tpu.ops.kda import causal_conv, kda_chunked, short_conv
from jumbo_mae_tpu_tpu.ops.masking import block_noise

# the counters an expert layer reports, in the order of its stats vector
MOE_COUNTERS = ("rows_min", "rows_mean", "rows_max", "imbalance", "held_share", "dropped",
                "rounds")
# one more where the experts' gate is a ReLU (``MlaMoeConfig.moe_counters``): the
# share of the held rows' ``relu(W_g x) ⊙ W_u x`` entries that are exactly zero
ACT_ZERO_COUNTER = "act_zero_share"
# the counters a linear-attention layer reports, in the order of its stats vector
KDA_COUNTERS = ("state_absmax", "decay_mean", "beta_max", "neg_eig_share")


GQA_KINDS = ("full_attention", "sliding_attention")
ATTENTION_KINDS = ("kda", "mla", *GQA_KINDS)
# a block's token mixer: one of the attention kinds, or the gated short
# convolution, which has no heads, no rope and no (query, key) pairs
MIXER_KINDS = (*ATTENTION_KINDS, "conv")


@dataclass(frozen=True)
class Rope:
    """One attention kind's rotary embedding, under ``rope_parameters``'
    own keys (module docstring)."""

    rope_theta: float
    rope_type: str = "default"  # or "yarn"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type {self.rope_type!r}: only default and yarn are implemented")

    def inv_freq(self, r: int) -> np.ndarray:
        """The ``r / 2`` pair frequencies, float64: exact in the configuration's
        numbers, so that every float32 program rounds the same constants."""
        j = np.arange(r // 2, dtype=np.float64)
        f = self.rope_theta ** (-2.0 * j / r)
        if self.rope_type == "default":
            return f
        turn = lambda beta: r * math.log(self.original_max_position_embeddings
                                         / (2 * math.pi * beta)) / (2 * math.log(self.rope_theta))
        low = min(max(math.floor(turn(self.beta_fast)), 0), r - 1)
        high = min(max(math.ceil(turn(self.beta_slow)), 0), r - 1)
        blend = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
        return (1.0 - blend) * f + blend * f / self.factor


@dataclass(frozen=True)
class MlaMoeConfig:
    """Sizes as ``config.json`` names them (``JoyAI-LLM-Flash`` defaults: the
    all-MLA family), plus what this chip holds of them; the hybrid family's
    fields below ``init_std``, the grouped-query family's below those, then
    the expert layer's variants (the fifth family's, SmallThinker's), the
    short-convolution family's and, last, the block-diffusion family's
    (``SDAR``: the seventh, a training objective over a grouped-query trunk).
    The name is the first family's: the class holds all seven (a rename would
    touch every recipe's reader and test for no behaviour)."""

    vocab_size: int = 129280
    vocab_rows: tuple[int, int] | None = None  # (first row, rows held); None = all
    dim: int = 2048  # hidden_size
    layers: int = 40  # num_hidden_layers: trunk blocks, the dense ones first
    first_k_dense: int = 1  # first_k_dense_replace
    heads: int = 32
    q_lora_rank: int | None = 1536  # None: q = x W_q, no query latent
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    dense_hidden: int = 7168  # intermediate_size
    expert_hidden: int = 768  # moe_intermediate_size
    n_routed_experts: int = 256
    experts_held: tuple[int, int] | None = None  # (first expert, experts held); None = all
    n_shared_experts: int = 1
    shared_expert_hidden: int | None = None  # moe_shared_expert_intermediate_size; None = expert_hidden
    experts_per_token: int = 8
    n_group: int = 1  # the experts lie in n_group groups, of which a token's
    topk_group: int = 1  # best topk_group stay eligible (n_group 1: no limit)
    routed_scaling_factor: float = 2.5
    router_bias_rate: float = 0.001  # assumed: the noaux_tc rule's step
    mtp_layers: int = 1  # num_nextn_predict_layers (0 or 1)
    mtp_loss_weight: float = 0.3  # assumed
    rope_theta: float = 32e6
    rms_eps: float = 1e-6
    init_std: float = 0.02  # assumed
    embed_init_std: float | None = None  # the token embedding's; None = init_std
    # the hybrid family: block i is of the MLA kind when (i + 1) is a multiple
    # of layer_group_size and of the KDA kind otherwise; 0 = every block MLA
    layer_group_size: int = 0
    kda_head_dim: int = 128  # head_dim: d_k = d_v of a linear-attention head
    kda_conv: int = 4  # short_conv_kernel_size
    kda_lower_bound: float = -5.0  # the safe gate's floor of the log-decay
    kda_chunk: int = 64  # positions a chunk of the scan (ops/kda.py)
    kda_heads: int | None = None  # linear-attention heads held; None = heads
    kda_gate: str = "safe"  # or "softplus": g = −exp(A_log) · softplus(·), no floor
    kda_beta_scale: float = 1.0  # β = scale · sigmoid(x W_b); 2 = kda_allow_neg_eigval
    kda_gate_rank: int | None = None  # W_f and W_γ through this rank; None = one matrix each
    kda_out_gate: str = "head"  # the output gate: one logit a "head" or an "element"
    attn_gate: bool = False  # head-wise sigmoid gate on the attention output
    # the clamped SwiGLU is not implemented: a non-zero limit is refused
    expert_swiglu_limit: float = 0.0
    shared_expert_swiglu_limit: float = 0.0
    # each block's mixer kind, of MIXER_KINDS; None = by layer_group_size
    # (``kinds``). A grouped-query block i has heads_per_layer[i] query heads
    # (``heads`` where the list is None) over kv_heads key/value heads of
    # head_dim; a kind's rope_parameters entry may be None: no rotary embedding
    layer_types: tuple[str, ...] | None = None
    heads_per_layer: tuple[int, ...] | None = None  # num_attention_heads_per_layer
    kv_heads: int = 8  # num_key_value_heads
    head_dim: int = 128
    sliding_window: int = 512  # keys a sliding_attention query sees, itself included
    rope_parameters: tuple[tuple[str, Rope | None], ...] | None = None  # a Rope a kind
    # the model's own head count a kind, where this chip holds a share of them
    heads_published: tuple[tuple[str, int], ...] | None = None
    # the expert layer's variants (module docstring): what the router reads,
    # how it scores, and a routed expert's gate activation
    router_input: str = "ffn_norm"  # or "block_input": before the input norm and attention
    router_scoring: str = "sigmoid_bias"  # or "softmax_topk": softmax over the chosen logits
    expert_act: str = "silu"  # or "relu"
    # the short-convolution family: a "conv" entry of layer_types is the gated
    # short-convolution mixer of conv_taps taps (conv_L_cache); a per-head
    # RMSNorm on a grouped-query block's q and k; the head is the embedding
    conv_taps: int = 3
    qk_norm: bool = False
    tie_embeddings: bool = False
    # the block-diffusion family (module docstring): the diffusion block's
    # length, a power of two (0: a causal next-token model, as every other family)
    diffusion_block: int = 0

    grad_ckpt: bool = True
    remat_policy: RematPolicy = "none"
    dtype: str = "bfloat16"

    def __post_init__(self):
        for name in ("vocab_rows", "experts_held", "heads_per_layer"):  # a recipe gives lists
            value = getattr(self, name)
            if value is not None:
                object.__setattr__(self, name, tuple(int(v) for v in value))
        if self.layer_types is not None:
            self._check_layer_types()
        if isinstance(self.heads_published, dict):  # a recipe gives a mapping
            object.__setattr__(self, "heads_published", tuple(self.heads_published.items()))
        if self.kda_gate not in ("safe", "softplus") or self.kda_out_gate not in (
                "head", "element"):
            raise ValueError(f"kda_gate {self.kda_gate!r} / kda_out_gate {self.kda_out_gate!r}: "
                             "safe or softplus, head or element")
        for name, known in (("router_input", ("ffn_norm", "block_input")),
                            ("router_scoring", ("sigmoid_bias", "softmax_topk")),
                            ("expert_act", ("silu", "relu"))):
            if getattr(self, name) not in known:
                raise ValueError(f"{name} {getattr(self, name)!r}: one of {known}")
        if self.router_scoring == "softmax_topk" and self.n_group > 1:
            raise ValueError("softmax_topk routing has no group limit: n_group must be 1")
        if self.mtp_layers not in (0, 1):
            raise ValueError("mtp_layers must be 0 or 1")
        if self.diffusion_block:
            b = self.diffusion_block
            if b < 0 or b & (b - 1):
                raise ValueError(f"diffusion_block {b} must be a power of two")
            if set(self.kinds) != {"full_attention"} or self.mtp_layers:
                raise ValueError("a block-diffusion model's blocks are full_attention, with no "
                                 "MTP module: the pattern over the clean and the noisy copy "
                                 "is the grouped-query core's alone")
        if self.expert_swiglu_limit or self.shared_expert_swiglu_limit:
            raise ValueError("a non-zero SwiGLU limit (the clamped SwiGLU) is not implemented")
        if self.n_routed_experts % self.n_group or not 0 < self.topk_group <= self.n_group:
            raise ValueError(f"n_group {self.n_group} / topk_group {self.topk_group} do not "
                             f"divide the {self.n_routed_experts} routed experts")
        if self.n_group > 1 and self.topk_group * (self.n_routed_experts // self.n_group) \
                < self.experts_per_token:
            raise ValueError("the groups kept hold fewer experts than a token picks")
        e0, n = self.held
        if not (0 <= e0 and n > 0 and e0 + n <= self.n_routed_experts):
            raise ValueError(f"experts_held {self.experts_held} outside the "
                             f"{self.n_routed_experts} routed experts")
        v0, rows = self.rows
        if not (0 <= v0 and rows > 0 and v0 + rows <= self.vocab_size):
            raise ValueError(f"vocab_rows {self.vocab_rows} outside the vocabulary")

    def _check_layer_types(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        ropes = self.rope_parameters or ()
        if isinstance(ropes, dict):  # a recipe gives the published group: a kind -> keys or null
            order = lambda item: GQA_KINDS.index(item[0]) if item[0] in GQA_KINDS else 2
            ropes = tuple((kind, None if keys is None else Rope(**keys))
                          for kind, keys in sorted(ropes.items(), key=order))
        object.__setattr__(self, "rope_parameters", tuple(ropes))
        grouped = [i for i, kind in enumerate(self.layer_types) if kind in GQA_KINDS]
        heads = self.heads_per_layer or (self.heads,) * self.layers
        if not (len(self.layer_types) == len(heads) == self.layers):
            raise ValueError(f"layer_types and heads_per_layer must name each of the "
                             f"{self.layers} layers")
        if (set(self.layer_types) - set(MIXER_KINDS)
                or not {self.layer_types[i] for i in grouped} <= dict(ropes).keys() <= set(
                    GQA_KINDS)):
            raise ValueError(f"layer_types name the kinds {MIXER_KINDS}, and rope_parameters "
                             f"each of {GQA_KINDS} among them (a Rope, or None for no rotation)")
        if any(heads[i] % self.kv_heads for i in grouped):
            raise ValueError(f"query heads {heads} are no multiple of {self.kv_heads} "
                             "key/value heads")
        if self.layer_group_size:
            raise ValueError("layer_types and layer_group_size both say which block is of "
                             "which kind: give one")
        if self.mtp_layers and grouped:
            raise ValueError("a grouped-query block has no MTP module beside it: the module's "
                             "block is of the MLA kind, built for the all-MLA and hybrid trunks")

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def rows(self) -> tuple[int, int]:
        return self.vocab_rows or (0, self.vocab_size)

    @property
    def mask_id(self) -> int:
        """The id a masked token of a block-diffusion model's noisy copy
        takes: the last vocabulary row held, which no document holds."""
        return self.rows[0] + self.rows[1] - 1

    def token_row(self, seq: int) -> int:
        """Ids a batch row holds for ``seq`` trained tokens: those, the next
        one and one more a multi-token-prediction module; a block-diffusion
        model's row is the clean tokens alone (nothing is shifted)."""
        return seq if self.diffusion_block else seq + 1 + self.mtp_layers

    @property
    def kinds(self) -> tuple[str, ...]:
        """Each trunk block's mixer kind, of ``MIXER_KINDS``: the one
        list every reader goes by. ``layer_types`` where given; else filled by
        the hybrid family's rule (block ``i`` is MLA when ``i + 1`` is a
        multiple of ``layer_group_size``, KDA otherwise; 0: every block MLA)."""
        if self.layer_types is not None:
            return self.layer_types
        p = self.layer_group_size
        return tuple("kda" if p and (i + 1) % p else "mla" for i in range(self.layers))

    def is_kda(self, layer: int) -> bool:
        """Whether trunk block ``layer`` is of the linear-attention kind."""
        return self.kinds[layer] == "kda"

    @property
    def kda_layers(self) -> int:
        return self.kinds.count("kda")

    def attention_kind(self, layer: int) -> str:
        """Trunk block ``layer``'s attention: its entry of ``kinds``."""
        return self.kinds[layer]

    def query_heads(self, layer: int) -> int:
        """The query heads a grouped-query block ``layer`` holds."""
        return self.heads_per_layer[layer] if self.heads_per_layer else self.heads

    def rope(self, kind: str) -> Rope | None:
        """A grouped-query kind's rotary embedding; None: it has none."""
        return dict(self.rope_parameters)[kind]

    def attn_heads(self) -> dict:
        """``{kind: (held, published)}``: the heads this chip holds of each
        attention kind among the blocks (a grouped-query kind's query heads,
        the first such block's) and the model's own count, which is the held
        one where ``heads_published`` names none. Static."""
        def of_block(i, kind):
            if kind in GQA_KINDS:
                return self.query_heads(i)
            return (self.kda_heads or self.heads) if kind == "kda" else self.heads

        held = {}
        for i, kind in enumerate(self.kinds + ("mla",) * self.mtp_layers):
            if kind != "conv":  # a short-convolution block has no heads
                held.setdefault(kind, of_block(i, kind))
        published = dict(self.heads_published or ())
        return {kind: (n, published.get(kind, n)) for kind, n in sorted(held.items())}

    def attn_pairs(self, seq: int) -> dict:
        """``{kind: (visited, needed)}`` for each kind of softmax attention
        among the blocks: the score entries of one (head, sequence) of
        ``seq`` tokens that the causal kernels compute (the block pairs their
        tables walk; of a pair a mask cuts, the sub-tiles that hold a visible
        entry), and those the mask keeps
        (``ops/pallas/attention.causal_pairs``). A block-diffusion model has
        one kind, ``"block_diffusion"``: the pair of copies of a sequence of
        ``seq`` clean tokens under its pattern. Static."""
        from jumbo_mae_tpu_tpu.ops.pallas.attention import causal_pairs

        if self.diffusion_block:
            return {"block_diffusion": causal_pairs(seq, diffusion=self.diffusion_block)}
        kinds = set(self.kinds) - {"kda", "conv"}  # neither has (query, key) pairs
        if self.mtp_layers:
            kinds.add("mla")
        window = lambda kind: self.sliding_window if kind == "sliding_attention" else None
        return {kind: causal_pairs(seq, window(kind)) for kind in sorted(kinds)}

    @property
    def layers_by_kind(self) -> dict:
        """``{kind: trunk blocks of that mixer kind}``, sorted. Static."""
        return {kind: self.kinds.count(kind) for kind in sorted(set(self.kinds))}

    @property
    def moe_counters(self) -> tuple[str, ...]:
        """The counters an expert layer of this configuration reports, in
        the order of its stats vector."""
        return MOE_COUNTERS + ((ACT_ZERO_COUNTER,) if self.expert_act == "relu" else ())

    @property
    def shared_hidden(self) -> int:
        return self.n_shared_experts * (self.shared_expert_hidden or self.expert_hidden)

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

    def setup(self):
        self.kernel = self.param("kernel", _normal(self.cfg), self.shape, jnp.float32)

    def __call__(self, x):
        dtype = self.cfg.compute_dtype
        return jnp.einsum(self.spec, x.astype(dtype), self.kernel.astype(dtype))


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


def _rope_angles(rope: Rope, seq: int, r: int):
    """``attention_factor`` times the cosine and the sine of ``position ·
    inv_freq_j``: two (seq, r / 2) float32 tables."""
    inv = jnp.asarray(rope.inv_freq(r), jnp.float32)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    return rope.attention_factor * jnp.cos(angle), rope.attention_factor * jnp.sin(angle)


def _rope_half_pass(x, rope: Rope, sign: float, interpret: bool):
    """``rope_half`` as the one-pass kernel (``sign`` 1), or its transpose
    (−1): a rotation scaled by a factor transposes to the rotation back
    scaled by the same factor, the same map with the sines negated."""
    from jumbo_mae_tpu_tpu.ops.pallas.rope import rotate_half

    seq, d = x.shape[-2:]
    r = int(d * rope.partial_rotary_factor)
    cos, sin = _rope_angles(rope, seq, r)
    c = jnp.concatenate([cos, cos, jnp.ones((seq, d - r), jnp.float32)], axis=-1)
    s = jnp.concatenate([-sign * sin, sign * sin, jnp.zeros((seq, d - r), jnp.float32)], axis=-1)
    return rotate_half(x, c, s, r, interpret=interpret)


# no residual: the transpose makes its tables again from the static Rope
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _rope_half_kernel(x, rope: Rope, interpret: bool):
    return _rope_half_pass(x, rope, 1.0, interpret)


_rope_half_kernel.defvjp(
    lambda x, rope, interpret: (_rope_half_pass(x, rope, 1.0, interpret), None),
    lambda rope, interpret, _, ct: (_rope_half_pass(ct, rope, -1.0, interpret),))


def rope_half(x, rope: Rope, *, interpret: bool = False):
    """Rotary embedding with the rotate-half pairing: of the last axis' first
    ``r = partial_rotary_factor · d`` dimensions, ``j`` turns with ``j + r/2``
    by ``position · inv_freq_j``, ``cos`` and ``sin`` times
    ``attention_factor``; the other ``d − r`` pass through. Positions run
    along the axis before the last. Float32 inside. On the TPU a head-major
    (batch, heads, seq, d) operand whose shape the kernel takes
    (``ops/pallas/rope.rope_blocks``) goes through it in one pass;
    ``interpret`` runs the kernel in the Pallas interpreter (tests)."""
    from jumbo_mae_tpu_tpu.ops.pallas.rope import rope_blocks

    seq, d = x.shape[-2:]
    r = int(d * rope.partial_rotary_factor)
    if ((interpret or jax.default_backend() == "tpu") and x.ndim == 4
            and rope_blocks(x.shape[1], seq, d)):
        return _rope_half_kernel(x, rope, interpret)
    cos, sin = _rope_angles(rope, seq, r)
    turned = x[..., :r].astype(jnp.float32)
    a, b = turned[..., : r // 2], turned[..., r // 2:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)
    return out if r == d else jnp.concatenate([out, x[..., r:]], axis=-1)


class GroupedQueryAttention(nn.Module):
    """Grouped-query softmax attention of one kind (module docstring):
    ``heads`` query heads over ``cfg.kv_heads`` key/value heads, full or,
    where ``sliding``, windowed."""

    cfg: MlaMoeConfig
    heads: int
    sliding: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, g, d = self.heads, cfg.kv_heads, cfg.head_dim
        rope = cfg.rope("sliding_attention" if self.sliding else "full_attention")
        with jax.named_scope(SCOPE_GQA_PROJ):
            # with qk_norm: per head, over head_dim, one learnt scale each
            norm = lambda name, t: (RMSNorm(cfg.rms_eps, cfg.compute_dtype, name=name)(t)
                                    if cfg.qk_norm else t)
            q = norm("q_norm", Proj((cfg.dim, h, d), "bsd,dhe->bhse", cfg, name="q")(x)) * d**-0.5
            k = norm("k_norm", Proj((cfg.dim, g, d), "bsd,dhe->bhse", cfg, name="k")(x))
            v = Proj((cfg.dim, g, d), "bsd,dhe->bhse", cfg, name="v")(x)
            if cfg.attn_gate:
                gate = Proj((cfg.dim, h), "bsd,dh->bhs", cfg, name="gate")(x)
        if rope is not None:  # a kind without rotary embedding opens no rope scope
            with jax.named_scope(SCOPE_ROPE):
                if cfg.diffusion_block:
                    # the rows are a clean and a noisy copy at the same positions:
                    # each head's two copies read as two heads of half the rows
                    q, k = (rope_half(t.reshape(t.shape[0], 2 * t.shape[1], -1, d),
                                      rope).reshape(t.shape) for t in (q, k))
                else:
                    q, k = rope_half(q, rope), rope_half(k, rope)
        if cfg.diffusion_block:
            with jax.named_scope(SCOPE_BD_CORE):
                z = causal_attention(q, None, k, None, v, impl=None,
                                     diffusion=cfg.diffusion_block)
        else:
            with jax.named_scope(SCOPE_SWA_CORE if self.sliding else SCOPE_ATTN_CORE):
                # impl=None: the op asks its rule; the keyword is what the
                # benchmark's mutation tests require of this call (ops/attention.py)
                z = causal_attention(q, None, k, None, v, impl=None,
                                     window=cfg.sliding_window if self.sliding else None)
        with jax.named_scope(SCOPE_ATTN_OUT):
            if cfg.attn_gate:
                z = _head_gate(z, gate)
            return Proj((h, d, cfg.dim), "bhsd,hdm->bsm", cfg, name="out")(z)


class ShortConv(nn.Module):
    """The gated short-convolution mixer (module docstring): ``y = (C ⊙
    conv_K(B ⊙ x̃)) W_out`` with ``B, C, x̃`` the thirds of ``x W_in``; a
    depth-wise causal filter of ``cfg.conv_taps`` taps, no activation, no
    heads."""

    cfg: MlaMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg, d = self.cfg, self.cfg.dim
        with jax.named_scope(SCOPE_SCONV_IN):
            z = Proj((d, 3 * d), "bsd,de->bse", cfg, name="in_proj")(x)
        with jax.named_scope(SCOPE_SCONV_MIX):
            b, c, u = z[..., :d], z[..., d:2 * d], z[..., 2 * d:]
            make = lambda key: {"kernel": _normal(cfg)(key, (cfg.conv_taps, d), jnp.float32)}
            w = self.param("conv", make)["kernel"]  # a leaf conv/kernel: one filter a channel
            y = c * causal_conv(b * u, w, None)
        with jax.named_scope(SCOPE_SCONV_OUT):
            return Proj((d, d), "bsd,de->bse", cfg, name="out_proj")(y)


class LatentAttention(nn.Module):
    cfg: MlaMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, dn, dr, dv = cfg.heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.compute_dtype, name=name)
        with jax.named_scope(SCOPE_MLA_LATENT):
            if cfg.q_lora_rank is None:
                q = Proj((cfg.dim, h, dn + dr), "bsd,dhe->bhse", cfg, name="q")(x)
            else:
                c_q = norm("q_norm")(Proj((cfg.dim, cfg.q_lora_rank), "bsd,dr->bsr", cfg, name="q_a")(x))
                q = Proj((cfg.q_lora_rank, h, dn + dr), "bsr,rhd->bhsd", cfg, name="q_b")(c_q)
            q = q * cfg.qk_head_dim**-0.5
            kv = Proj((cfg.dim, cfg.kv_lora_rank + dr), "bsd,dr->bsr", cfg, name="kv_a")(x)
            c_kv, k_pe = kv[..., : cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank:]
            kv = Proj((cfg.kv_lora_rank, h, dn + dv), "bsr,rhd->bhsd", cfg,
                      name="kv_b")(norm("kv_norm")(c_kv))
            if cfg.attn_gate:
                gate = Proj((cfg.dim, h), "bsd,dh->bhs", cfg, name="gate")(x)
        with jax.named_scope(SCOPE_ROPE):
            q_pe = rope_interleaved(q[..., dn:], cfg.rope_theta)
            k_pe = rope_interleaved(k_pe, cfg.rope_theta)
        with jax.named_scope(SCOPE_ATTN_CORE):
            z = causal_attention(q[..., :dn], q_pe, kv[..., :dn], k_pe, kv[..., dn:], impl=None)
        with jax.named_scope(SCOPE_ATTN_OUT):
            if cfg.attn_gate:
                z = _head_gate(z, gate)
            return Proj((h, dv, cfg.dim), "bhsd,hdm->bsm", cfg, name="out")(z)


def _head_gate(z, gate):
    """``sigmoid(gate) · z`` head by head: ``z`` (batch, heads, seq, width),
    ``gate`` (batch, heads, seq) logits; the sigmoid in float32."""
    return z * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None].astype(z.dtype)


def _decay_bias_init(key, shape, a_log, lower_bound):
    """``dt_bias`` drawn so that the decay a step at a zero gate input,
    ``exp(lower_bound · sigmoid(exp(A_log) · dt_bias))``, leaves ``1 − α``
    log-uniform over 0.001 .. 0.1 (α over 0.9 .. 0.999): assumed."""
    log_miss = jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1))
    share = jnp.log1p(-jnp.exp(log_miss)) / lower_bound  # what the sigmoid has to give
    return (jnp.log(share) - jnp.log1p(-share)) / jnp.exp(a_log)[:, None]


def _softplus_bias_init(key, shape):
    """``dt_bias`` of the softplus gate: ``softplus(dt_bias) = dt`` with ``dt``
    log-uniform over 0.001 .. 0.1, so that ``g = −exp(A_log) · dt`` at a zero
    gate input (flash-linear-attention's ``KimiDeltaAttention``: assumed)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class KdaAttention(nn.Module):
    """Kimi Delta Attention (module docstring), its gates by what the
    configuration says: ``kda_gate``, ``kda_beta_scale``, ``kda_gate_rank``,
    ``kda_out_gate``. Returns ``(y, stats)``, ``stats`` in ``KDA_COUNTERS``
    order."""

    cfg: MlaMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, dh, d, f32 = cfg.kda_heads or cfg.heads, cfg.kda_head_dim, cfg.dim, jnp.float32
        dtype = cfg.compute_dtype
        safe, rank = cfg.kda_gate == "safe", cfg.kda_gate_rank
        with jax.named_scope(SCOPE_KDA_PROJ):
            wide = lambda name: Proj((d, h, dh), "bsd,dhe->bhse", cfg, name=name)(x)
            thin = lambda name: Proj((d, h), "bsd,dh->bhs", cfg, name=name)(x)

            def gate_proj(name, head_wise=False):
                # one matrix, or through ``rank``: the first factor is the same
                # on every chip that holds a share of the layer's heads
                if rank is None:
                    return thin(name) if head_wise else wide(name)
                low = Proj((d, rank), "bsd,dr->bsr", cfg, name=f"{name}_a")(x)
                if head_wise:
                    return Proj((rank, h), "bsr,rh->bhs", cfg, name=f"{name}_b")(low)
                return Proj((rank, h, dh), "bsr,rhe->bhse", cfg, name=f"{name}_b")(low)

            q, k, v, a = wide("q"), wide("k"), wide("v"), gate_proj("f")
            b, gate = thin("b"), gate_proj("gate", cfg.kda_out_gate == "head")
        with jax.named_scope(SCOPE_KDA_CONV):
            def filt(name):  # a leaf <name>/kernel: one filter a channel
                bound = cfg.kda_conv**-0.5
                make = lambda key: {"kernel": jax.random.uniform(
                    key, (cfg.kda_conv, h, dh), f32, -bound, bound)}
                return self.param(name, make)["kernel"]

            # filter, SiLU and (q, k) the L2 norm with its scale: one pass a tensor
            q, k, v = (short_conv(u, filt(f"{n}_conv"), scale) for n, u, scale in
                       (("q", q, dh**-0.5), ("k", k, 1.0), ("v", v, None)))
        with jax.named_scope(SCOPE_KDA_GATE):
            rates = (0.25, 1.0) if safe else (1.0, 16.0)  # exp(A_log)'s range: assumed
            a_log = self.param("A_log", lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, f32, *rates)), (h,))
            if safe:
                dt_bias = self.param("dt_bias", _decay_bias_init, (h, dh), a_log,
                                     cfg.kda_lower_bound)
                g = cfg.kda_lower_bound * jax.nn.sigmoid(
                    jnp.exp(a_log)[:, None, None] * (a.astype(f32) + dt_bias[:, None, :]))
            else:
                dt_bias = self.param("dt_bias", _softplus_bias_init, (h, dh))
                g = -jnp.exp(a_log)[:, None, None] * jax.nn.softplus(
                    a.astype(f32) + dt_bias[:, None, :])
            beta = jax.nn.sigmoid(b.astype(f32))
            if cfg.kda_beta_scale != 1.0:
                beta = cfg.kda_beta_scale * beta
        with jax.named_scope(SCOPE_KDA_CORE):
            # the scan is told the floor under g, or that there is none
            o, state = kda_chunked(q, k, v, g, beta, chunk=cfg.kda_chunk,
                                   floor=cfg.kda_lower_bound if safe else None)
        with jax.named_scope(SCOPE_KDA_OUT):
            with jax.named_scope(SCOPE_KDA_GATE):
                o = RMSNorm(cfg.rms_eps, dtype, name="o_norm")(o)
                if cfg.kda_out_gate == "head":
                    o = _head_gate(o, gate)
                else:
                    o = o * jax.nn.sigmoid(gate.astype(f32)).astype(o.dtype)
            y = Proj((h, dh, d), "bhse,hed->bsd", cfg, name="out")(o)
        with jax.named_scope(SCOPE_KDA_GATE):
            stats = jnp.stack([jnp.abs(state).max(), jnp.exp(g).mean(), beta.max(),
                               (beta > 1.0).mean(dtype=f32)])
        return y, jax.lax.stop_gradient(stats)


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


def _glu(gu, act: str):
    """``act(gate) ⊙ up`` of the stacked product (rows, gate ‖ up)."""
    hidden = gu.shape[1] // 2
    return (nn.silu if act == "silu" else nn.relu)(gu[:, :hidden]) * gu[:, hidden:]


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def routed_experts(x, w_gu, w_down, gate, row_to_pair, pair_to_row, group_sizes, rounds,
                   chunk, impl="auto", interpret=False, act="silu"):
    """``(Σ_slots gate · E(x), zeros)`` over the pairs on held experts, an
    expert's gate activation ``act`` (``"silu"`` or ``"relu"``): ``x`` (tokens,
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
                       chunk, impl, interpret, act)[0]


def _routed_fwd(x, w_gu, w_down, gate, row_to_pair, pair_to_row, group_sizes, rounds,
                chunk, impl, interpret, act):
    product = functools.partial(grouped_matmul, impl=impl, interpret=interpret)

    def one_round(r, carry):
        y, zeros = carry
        token, _, sizes, row_of_pair, mine = _round_rows(
            r, chunk, row_to_pair, pair_to_row, gate, group_sizes)
        rows = x[token]
        with jax.named_scope(SCOPE_EXPERTS):
            hidden = _glu(product(rows, w_gu, sizes), act)
            out = product(hidden, w_down, sizes)
            if zeros is not None:  # the round's rows past its experts' sizes are padding
                held = jnp.arange(chunk)[:, None] < sizes.sum()
                zeros = zeros + ((hidden == 0) & held).sum(dtype=jnp.float32)
        return y + _sum_slots(out, row_of_pair, jnp.where(mine, gate, 0.0)), zeros

    with jax.named_scope(SCOPE_MOE_DISPATCH):
        y, zeros = jax.lax.fori_loop(
            0, rounds, one_round, (jnp.zeros(x.shape, jnp.float32),
                                   jnp.zeros((), jnp.float32) if act == "relu" else None))
        y = y.astype(x.dtype)
    return (y, zeros), (x, w_gu, w_down, gate, row_to_pair, pair_to_row, group_sizes, rounds)


def _routed_bwd(chunk, impl, interpret, act, residuals, cotangents):
    dy, _ = cotangents  # the count of zeros takes no gradient
    x, w_gu, w_down, gate, row_to_pair, pair_to_row, group_sizes, rounds = residuals
    product = functools.partial(grouped_matmul, impl=impl, interpret=interpret)
    outer = functools.partial(grouped_outer, impl=impl, interpret=interpret)

    def one_round(r, carry):
        d_x, d_gate, d_w_gu, d_w_down = carry
        token, gate_of_row, sizes, row_of_pair, mine = _round_rows(
            r, chunk, row_to_pair, pair_to_row, gate, group_sizes)
        rows, d_out = x[token], dy[token]
        with jax.named_scope(SCOPE_EXPERTS):
            hidden, glu_vjp = jax.vjp(functools.partial(_glu, act=act),
                                      product(rows, w_gu, sizes))
            # out = gate · (hidden W_down), so the gate's gradient is
            # hidden · (d_out W_downᵀ) and the activation's is gate times it
            d_act = product(d_out, w_down, sizes, transpose_rhs=True)
            d_gate_of_row = (hidden.astype(jnp.float32) * d_act.astype(jnp.float32)).sum(axis=1)
            scale = gate_of_row[:, None].astype(hidden.dtype)
            d_w_down = outer(hidden * scale, d_out, sizes, d_w_down)
            (d_gu,) = glu_vjp(d_act * scale)
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


def _group_limited(biased, n_group: int, topk_group: int):
    """``biased`` (tokens, experts) with the experts outside a token's best
    ``topk_group`` of ``n_group`` equal groups set to ``-inf``; a group's
    score is the sum of its two largest entries."""
    n, e = biased.shape
    grouped = biased.reshape(n, n_group, e // n_group)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
    _, keep = jax.lax.top_k(group_score, topk_group)  # (n, topk_group)
    kept = (keep[..., None] == jnp.arange(n_group)).any(axis=1)  # (n, n_group)
    return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(n, e)


class SparseExperts(nn.Module):
    """Router over all experts, grouped products over those held here, and
    the shared expert where the configuration has one. ``x`` is what the
    experts read; the router reads ``router_x`` where one is given (the
    block's input: ``router_input: "block_input"``), else ``x``. Returns
    ``(y, stats)``, ``stats`` in ``cfg.moe_counters`` order."""

    cfg: MlaMoeConfig

    @nn.compact
    def __call__(self, x, router_x=None):
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
            read = flat if router_x is None else router_x.reshape(n, d)
            logits = jnp.dot(read.astype(jnp.float32), w_r, precision=jax.lax.Precision.HIGHEST)
            if cfg.router_scoring == "softmax_topk":
                picked, chosen = jax.lax.top_k(logits, k)  # (n, k)
                weights = cfg.routed_scaling_factor * jax.nn.softmax(picked, axis=1)
            else:
                scores = jax.nn.sigmoid(logits)
                bias = self.variable(
                    "batch_stats", "router_bias",
                    lambda: 0.01 * jax.random.normal(self.make_rng("params"), (e,), jnp.float32))
                biased = scores + bias.value
                if cfg.n_group > 1:
                    biased = _group_limited(biased, cfg.n_group, cfg.topk_group)
                _, chosen = jax.lax.top_k(biased, k)  # (n, k)
                picked = jnp.take_along_axis(scores, chosen, axis=1)
                weights = cfg.routed_scaling_factor * picked / picked.sum(axis=1, keepdims=True)
                counts = (chosen[..., None] == jnp.arange(e)).sum(axis=(0, 1)).astype(jnp.float32)
                if not self.is_initializing() and self.is_mutable_collection("batch_stats"):
                    bias.value = bias.value + cfg.router_bias_rate * jnp.sign(
                        counts.mean() - counts)
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
        routed, zeros = routed_experts(flat, w_gu, w_down, gate, row_to_pair, pair_to_row,
                                       group_sizes, rounds, chunk, act=cfg.expert_act)
        with jax.named_scope(SCOPE_ROUTER):
            per_expert = group_sizes.astype(jnp.float32)
            mean = per_expert.mean()
            stats = [
                per_expert.min(), mean, per_expert.max(),
                per_expert.max() / jnp.maximum(mean, 1.0),
                total / (n * k),
                # the rounds take ``chunk`` rows each: what they did not reach
                (total - jnp.minimum(total, rounds * chunk)).astype(jnp.float32),
                rounds.astype(jnp.float32),
            ]
            if zeros is not None:  # ACT_ZERO_COUNTER: of the held rows' entries
                stats.append(zeros / jnp.maximum(total * cfg.expert_hidden, 1))
            stats = jnp.stack(stats)
        if not cfg.shared_hidden:  # no shared expert: no module, no zero-width leaf
            return routed.reshape(b, s, d), jax.lax.stop_gradient(stats)
        with jax.named_scope(SCOPE_SHARED_EXPERT):
            shared = GatedMlp(cfg.shared_hidden, cfg, name="shared")(x)
        return shared + routed.reshape(b, s, d), jax.lax.stop_gradient(stats)


class Block(nn.Module):
    """One pre-norm residual block: the mixer of ``kind``
    (``MlaMoeConfig.attention_kind``: latent, linear, grouped-query of
    ``heads`` query heads, full or sliding, or the gated short convolution),
    then the dense MLP
    (``sparse=False``) or the expert layer, whose router reads the block's
    input where ``router_input`` says so. Returns ``(x, stats,
    kda_stats)``: the expert layer's counters and the linear-attention
    layer's (None where the block has none)."""

    cfg: MlaMoeConfig
    sparse: bool
    kind: str = "mla"
    heads: int = 0  # a grouped-query block's query heads

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        del deterministic  # no dropout; the argument keeps maybe_remat's signature
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_eps, cfg.compute_dtype, name=name)
        kda_stats = None
        router_x = (x,) if cfg.router_input == "block_input" else ()
        if self.kind == "kda":
            y, kda_stats = KdaAttention(cfg, name="attn")(norm("ln1")(x))
            x = x + y
        elif self.kind == "mla":
            x = x + LatentAttention(cfg, name="attn")(norm("ln1")(x))
        elif self.kind == "conv":
            x = x + ShortConv(cfg, name="conv")(norm("ln1")(x))
        else:
            attn = GroupedQueryAttention(cfg, self.heads, self.kind == "sliding_attention",
                                         name="attn")
            x = x + attn(norm("ln1")(x))
        if self.sparse:
            y, stats = SparseExperts(cfg, name="moe")(norm("ln2")(x), *router_x)
        else:
            with jax.named_scope(SCOPE_DENSE_MLP):
                y = GatedMlp(cfg.dense_hidden, cfg, name="mlp")(norm("ln2")(x))
            stats = jnp.zeros((len(cfg.moe_counters),), jnp.float32)
        return x + y, stats, kda_stats


class MlaMoeLM(nn.Module):
    """``__call__(tokens)`` with ``tokens`` (batch, seq + 1 + mtp_layers)
    int32 ids from the vocabulary rows held: the training loss and the
    step's counters. ``logits(tokens)`` returns both heads' logits. A
    block-diffusion model (``cfg.diffusion_block``) takes (batch, seq) clean
    ids and a noise key — ``noise_key``, else the ``noise`` stream's when it
    trains and a fixed key when it does not (``deterministic``: evaluation) —
    and ``logits`` returns one array over the 2 · seq rows, the clean copy's
    first (module docstring).

    Under the ``lm_head`` scope the training path runs the final norm and,
    a head, ``ops/head_loss.head_loss``: a tile of tokens at a time the
    logits over the rows held, each token's loss, and the gradients of the
    normed hidden state and of the head kernel, all in the forward pass (the
    backward pass only scales them), whatever ``grad_ckpt`` says — nothing
    of the head is rematerialised. ``loss`` is the scalar that carries the
    gradient (each head's ``Σ weight · nll``); ``loss_per_sample``,
    ``loss_trunk`` and ``loss_mtp`` are values only. ``logits()`` is the
    plain product for every token (evaluation, the references' comparisons)."""

    cfg: MlaMoeConfig

    def setup(self):
        cfg = self.cfg
        block = maybe_remat(Block, cfg)
        embed_init = nn.initializers.normal(cfg.embed_init_std or cfg.init_std)
        self.embedding = self.param("embedding", embed_init, (cfg.rows[1], cfg.dim), jnp.float32)
        heads = [cfg.query_heads(i) if kind in GQA_KINDS else 0
                 for i, kind in enumerate(cfg.kinds)]
        self.blocks = [block(cfg, sparse=i >= cfg.first_k_dense, kind=cfg.kinds[i],
                             heads=heads[i], name=f"block_{i}") for i in range(cfg.layers)]
        self.ln = RMSNorm(cfg.rms_eps, cfg.compute_dtype, name="ln")
        if not cfg.tie_embeddings:
            self.head = Proj((cfg.dim, cfg.rows[1]), "bsd,dv->bsv", cfg, name="head")
        if cfg.mtp_layers:
            self.mtp_embed_norm = RMSNorm(cfg.rms_eps, cfg.compute_dtype, name="mtp_embed_norm")
            self.mtp_hidden_norm = RMSNorm(cfg.rms_eps, cfg.compute_dtype, name="mtp_hidden_norm")
            self.mtp_merge = Proj((2 * cfg.dim, cfg.dim), "bsd,dm->bsm", cfg, name="mtp_merge")
            self.mtp_block = block(cfg, sparse=True, name="mtp_block")

    def _embed(self, ids):
        with jax.named_scope(SCOPE_EMBED):
            return self.embedding[ids].astype(self.cfg.compute_dtype)

    def _noise(self, tokens, deterministic: bool, noise_key):
        """A block-diffusion step's draw for the clean ``tokens`` (batch, seq):
        the noisy copy's ids, the loss's weight a position, ``masked / (t ·
        batch · seq)``, and the share of the positions that are masked."""
        cfg = self.cfg
        batch, seq = tokens.shape
        with jax.named_scope(SCOPE_BD_NOISE):
            if noise_key is None:  # evaluation sees the same noise every time
                noise_key = jax.random.key(0) if deterministic else self.make_rng("noise")
            t, masked = block_noise(noise_key, batch, seq, cfg.diffusion_block)
            noisy = jnp.where(masked, cfg.mask_id, tokens)
            weights = masked / (jnp.repeat(t, cfg.diffusion_block, axis=1) * (batch * seq))
            return noisy, weights, masked.mean(dtype=jnp.float32)

    def _hidden(self, tokens, deterministic: bool):
        """Both heads' last hidden states ``[trunk, mtp?]``, the expert
        layers' stats ``{name: vector}`` and the linear-attention layers'. A
        block-diffusion model's ``tokens`` are the 2 · seq ids a row, the
        clean copy's and then the noisy one's, and every one is embedded."""
        cfg = self.cfg
        seq = tokens.shape[1] if cfg.diffusion_block else tokens.shape[1] - 1 - cfg.mtp_layers
        ids = tokens - cfg.rows[0]
        x = self._embed(ids[:, :seq])
        stats, kda = {}, {}
        for i, blk in enumerate(self.blocks):
            x, st, linear = blk(x, deterministic)
            if i >= cfg.first_k_dense:
                stats[f"l{i}"] = st
            if linear is not None:
                kda[f"l{i}"] = linear
        hidden = [x]
        if cfg.mtp_layers:
            with jax.named_scope(SCOPE_MTP_MERGE):
                nxt = self.mtp_embed_norm(self._embed(ids[:, 1 : seq + 1]))
                merged = self.mtp_merge(jnp.concatenate([nxt, self.mtp_hidden_norm(x)], axis=-1))
            y, stats["mtp"], _ = self.mtp_block(merged, deterministic)
            hidden.append(y)
        return hidden, stats, kda

    def _moe_counters(self, stats) -> dict:
        """The expert layers' counters ``{name: vector}`` as the step's
        outputs: each layer's, and the step's own summaries of them."""
        cfg = self.cfg
        out = {}
        table = jnp.stack(list(stats.values()))  # (expert layers, counters)
        for name, st in stats.items():
            out |= {f"moe_{c}_{name}": st[j] for j, c in enumerate(cfg.moe_counters)}
        col = {c: table[:, j] for j, c in enumerate(cfg.moe_counters)}
        out |= {"moe_imbalance": col["imbalance"].max(), "moe_held_share": col["held_share"].mean(),
                "moe_dropped": col["dropped"].sum(), "moe_rounds": col["rounds"].max()}
        if ACT_ZERO_COUNTER in col:
            out[f"moe_{ACT_ZERO_COUNTER}"] = col[ACT_ZERO_COUNTER].mean()
        return out

    def _head_kernel(self):
        """The head's (dim, rows held) kernel in the compute dtype: its own
        parameter, or with ``tie_embeddings`` the embedding's rows
        transposed, so that the embedding's gradient is the lookup's and the
        head's ``dW`` added."""
        kernel = self.embedding.T if self.cfg.tie_embeddings else self.head.kernel
        return kernel.astype(self.cfg.compute_dtype)

    def _logits(self, h):
        h = self.ln(h).astype(self.cfg.compute_dtype)
        return jnp.einsum("bsd,dv->bsv", h, self._head_kernel()).astype(jnp.float32)

    def logits(self, tokens, deterministic: bool = True, *, noise_key=None):
        if self.cfg.diffusion_block:
            noisy, _, _ = self._noise(tokens, deterministic, noise_key)
            tokens = jnp.concatenate([tokens, noisy], axis=1)
        with jax.named_scope(SCOPE_LM_HEAD):
            return [self._logits(h) for h in self._hidden(tokens, deterministic)[0]]

    def _diffusion_loss(self, tokens, deterministic: bool, noise_key):
        """``__call__`` of a block-diffusion model: ``(out, stats)``."""
        cfg = self.cfg
        batch, seq = tokens.shape
        noisy, weights, masked_share = self._noise(tokens, deterministic, noise_key)
        (h,), stats, _ = self._hidden(jnp.concatenate([tokens, noisy], axis=1), deterministic)
        with jax.named_scope(SCOPE_LM_HEAD):
            # the noisy copy's rows alone; a position's target is its own clean id
            total, nll = head_loss(
                self.ln(h[:, seq:]).reshape(batch * seq, cfg.dim), self._head_kernel(),
                (tokens - cfg.rows[0]).reshape(batch * seq), weights.reshape(batch * seq))
            per_sample = batch * (weights * nll.reshape(batch, seq)).sum(axis=-1)  # values only
        return {"loss_trunk": per_sample.mean(), "loss": total, "loss_per_sample": per_sample,
                "bd_masked_share": masked_share}, stats

    def __call__(self, tokens, deterministic: bool = True, *, noise_key=None):
        cfg = self.cfg
        if cfg.diffusion_block:
            out, stats = self._diffusion_loss(tokens, deterministic, noise_key)
            return out | self._moe_counters(stats)
        batch, seq = tokens.shape[0], tokens.shape[1] - 1 - cfg.mtp_layers
        hidden, stats, kda = self._hidden(tokens, deterministic)
        ids = tokens - cfg.rows[0]
        # each head's share of the scalar a token: the mean over sequences
        # and positions, the MTP head's times its weight
        shares = [1.0] + [cfg.mtp_loss_weight] * cfg.mtp_layers
        totals, losses = [], []
        with jax.named_scope(SCOPE_LM_HEAD):
            # the kernel enters in the compute dtype, as a ``Proj``'s does: its
            # gradient is one rounding of a float32 sum, as the plain product's
            kernel = self._head_kernel()
            for i, (h, share) in enumerate(zip(hidden, shares)):
                total, nll = head_loss(
                    self.ln(h).reshape(batch * seq, cfg.dim), kernel,
                    ids[:, 1 + i : seq + 1 + i].reshape(batch * seq),
                    jnp.full((batch * seq,), share / (batch * seq), jnp.float32))
                totals.append(total)
                losses.append(nll.reshape(batch, seq).mean(axis=-1))  # per sequence
        per_sample = losses[0]
        out = {"loss_trunk": losses[0].mean()}
        if cfg.mtp_layers:
            per_sample = per_sample + cfg.mtp_loss_weight * losses[1]
            out["loss_mtp"] = losses[1].mean()
        # ``loss`` carries the gradient; the per-sequence values are values only
        out |= {"loss": sum(totals), "loss_per_sample": per_sample}
        out |= self._moe_counters(stats)
        if kda:
            for name, st in kda.items():
                out |= {f"kda_{c}_{name}": st[j] for j, c in enumerate(KDA_COUNTERS)}
            table = jnp.stack(list(kda.values()))  # (linear-attention layers, counters)
            out |= {"kda_state_absmax": table[:, 0].max(), "kda_decay_mean": table[:, 1].mean(),
                    "kda_beta_max": table[:, 2].max(), "kda_neg_eig_share": table[:, 3].mean()}
        return out
