"""Transformer building blocks for the Jumbo ViT family.

Fresh flax.linen implementations with behavioral parity to
``/root/reference/src/modeling.py:106-219`` (PatchEmbed, Attention,
FeedForward, ViTLayer, JumboLayer, LinearCLS), designed TPU-first:

- compute in a configurable dtype (bfloat16 by default) with float32 params;
- attention scores accumulate in float32 on the MXU and softmax computes in
  float32, but the materialized score/prob tensors follow the compute dtype
  (halves the O(S²) HBM traffic under bf16; exact under f32 compute, which
  is what every parity test runs — see PERF_ARCHIVE.md);
- attention's lowering (the einsum form here, a Pallas flash kernel, ring
  attention over a split sequence) is ``ops/attention.py``'s to choose, from
  what a call can observe (the einsum path is also the parity oracle in tests).

Parameter naming is semantic (q/k/v/out, fc1/fc2, ln1/ln2/ln3, ls1/ls2/ls3)
rather than the reference's wq/w1/norm1/scale1; ``tools/`` converters map
between layouts.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import initializers as init
from flax.linen.dtypes import promote_dtype

from jumbo_mae_tpu_tpu.models.config import DecoderConfig, JumboViTConfig
from jumbo_mae_tpu_tpu.obs.trace import SCOPE_ATTN_CORE
from jumbo_mae_tpu_tpu.ops import shared_grad
from jumbo_mae_tpu_tpu.ops.attention import attention, lowering_here
from jumbo_mae_tpu_tpu.ops.posemb import sincos2d_positional_embedding

TRUNC_NORMAL = init.truncated_normal(0.02)

ConfigT = Any  # JumboViTConfig | DecoderConfig — same attribute surface


def segment_attention_mask(segment_ids: jax.Array) -> jax.Array:
    """Block-diagonal attention mask for token-packed sequences.

    ``segment_ids`` is (batch, seq) int32 — ``slot+1`` on tokens a packed
    segment owns, 0 on padding. A position attends only within its own
    segment (``same id AND id > 0``); the diagonal is OR'd in so all-pad
    positions softmax over themselves instead of an all(-inf) row whose
    NaN would pollute valid rows through the probs·V matmul. Returns
    (batch, 1, seq, seq) bool, broadcast over heads."""
    s = segment_ids
    same = (s[:, :, None] == s[:, None, :]) & (s[:, :, None] > 0)
    eye = jnp.eye(s.shape[-1], dtype=bool)[None]
    return (same | eye)[:, None, :, :]


class Attention(nn.Module):
    """Multi-head self-attention.

    Parity: ``/root/reference/src/modeling.py:127-138`` — separate q/k/v
    projections to (heads, head_dim), queries pre-scaled by head_dim**-0.5,
    dropout on the attention probabilities and on the output projection.

    The q/k/v projections stay ``nn.DenseGeneral`` deliberately: a
    flat-2-D-matmul variant with identical params won a standalone
    microbench (2.55 vs 2.8–3.8 ms at the H/14 encoder slice) but LOST
    7% step-level on H/14 (269–270 vs 292 img/s, two runs) — in the full
    graph XLA fuses the 4-D contraction's output layout straight into
    the attention einsums, which the reshape breaks. PERF_ARCHIVE.md §Round 5.
    """

    cfg: ConfigT

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        deterministic: bool = True,
        mask: jax.Array | None = None,
    ) -> jax.Array:
        cfg = self.cfg
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (cfg.heads, cfg.head_dim),
            kernel_init=TRUNC_NORMAL,
            dtype=cfg.compute_dtype,
            name=name,
        )
        q = dense("q")(x) * cfg.head_dim**-0.5
        k = dense("k")(x)
        v = dense("v")(x)

        # The probabilities themselves are needed where a mask is given
        # (token-packed serving's block-diagonal segment mask) or dropout is
        # active in this call; only the einsum form below has them, and the
        # rule (ops/attention.py) then answers with it.
        how = lowering_here(
            x.shape[1],
            probs_needed=mask is not None or (cfg.dropout > 0.0 and not deterministic),
        )

        # z_head_major tracks each branch's output layout: (B,H,S,D) for the
        # einsum path, (B,S,H,D) for the kernels and the ring — set alongside
        # z so a new branch can't silently mismatch the out-projection's axes.
        with jax.named_scope(SCOPE_ATTN_CORE):
            if how != "einsum":
                z, z_head_major = attention(q, k, v), False
            else:
                # Scores materialize in the compute dtype; the MXU still
                # accumulates the dot in f32, and softmax still computes in f32
                # (the convert fuses into the softmax chain). Under bf16 compute
                # this halves the HBM traffic of the O(S²) score tensor — the
                # single largest bandwidth item in the profile: −27 ms/step on
                # the v5e bench workload's 8 decoder layers (PERF_ARCHIVE.md). Only the
                # materialized rounding is bf16; with float32 compute (all
                # parity tests/oracles) the path is exact and unchanged.
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, k)
                scores = logits.astype(jnp.float32)
                if mask is not None:
                    # -inf before softmax underflows to an exact 0 probability:
                    # a masked key contributes exactly 0·v, so segment isolation
                    # is bit-exact, not approximate (every query keeps at least
                    # its diagonal, so no row is all -inf)
                    scores = jnp.where(mask, scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1).astype(
                    cfg.compute_dtype
                )
                probs = nn.Dropout(cfg.dropout)(probs, deterministic)
                # Keep z head-major (B,H,S,D) — the layout the scores matmul
                # produces natively — and let the output projection contract
                # (h, d) from there: measured −17% attention fwd+bwd on v5e at
                # the encoder shape vs transposing back to (B,S,H,D) (PERF_ARCHIVE.md).
                z, z_head_major = jnp.einsum("bhqk,bkhd->bhqd", probs, v), True

        # kernel shape is (heads, head_dim, dim) for either axis choice, so
        # both paths share the same checkpoint layout
        out = nn.DenseGeneral(
            cfg.dim,
            axis=(1, 3) if z_head_major else (-2, -1),
            kernel_init=TRUNC_NORMAL,
            dtype=cfg.compute_dtype,
            name="out",
        )(z)
        return nn.Dropout(cfg.dropout)(out, deterministic)


class Mlp(nn.Module):
    """Dense(hidden) → GELU → Dense(out) with dropout after each dense.

    Parity: ``FeedForward``, ``/root/reference/src/modeling.py:141-148``.
    The shared "jumbo MLP" (dim = k·encoder_dim) is :class:`JumboMlp`.
    """

    dim: int
    hidden_dim: int
    dropout: float
    dtype: Any

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        x = nn.Dense(
            self.hidden_dim, kernel_init=TRUNC_NORMAL, dtype=self.dtype, name="fc1"
        )(x)
        x = nn.Dropout(self.dropout)(nn.gelu(x), deterministic)
        x = nn.Dense(
            self.dim, kernel_init=TRUNC_NORMAL, dtype=self.dtype, name="fc2"
        )(x)
        return nn.Dropout(self.dropout)(x, deterministic)


class SharedDense(nn.Module):
    """``nn.Dense``'s parameters (``kernel``, ``bias``; same inits and dtype
    rules) and arithmetic, for a kernel that every layer applies. Declared in
    ``setup`` so that the kernel can be reached before the first call
    (:meth:`open_slots`). Called with a slot, the kernel takes no gradient
    here: ``ops.shared_grad.record`` leaves ``(x, dy)`` in the slot and the
    one product over all layers' rows is formed where the slots were opened."""

    in_features: int
    features: int
    dtype: Any

    def setup(self):
        self.kernel = self.param(
            "kernel", TRUNC_NORMAL, (self.in_features, self.features)
        )
        self.bias = self.param("bias", init.zeros, (self.features,))

    @nn.nowrap
    def open_slots(self, layers: int, rows: int) -> tuple:
        # under the module's own scope, as a call is: the trace books the
        # product to the part its path names
        with jax.named_scope(self.name):
            return shared_grad.open_slots(self.kernel, layers, rows, self.dtype)

    def __call__(self, x: jax.Array, slot: tuple | None = None) -> jax.Array:
        x, kernel, bias = promote_dtype(x, self.kernel, self.bias, dtype=self.dtype)
        if slot is None:
            return x @ kernel + bias
        return shared_grad.record(slot, x, x @ jax.lax.stop_gradient(kernel)) + bias


class JumboMlp(nn.Module):
    """:class:`Mlp`'s arithmetic and parameter tree (``fc1``, ``fc2``) for the
    one MLP all ``JumboBlock``s share. Each layer sees only ``rows`` = one
    concatenated CLS vector an image, so per-layer kernel gradients are L
    thin products whose kernel-sized partials are summed through HBM;
    :meth:`open_slots` before the block loop and a layer's pair of slots at
    each call form them as one product a kernel after the last block's
    backward pass instead. Without slots (the pipeline runtime, forward-only
    programs) a call is a plain dense and autodiff's per-call gradient."""

    dim: int
    hidden_dim: int
    dropout: float
    dtype: Any

    def setup(self):
        self.fc1 = SharedDense(self.dim, self.hidden_dim, self.dtype)
        self.fc2 = SharedDense(self.hidden_dim, self.dim, self.dtype)
        # the names nn.compact gives Mlp's two dropouts: flax folds a
        # module's path into its dropout key, so a seed keeps drawing the
        # masks it drew when this MLP was an Mlp
        self.Dropout_0 = nn.Dropout(self.dropout)
        self.Dropout_1 = nn.Dropout(self.dropout)

    @nn.nowrap
    def open_slots(self, layers: int, rows: int) -> tuple:
        """One ``(fc1 slot, fc2 slot)`` pair a layer, for ``rows`` rows."""
        with jax.named_scope(self.name):
            return tuple(
                zip(self.fc1.open_slots(layers, rows), self.fc2.open_slots(layers, rows))
            )

    def __call__(
        self, x: jax.Array, deterministic: bool = True, slots: tuple | None = None
    ) -> jax.Array:
        slot1, slot2 = slots if slots is not None else (None, None)
        x = self.fc1(x, slot1)
        x = self.Dropout_0(nn.gelu(x), deterministic)
        x = self.fc2(x, slot2)
        return self.Dropout_1(x, deterministic)


def make_jumbo_mlp(cfg: JumboViTConfig, name: str | None = "jumbo_mlp") -> JumboMlp:
    """The shared jumbo CLS MLP's one architectural definition — used by
    :class:`~jumbo_mae_tpu_tpu.models.vit.JumboViT` (owner of the shared
    params, and of the slots through which the two kernels' gradient is
    formed once a step) and by the pipeline-parallel runtime (no slots:
    per-call gradients), so the two can never diverge."""
    return JumboMlp(
        dim=cfg.num_cls_tokens * cfg.dim,
        hidden_dim=4 * cfg.num_cls_tokens * cfg.dim,
        dropout=cfg.dropout,
        dtype=cfg.compute_dtype,
        name=name,
    )


class DropPath(nn.Module):
    """Stochastic depth: drop the whole residual branch per sample, i.e. a
    Dropout broadcast over every non-batch axis (the reference's idiom,
    ``/root/reference/src/modeling.py:157,181-183``)."""

    rate: float

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        bcast = tuple(range(1, x.ndim))
        return nn.Dropout(self.rate, broadcast_dims=bcast)(x, deterministic)


class PlainBlock(nn.Module):
    """Pre-norm transformer block (used by the MAE decoder).

    Parity: ``ViTLayer``, ``/root/reference/src/modeling.py:150-167``.
    """

    cfg: ConfigT

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        cfg = self.cfg
        ls = (
            lambda name: self.param(name, init.constant(1e-4), (cfg.dim,))
            if cfg.layerscale
            else 1.0
        )
        h = Attention(cfg, name="attn")(
            nn.LayerNorm(dtype=cfg.compute_dtype, name="ln1")(x), deterministic
        )
        x = x + DropPath(cfg.droppath, name="dp1")(ls("ls1") * h, deterministic)
        h = Mlp(
            cfg.dim, cfg.hidden_dim, cfg.dropout, cfg.compute_dtype, name="mlp"
        )(nn.LayerNorm(dtype=cfg.compute_dtype, name="ln2")(x), deterministic)
        x = x + DropPath(cfg.droppath, name="dp2")(ls("ls2") * h, deterministic)
        return x


class JumboBlock(nn.Module):
    """The fork's signature block (parity: ``JumboLayer``,
    ``/root/reference/src/modeling.py:169-206``).

    Attention over the full sequence; then patch tokens get the usual MLP
    while the ``num_cls_tokens`` CLS tokens are concatenated to one
    (B, k·dim) vector, LayerNorm'd, and passed through a **shared** wide MLP
    (``jumbo_mlp``, owned by the encoder and passed in as an attribute).

    Quirk preserved on purpose (training dynamics depend on it): the CLS
    residual base is the *post-norm* vector — ``cc = ln3(concat);
    cc = cc + dp(ls3 · jumbo_mlp(cc))`` — not the pre-norm input.

    ``packed`` (positional, a traced pytree — stays past the remat
    wrapper's static ``deterministic`` slot) switches the block to
    token-packed layout: attention takes the block-diagonal segment mask,
    and the CLS tokens live at each segment's ``cls_index`` offsets
    instead of the sequence head. The per-segment math is identical —
    gather the k CLS tokens, same ln3/jumbo_mlp/residual, scatter back —
    so a packed segment computes exactly what its unpacked batch row
    would (the parity tests' contract).

    ``slots`` (positional and traced, like ``packed``) is this layer's pair
    of slots from ``jumbo_mlp.open_slots``: with it the shared kernels take
    no gradient in this block's backward pass, which leaves its rows and
    their cotangents in the slots for the one product formed where they were
    opened (:class:`JumboMlp`). Without it the call differentiates as any
    dense does: a standalone block (the pipeline runtime) passes none.
    """

    cfg: JumboViTConfig
    jumbo_mlp: nn.Module

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        deterministic: bool = True,
        packed: dict | None = None,
        slots: tuple | None = None,
    ) -> jax.Array:
        cfg = self.cfg
        k = cfg.num_cls_tokens
        ls = (
            lambda name, d: self.param(name, init.constant(1e-4), (d,))
            if cfg.layerscale
            else 1.0
        )

        h = Attention(cfg, name="attn")(
            nn.LayerNorm(dtype=cfg.compute_dtype, name="ln1")(x),
            deterministic,
            mask=None if packed is None else packed["mask"],
        )
        x = x + DropPath(cfg.droppath, name="dp1")(
            ls("ls1", cfg.dim) * h, deterministic
        )

        if packed is None:
            cls, patches = x[:, :k, :], x[:, k:, :]
            bs = cls.shape[0]

            cc = nn.LayerNorm(dtype=cfg.compute_dtype, name="ln3")(
                cls.reshape(bs, k * cfg.dim)
            )
            # slots go only to a shared MLP that was handed some: a block
            # built over a plain Mlp has none to take
            shared = (cc, deterministic) if slots is None else (cc, deterministic, slots)
            cc = cc + DropPath(cfg.droppath, name="dp3")(
                ls("ls3", k * cfg.dim) * self.jumbo_mlp(*shared), deterministic
            )

            h = Mlp(
                cfg.dim, cfg.hidden_dim, cfg.dropout, cfg.compute_dtype, name="mlp"
            )(nn.LayerNorm(dtype=cfg.compute_dtype, name="ln2")(patches), deterministic)
            patches = patches + DropPath(cfg.droppath, name="dp2")(
                ls("ls2", cfg.dim) * h, deterministic
            )

            return jnp.concatenate([cc.reshape(bs, k, cfg.dim), patches], axis=1)

        # ---- packed layout: (rows, budget, dim) with per-segment CLS ----
        rows, seq, dim = x.shape
        cls_index = packed["cls_index"]  # (rows, max_segments, k)
        smax = cls_index.shape[1]
        # gather each slot's k CLS tokens -> the same (k·dim) concat the
        # unpacked branch builds from the sequence head
        g = jnp.take_along_axis(
            x, cls_index.reshape(rows, smax * k)[..., None], axis=1
        ).reshape(rows, smax, k * cfg.dim)
        cc = nn.LayerNorm(dtype=cfg.compute_dtype, name="ln3")(g)
        cc = cc + DropPath(cfg.droppath, name="dp3")(
            ls("ls3", k * cfg.dim) * self.jumbo_mlp(cc, deterministic),
            deterministic,
        )

        # patch MLP over ALL positions (it is per-token, so computing it on
        # CLS/pad positions is inert — CLS positions are overwritten below
        # and pads are never read through the masked attention)
        h = Mlp(
            cfg.dim, cfg.hidden_dim, cfg.dropout, cfg.compute_dtype, name="mlp"
        )(nn.LayerNorm(dtype=cfg.compute_dtype, name="ln2")(x), deterministic)
        patches = x + DropPath(cfg.droppath, name="dp2")(
            ls("ls2", cfg.dim) * h, deterministic
        )

        # scatter the updated CLS back to their in-row positions
        cc4 = cc.reshape(rows, smax, k, cfg.dim)
        slot0 = jnp.clip(packed["segment_ids"] - 1, 0)  # (rows, seq)
        pos0 = jnp.clip(packed["cls_pos"], 0)
        cls_vals = cc4[jnp.arange(rows)[:, None], slot0, pos0]
        return jnp.where(packed["cls_pos"][..., None] >= 0, cls_vals, patches)


class PatchEmbed(nn.Module):
    """Conv patchify + positional embedding added in 2-D grid shape.

    Parity: ``/root/reference/src/modeling.py:106-124``.
    """

    cfg: JumboViTConfig

    @nn.compact
    def __call__(self, images: jax.Array) -> jax.Array:
        cfg = self.cfg
        p = cfg.patch_size
        x = nn.Conv(
            cfg.dim,
            kernel_size=(p, p),
            strides=(p, p),
            padding="VALID",
            kernel_init=TRUNC_NORMAL,
            dtype=cfg.compute_dtype,
            name="proj",
        )(images)
        if cfg.posemb == "learnable":
            pos = self.param("pos_embed", TRUNC_NORMAL, (*cfg.grid, cfg.dim))
        else:
            pos = sincos2d_positional_embedding(*cfg.grid, cfg.dim)
        x = x + jnp.asarray(pos, x.dtype)
        return x.reshape(x.shape[0], -1, cfg.dim)


class ClassifierHead(nn.Module):
    """Linear head over concatenated CLS tokens, with an optional BatchNorm
    (linear-probe mode). Parity: ``LinearCLS``,
    ``/root/reference/src/modeling.py:209-219``.

    Under jit+GSPMD the batch axis is globally sharded, so BatchNorm's batch
    statistics are already computed over the *global* batch — no
    ``axis_name`` plumbing needed (the reference needed
    ``axis_name="batch"`` because of pmap).
    """

    labels: int
    batch_norm: bool

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        if self.batch_norm:
            x = nn.BatchNorm(use_running_average=deterministic, name="bn")(x)
        return nn.Dense(
            self.labels, kernel_init=TRUNC_NORMAL, name="fc"
        )(x)
