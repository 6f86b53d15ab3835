"""The Jumbo ViT encoder.

Parity: ``ViT``, ``/root/reference/src/modeling.py:221-274``. One module
serves three modes:

- **MAE mode** (``cfg.mask_ratio`` set, ``cfg.labels`` None/0): after patch
  embedding and CLS prepending, patch tokens are randomly masked and only the
  visible ones are encoded. Returns ``(tokens, mask, ids_restore)``.
- **classify mode** (``cfg.labels > 0``): full sequence encoded; the
  ``num_cls_tokens`` CLS embeddings are concatenated and fed to the linear
  head. ``cfg.linear_probing`` stops gradients into the trunk;
  ``cfg.batch_norm`` enables the probe-head BatchNorm.
- **feature mode** (``cfg.labels`` None and no mask_ratio): returns the
  normalized token sequence (useful for downstream / conversion tests).

The shared ``jumbo_mlp`` (width k·dim) is built once here and passed to every
block — the weight sharing is the defining property of the architecture.
Its two kernels' gradient is formed here too, once a step: ``__call__`` opens
one pair of slots a layer before the block loop (``JumboMlp.open_slots``),
each block's backward pass leaves its CLS rows and their cotangents in its
pair, and after ``block_0``'s the two products over all layers' rows run
(``ops/shared_grad.py``). A program that never differentiates drops the slots
as dead code.
Gradient checkpointing wraps each block with ``nn.remat`` (deterministic flag
static). The reference's ``pooling`` flag was parsed but ignored
(defect ledger #3); here ``pooling="gap"`` is actually implemented.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import initializers as init

from jumbo_mae_tpu_tpu.models.config import JumboViTConfig, maybe_remat
from jumbo_mae_tpu_tpu.models.layers import (
    ClassifierHead,
    JumboBlock,
    PatchEmbed,
    make_jumbo_mlp,
    segment_attention_mask,
)
from jumbo_mae_tpu_tpu.ops.masking import random_masking


def pool_tokens(tokens: jax.Array, num_cls_tokens: int, pooling: str = "cls"):
    """The probe/head representation: ``"cls"`` concatenates the
    ``num_cls_tokens`` CLS embeddings (parity:
    ``/root/reference/src/modeling.py:269-274``); ``"gap"`` mean-pools the
    patch tokens. Shared by :class:`JumboViT` and
    ``tools/extract_features.py`` so the exported features can never drift
    from what the in-train heads consume."""
    if pooling == "gap":
        return tokens[:, num_cls_tokens:, :].mean(axis=1)
    return tokens[:, :num_cls_tokens, :].reshape(tokens.shape[0], -1)


class JumboViT(nn.Module):
    cfg: JumboViTConfig

    def setup(self):
        cfg = self.cfg
        self.embed = PatchEmbed(cfg, name="embed")
        self.cls_tokens = self.param(
            "cls_tokens", init.zeros, (1, cfg.num_cls_tokens, cfg.dim)
        )
        self.jumbo_mlp = make_jumbo_mlp(cfg)
        block_cls = maybe_remat(JumboBlock, cfg)
        self.blocks = [
            block_cls(cfg, self.jumbo_mlp, name=f"block_{i}")
            for i in range(cfg.layers)
        ]
        self.norm = nn.LayerNorm(dtype=cfg.compute_dtype, name="ln")
        self.drop = nn.Dropout(cfg.dropout)
        self.head = (
            ClassifierHead(cfg.labels, cfg.batch_norm, name="head")
            if (cfg.labels or 0) > 0
            else None
        )

    @property
    def mae_mode(self) -> bool:
        return self.head is None and self.cfg.mask_ratio is not None

    def __call__(
        self,
        images: jax.Array,
        deterministic: bool = True,
        *,
        mask_noise: jax.Array | None = None,
        blocks_override=None,
    ):
        """``blocks_override`` (optional callable ``tokens -> tokens``)
        replaces the sequential block chain — the seam the pipeline-parallel
        train step uses to run the same ``block_*`` parameters through the
        GPipe schedule (``parallel/pipeline.py``) instead of the Python
        loop. The override closes over the parameter tree at the step level,
        so gradients flow through it unchanged."""
        cfg = self.cfg
        k = cfg.num_cls_tokens
        x = self.embed(images)
        bs = x.shape[0]

        mask = ids_restore = None
        if self.mae_mode:
            rng = None if mask_noise is not None else self.make_rng("noise")
            x, mask, ids_restore = random_masking(
                x,
                rng,
                cfg.keep_len,
                mode=cfg.mask_mode,
                noise=mask_noise,
            )

        cls = jnp.broadcast_to(
            jnp.asarray(self.cls_tokens, x.dtype), (bs, k, cfg.dim)
        )
        x = jnp.concatenate([cls, x], axis=1)
        x = self.drop(x, deterministic)

        if blocks_override is not None:
            x = blocks_override(x)
        else:
            # each block gets only its own layer's slots, so that what a
            # rematted block keeps of its inputs is one layer's zeros
            slots = self.jumbo_mlp.open_slots(cfg.layers, bs)
            for block, layer_slots in zip(self.blocks, slots):
                x = block(x, deterministic, None, layer_slots)
        x = self.norm(x)

        if self.mae_mode:
            return x, mask, ids_restore

        if self.head is None:
            return x

        if cfg.linear_probing:
            x = jax.lax.stop_gradient(x)

        pooled = pool_tokens(x, k, cfg.pooling)
        return self.head(pooled.astype(jnp.float32), deterministic)

    # ------------------------------------------------- token-packed serving

    def patchify(self, images: jax.Array) -> jax.Array:
        """Patch embedding only (conv + posemb), (B, N, dim) — the packed
        serving path embeds each request at its own resolution, then packs
        the resulting token segments into one buffer. CLS tokens are NOT
        prepended here: the positional embedding applies to patches only
        in this architecture, so CLS injection can happen inside the packed
        executable (see :meth:`encode_packed`) with identical numerics."""
        return self.embed(images)

    def encode_packed(
        self,
        tokens: jax.Array,
        segment_ids: jax.Array,
        cls_pos: jax.Array,
        cls_index: jax.Array,
        deterministic: bool = True,
    ) -> jax.Array:
        """Run the block stack over a token-packed buffer.

        ``tokens`` is (rows, budget, dim) — already patch-embedded, zeros
        at CLS slots and padding. ``segment_ids``/``cls_pos``/``cls_index``
        are the :mod:`~jumbo_mae_tpu_tpu.infer.packing` plan arrays. The
        CLS parameter is injected at each segment's ``cls_pos`` slots;
        attention is block-diagonal per segment; every other op is
        per-token — so each segment computes exactly what its own unpacked
        batch row would."""
        cfg = self.cfg
        x = tokens.astype(cfg.compute_dtype)
        cls = jnp.asarray(self.cls_tokens, x.dtype)[0]  # (k, dim)
        x = jnp.where(cls_pos[..., None] >= 0, cls[jnp.clip(cls_pos, 0)], x)
        x = self.drop(x, deterministic)
        packed = {
            "mask": segment_attention_mask(segment_ids),
            "segment_ids": segment_ids,
            "cls_pos": cls_pos,
            "cls_index": cls_index,
        }
        for block in self.blocks:
            x = block(x, deterministic, packed)
        return self.norm(x)

    def pool_packed(
        self,
        tokens: jax.Array,
        segment_ids: jax.Array,
        cls_pos: jax.Array,
        cls_index: jax.Array,
        pooling: str = "cls",
    ) -> jax.Array:
        """Per-segment :func:`pool_tokens`: (rows, max_segments, k·dim)
        for ``"cls"``, (rows, max_segments, dim) for ``"gap"``. Unoccupied
        slots pool garbage (slot 0's tokens / zero counts clamped to 1) —
        callers slice results by the pack plan, so those never escape."""
        cfg = self.cfg
        k = cfg.num_cls_tokens
        rows, _, dim = tokens.shape
        smax = cls_index.shape[1]
        if pooling == "gap":
            slot = jnp.arange(1, smax + 1, dtype=segment_ids.dtype)
            own = (segment_ids[:, None, :] == slot[None, :, None]) & (
                cls_pos[:, None, :] < 0
            )
            w = own.astype(tokens.dtype)
            sums = jnp.einsum("rsl,rld->rsd", w, tokens)
            counts = jnp.maximum(w.sum(axis=-1), 1.0)
            return sums / counts[..., None]
        g = jnp.take_along_axis(
            tokens, cls_index.reshape(rows, smax * k)[..., None], axis=1
        )
        return g.reshape(rows, smax, k * dim)

    def serve_packed(
        self,
        tokens: jax.Array,
        segment_ids: jax.Array,
        cls_pos: jax.Array,
        cls_index: jax.Array,
        deterministic: bool = True,
        *,
        pooling: str = "cls",
    ) -> dict[str, jax.Array]:
        """The packed serving forward: encode, pool per segment, and (when
        the model has a head) classify — ``{"pooled": ..., "logits": ...}``
        so features and logits requests ride one executable."""
        x = self.encode_packed(
            tokens, segment_ids, cls_pos, cls_index, deterministic
        )
        pooled = self.pool_packed(x, segment_ids, cls_pos, cls_index, pooling)
        out = {"pooled": pooled.astype(jnp.float32)}
        if self.head is not None:
            head_in = (
                pooled
                if pooling == self.cfg.pooling
                else self.pool_packed(
                    x, segment_ids, cls_pos, cls_index, self.cfg.pooling
                )
            )
            out["logits"] = self.head(
                head_in.astype(jnp.float32), deterministic
            ).astype(jnp.float32)
        return out

    def serve_full(
        self,
        images: jax.Array,
        deterministic: bool = True,
        *,
        pooling: str = "cls",
    ) -> dict[str, jax.Array]:
        """Unpacked mirror of :meth:`serve_packed` — same output contract
        from a plain image batch. This is the packed path's per-request
        parity oracle (it also serves non-native resolutions, which the
        bucketed ``__call__`` path rejects)."""
        cfg = self.cfg
        k = cfg.num_cls_tokens
        x = self.embed(images)
        bs = x.shape[0]
        cls = jnp.broadcast_to(
            jnp.asarray(self.cls_tokens, x.dtype), (bs, k, cfg.dim)
        )
        x = jnp.concatenate([cls, x], axis=1)
        x = self.drop(x, deterministic)
        for block in self.blocks:
            x = block(x, deterministic)
        x = self.norm(x)
        out = {"pooled": pool_tokens(x, k, pooling).astype(jnp.float32)}
        if self.head is not None:
            head_in = pool_tokens(x, k, cfg.pooling)
            out["logits"] = self.head(
                head_in.astype(jnp.float32), deterministic
            ).astype(jnp.float32)
        return out
