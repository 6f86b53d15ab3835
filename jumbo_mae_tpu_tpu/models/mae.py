"""MAE decoder and the end-to-end pretraining model.

Parity targets: ``MAEDecoder`` (``/root/reference/src/modeling.py:276-298``)
and ``PretrainModule`` (``/root/reference/src/pretraining.py:76-122``).

Differences by design (defect ledger fixes, SURVEY.md appendix):

- the number of mask tokens is ``num_patches - keep_len`` (the reference
  recomputes ``int(N·mask_ratio)`` which can disagree — ledger item, §7);
- CLS-token slicing uses ``cfg.num_cls_tokens`` everywhere (the reference
  hardcodes ``3`` in its pretrain module);
- loss is computed in float32 regardless of compute dtype.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import initializers as init

from jumbo_mae_tpu_tpu.models.config import (
    DecoderConfig,
    JumboViTConfig,
    maybe_remat,
)
from jumbo_mae_tpu_tpu.models.layers import TRUNC_NORMAL, PlainBlock
from jumbo_mae_tpu_tpu.models.vit import JumboViT
from jumbo_mae_tpu_tpu.obs.trace import SCOPE_LOSS, SCOPE_MASK, SCOPE_PREPROCESS
from jumbo_mae_tpu_tpu.ops.masking import unshuffle_with_mask_tokens
from jumbo_mae_tpu_tpu.ops.patches import (
    extract_patches,
    patch_mse_loss_per_sample,
)
from jumbo_mae_tpu_tpu.ops.posemb import sincos2d_positional_embedding
from jumbo_mae_tpu_tpu.ops.preprocess import normalize_images


class MAEDecoder(nn.Module):
    """Lightweight ViT decoder over the unshuffled full sequence.

    Fixed sincos2d positional embeddings are added to the patch tokens
    (never to CLS), then ``cfg.layers`` plain pre-norm blocks and a final LN.
    """

    cfg: DecoderConfig
    grid: tuple[int, int]
    num_cls_tokens: int

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        deterministic: bool = True,
        *,
        blocks_override=None,
    ) -> jax.Array:
        """``blocks_override`` (optional callable ``tokens -> tokens``)
        replaces the sequential block chain — the same pipeline-parallel
        seam the encoder has (``JumboViT.__call__``), so the decoder stack
        can be depth-sharded over a ``pipe`` mesh axis too."""
        cfg = self.cfg
        k = self.num_cls_tokens
        pos = sincos2d_positional_embedding(*self.grid, cfg.dim).reshape(
            1, -1, cfg.dim
        )
        x = jnp.concatenate(
            [x[:, :k, :], x[:, k:, :] + jnp.asarray(pos, x.dtype)], axis=1
        )
        if blocks_override is not None:
            x = blocks_override(x)
        else:
            block_cls = maybe_remat(PlainBlock, cfg)
            for i in range(cfg.layers):
                x = block_cls(cfg, name=f"block_{i}")(x, deterministic)
        return nn.LayerNorm(dtype=cfg.compute_dtype, name="ln")(x)


class MAEPretrainModel(nn.Module):
    """uint8 images → masked-patch reconstruction loss.

    Pipeline: on-device normalize → JumboViT (MAE mode) → project to decoder
    width → insert learned mask tokens and unshuffle → MAEDecoder → per-patch
    pixel regression → masked MSE (optionally per-patch-normalized targets).
    """

    encoder_cfg: JumboViTConfig
    decoder_cfg: DecoderConfig
    norm_pix_loss: bool = False

    def setup(self):
        enc = self.encoder_cfg.replace(labels=None)
        if enc.mask_ratio is None:
            raise ValueError("encoder_cfg.mask_ratio is required for MAE pretraining")
        self.encoder = JumboViT(enc, name="encoder")
        self.mask_token = self.param(
            "mask_token", TRUNC_NORMAL, (1, 1, self.decoder_cfg.dim)
        )
        self.decoder_proj = nn.Dense(
            self.decoder_cfg.dim,
            kernel_init=TRUNC_NORMAL,
            dtype=self.decoder_cfg.compute_dtype,
            name="decoder_proj",
        )
        self.decoder = MAEDecoder(
            self.decoder_cfg,
            grid=enc.grid,
            num_cls_tokens=enc.num_cls_tokens,
            name="decoder",
        )
        self.pixel_proj = nn.Dense(
            self.encoder_cfg.patch_size**2 * 3,
            kernel_init=TRUNC_NORMAL,
            name="pixel_proj",
        )

    def __call__(
        self,
        images: jax.Array,
        deterministic: bool = True,
        return_reconstruction: bool = False,
        *,
        mask_noise: jax.Array | None = None,
        blocks_override=None,
        dec_blocks_override=None,
    ):
        enc_cfg = self.encoder_cfg
        k = enc_cfg.num_cls_tokens
        with jax.named_scope(SCOPE_PREPROCESS):
            images = normalize_images(images, dtype=enc_cfg.compute_dtype)

        tokens, mask, ids_restore = self.encoder(
            images,
            deterministic,
            mask_noise=mask_noise,
            blocks_override=blocks_override,
        )
        tokens = self.decoder_proj(tokens)
        with jax.named_scope(SCOPE_MASK):
            cls, visible = tokens[:, :k, :], tokens[:, k:, :]
        full = unshuffle_with_mask_tokens(visible, self.mask_token, ids_restore)
        with jax.named_scope(SCOPE_MASK):
            full = jnp.concatenate([cls, full], axis=1)
        decoded = self.decoder(
            full, deterministic, blocks_override=dec_blocks_override
        )
        with jax.named_scope(SCOPE_LOSS):
            patches = decoded[:, k:, :].astype(jnp.float32)
        pred = self.pixel_proj(patches)

        with jax.named_scope(SCOPE_LOSS):
            target = extract_patches(images.astype(jnp.float32), enc_cfg.patch_size)
            if self.norm_pix_loss:
                mean = target.mean(axis=-1, keepdims=True)
                var = target.var(axis=-1, keepdims=True)
                target = (target - mean) / jnp.sqrt(var + 1e-6)
            loss_per_sample = patch_mse_loss_per_sample(pred, target, mask)
            out = {"loss": loss_per_sample.mean(), "loss_per_sample": loss_per_sample}
        if return_reconstruction:
            out["reconstruction"] = pred
            out["mask"] = mask
        return out
