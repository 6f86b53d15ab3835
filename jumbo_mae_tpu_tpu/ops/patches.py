"""Patchification and the masked-patch MSE loss.

Behavioral parity target: ``extract_patches`` / ``merge_patches`` /
``patch_mse_loss`` in ``/root/reference/src/utils_mae.py:51-82``. Pure
reshape/transpose — XLA fuses these into the surrounding program; no Pallas
needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jumbo_mae_tpu_tpu.obs.trace import SCOPE_LOSS, SCOPE_PATCHIFY


@jax.named_scope(SCOPE_PATCHIFY)
def extract_patches(images: jax.Array, patch_size: int) -> jax.Array:
    """(B, H, W, C) → (B, H/p · W/p, p²·C), row-major patch order."""
    b, h, w, c = images.shape
    gh, gw = h // patch_size, w // patch_size
    x = images.reshape(b, gh, patch_size, gw, patch_size, c)
    x = x.swapaxes(2, 3)
    return x.reshape(b, gh * gw, patch_size * patch_size * c)


def merge_patches(patches: jax.Array, patch_size: int) -> jax.Array:
    """(B, N, p²·C) → (B, H, W, C); inverse of :func:`extract_patches` for a
    square grid (N must be a perfect square)."""
    b, n, _ = patches.shape
    g = int(round(n**0.5))
    x = patches.reshape(b, g, g, patch_size, patch_size, -1)
    x = x.swapaxes(2, 3)
    return x.reshape(b, g * patch_size, g * patch_size, -1)


@jax.named_scope(SCOPE_LOSS)
def patch_mse_loss_per_sample(
    output: jax.Array, target: jax.Array, mask: jax.Array | None = None
) -> jax.Array:
    """(B,) mean-squared error over MASKED patches only, per sample.

    ``mask`` is (B, N) with 1 at masked positions; the per-sample mean over
    patches is divided by the masked ratio so the result is the mean over
    masked patches. With ``mask=None`` this degrades to a plain per-sample MSE.
    """
    per_patch = jnp.mean(jnp.square(target - output), axis=-1)
    if mask is None:
        return jnp.mean(per_patch, axis=-1)
    masked_ratio = jnp.sum(mask, axis=-1) / mask.shape[-1]
    per_sample = jnp.mean(jnp.where(mask > 0.0, per_patch, 0.0), axis=-1)
    return per_sample / masked_ratio


def patch_mse_loss(
    output: jax.Array, target: jax.Array, mask: jax.Array | None = None
) -> jax.Array:
    """Scalar batch mean of :func:`patch_mse_loss_per_sample`."""
    return jnp.mean(patch_mse_loss_per_sample(output, target, mask))
