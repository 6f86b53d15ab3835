"""Random patch masking for MAE pretraining.

Behavioral parity target: ``random_masking`` / ``index_sequence`` in
``/root/reference/src/utils_mae.py:84-102``. The reference draws ONE uniform
noise vector of shape ``(length,)`` — a single permutation shared by the whole
per-device batch (upstream facebookresearch/mae permutes per sample). Shared
mode is the parity default here; ``per_sample`` mode is also provided because
it is strictly stronger as an augmentation and costs one batched argsort.

TPU notes: the shuffle/unshuffle gathers are ``jnp.take``(_along_axis); XLA
lowers them to a dynamic gather, cheap at these sizes.
"""

from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from jumbo_mae_tpu_tpu.obs.trace import SCOPE_MASK

MaskMode = Literal["shared", "per_sample"]


def index_sequence(x: jax.Array, ids: jax.Array) -> jax.Array:
    """Gather along the sequence (second) axis.

    ``ids`` may be 1-D (shared permutation, applied to every batch row) or 2-D
    ``(batch, n)`` (per-sample permutation).
    """
    if ids.ndim == 1:
        return jnp.take(x, ids, axis=1)
    idx = ids.reshape(ids.shape + (1,) * (x.ndim - 2))
    return jnp.take_along_axis(x, idx, axis=1)


@jax.named_scope(SCOPE_MASK)
def random_masking(
    x: jax.Array,
    rng: jax.Array | None,
    keep_len: int,
    *,
    mode: MaskMode = "shared",
    noise: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Randomly drop all but ``keep_len`` tokens of ``x`` (batch, len, dim).

    Returns ``(kept, mask, ids_restore)`` where ``kept`` is
    ``(batch, keep_len, dim)``, ``mask`` is ``(batch, len)`` float32 with 1 at
    MASKED positions, and ``ids_restore`` inverts the shuffle (1-D in shared
    mode, 2-D in per-sample mode).

    ``noise`` optionally overrides the drawn uniform noise (shape ``(len,)``
    shared / ``(batch, len)`` per-sample) so a caller can pin the permutation
    — used for fixed eval masks and cross-implementation parity tests; ``rng``
    may then be None.
    """
    batch, length, _ = x.shape
    expected = (length,) if mode == "shared" else (batch, length)
    if noise is not None and noise.shape != expected:
        raise ValueError(
            f"injected noise shape {noise.shape} != {expected} for "
            f"mode={mode!r}"
        )
    if mode == "shared":
        if noise is None:
            noise = jax.random.uniform(rng, (length,), dtype=jnp.float32)
        ids_shuffle = jnp.argsort(noise)
        ids_restore = jnp.argsort(ids_shuffle)
        kept = index_sequence(x, ids_shuffle[:keep_len])
        shuffled_mask = (jnp.arange(length) >= keep_len).astype(jnp.float32)
        mask = jnp.broadcast_to(shuffled_mask[ids_restore], (batch, length))
        return kept, mask, ids_restore

    if mode == "per_sample":
        if noise is None:
            noise = jax.random.uniform(rng, (batch, length), dtype=jnp.float32)
        ids_shuffle = jnp.argsort(noise, axis=1)
        ids_restore = jnp.argsort(ids_shuffle, axis=1)
        kept = index_sequence(x, ids_shuffle[:, :keep_len])
        shuffled_mask = jnp.broadcast_to(
            (jnp.arange(length) >= keep_len).astype(jnp.float32), (batch, length)
        )
        mask = jnp.take_along_axis(shuffled_mask, ids_restore, axis=1)
        return kept, mask, ids_restore

    raise ValueError(f"unknown masking mode: {mode!r}")


BLOCK_NOISE_EPS = 1e-3  # the least noise level a diffusion block draws (SDAR's, BD3-LMs')


def block_noise(rng: jax.Array, batch: int, length: int, block: int,
                eps: float = BLOCK_NOISE_EPS) -> tuple[jax.Array, jax.Array]:
    """Block diffusion's noise for ``batch`` sequences of ``length`` tokens cut
    into blocks of ``block``: a level ``t ~ U[eps, 1]`` a (sequence, block),
    and each token of the block masked independently with probability ``t``.
    Returns ``(t, masked)``: (batch, length / block) float32 and (batch,
    length) bool. ``rng`` is split once: the first key draws the levels, the
    second one uniform a token, masked where it is below its block's level."""
    level_key, token_key = jax.random.split(rng)
    t = jax.random.uniform(level_key, (batch, length // block), jnp.float32, eps, 1.0)
    u = jax.random.uniform(token_key, (batch, length), jnp.float32)
    return t, u < jnp.repeat(t, block, axis=1)


# --------------------------------------------------------------------------
# Mask algebra (parity: ``/root/reference/src/utils_mae.py:24-49``). Masks are
# float arrays with 1.0 at MASKED positions. The reference fork never calls
# these itself (they come from its m3ae ancestry), but they complete the
# utils_mae surface for users combining masks — e.g. masking the union of an
# MAE mask and a padding mask.
# --------------------------------------------------------------------------


def no_mask(x: jax.Array) -> jax.Array:
    """All-zeros (nothing masked) mask for a (batch, len, ...) sequence."""
    return jnp.zeros(x.shape[:2], dtype=jnp.float32)


def all_mask(x: jax.Array) -> jax.Array:
    """All-ones (everything masked) mask for a (batch, len, ...) sequence."""
    return jnp.ones(x.shape[:2], dtype=jnp.float32)


def mask_not(mask: jax.Array) -> jax.Array:
    """``1.0 - mask`` — exact reference semantics: unlike union/intersection
    (which binarize with the reference's ``>0`` contract), the reference's
    complement is pure arithmetic, so a soft 0.3 inverts to 0.7."""
    return 1.0 - mask.astype(jnp.float32)


def mask_union(*masks: jax.Array) -> jax.Array:
    """Positions masked (>0) in ANY input mask; output is binary 0/1 like the
    reference's helpers, so soft/weighted inputs collapse rather than
    propagate."""
    out = (masks[0] > 0)
    for m in masks[1:]:
        out = out | (m > 0)
    return out.astype(jnp.float32)


def mask_intersection(*masks: jax.Array) -> jax.Array:
    """Positions masked (>0) in EVERY input mask; binary 0/1 output."""
    out = (masks[0] > 0)
    for m in masks[1:]:
        out = out & (m > 0)
    return out.astype(jnp.float32)


def mask_select(
    mask: jax.Array, when_unmasked: jax.Array, when_masked: jax.Array
) -> jax.Array:
    """Elementwise choose ``when_unmasked`` where mask==0 else
    ``when_masked`` — the reference's argument order (second argument is the
    UNMASKED value). The mask broadcasts over trailing feature axes."""
    m = mask.reshape(mask.shape + (1,) * (when_unmasked.ndim - mask.ndim))
    return jnp.where(m > 0, when_masked, when_unmasked)


@jax.named_scope(SCOPE_MASK)
def unshuffle_with_mask_tokens(
    visible: jax.Array,
    mask_token: jax.Array,
    ids_restore: jax.Array,
) -> jax.Array:
    """Restore the full sequence from visible tokens + a learned mask token.

    ``visible`` is ``(batch, keep_len, dim)``; ``mask_token`` broadcastable to
    ``(batch, length - keep_len, dim)``; ``ids_restore`` the inverse
    permutation from :func:`random_masking`. The number of mask tokens is
    derived as ``length - keep_len`` (the reference instead recomputes it as
    ``int(length * mask_ratio)``, which disagrees with ``keep_len`` for some
    ratios — ``/root/reference/src/pretraining.py:100-103``; fixed here).
    """
    batch, keep_len, dim = visible.shape
    length = ids_restore.shape[-1]
    mask_tokens = jnp.broadcast_to(mask_token, (batch, length - keep_len, dim))
    full = jnp.concatenate([visible, mask_tokens.astype(visible.dtype)], axis=1)
    return index_sequence(full, ids_restore)
