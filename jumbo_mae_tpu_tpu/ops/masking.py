"""Random patch masking for MAE pretraining.

Behavioral parity target: ``random_masking`` / ``index_sequence`` in
``/root/reference/src/utils_mae.py:84-102``. The reference draws ONE uniform
noise vector of shape ``(length,)`` — a single permutation shared by the whole
per-device batch (upstream facebookresearch/mae permutes per sample). Shared
mode is the parity default here; ``per_sample`` mode is also provided because
it is strictly stronger as an augmentation and costs one batched argsort.

TPU notes: the shuffle/unshuffle gathers have two selectable lowerings:

- ``impl="take"`` (default) — ``jnp.take``(_along_axis); XLA lowers to a
  dynamic gather, cheap at these sizes.
- ``impl="onehot"`` — the gather becomes a 0/1 one-hot matmul on the MXU
  (the north-star's "HBM-friendly gather/scatter", done the TPU way: the
  systolic array IS the hardware gather engine, and the unshuffle variant
  drops the concat so the full-sequence intermediate is written to HBM
  once instead of twice). Numerically EXACT in any dtype — multiplying by
  1.0 and summing zeros is lossless — so the two impls are
  bit-interchangeable; pick by profile (``BENCH_GATHER_IMPL``).
"""

from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from jumbo_mae_tpu_tpu.obs.trace import SCOPE_MASK

MaskMode = Literal["shared", "per_sample"]
GatherImpl = Literal["take", "onehot"]


# HIGHEST keeps f32 operands in full-precision MXU passes: the default
# precision would run bf16 passes and round f32 token values, breaking the
# bit-identical-to-take guarantee the A/B rests on. (For bf16 inputs it
# changes nothing — a 0/1 matmul has one nonzero product per output.)
_EXACT = jax.lax.Precision.HIGHEST


def _check_impl(impl: str) -> None:
    if impl not in ("take", "onehot"):
        raise ValueError(
            f"unknown gather impl {impl!r}; choose 'take' or 'onehot'"
        )


def index_sequence(
    x: jax.Array, ids: jax.Array, *, impl: GatherImpl = "take"
) -> jax.Array:
    """Gather along the sequence (second) axis.

    ``ids`` may be 1-D (shared permutation, applied to every batch row) or 2-D
    ``(batch, n)`` (per-sample permutation).
    """
    _check_impl(impl)
    if impl == "onehot":
        sel = jax.nn.one_hot(ids, x.shape[1], dtype=x.dtype)
        eq = "nk,bk...->bn..." if ids.ndim == 1 else "bnk,bk...->bn..."
        return jnp.einsum(eq, sel, x, precision=_EXACT)
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
    gather_impl: GatherImpl = "take",
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
        kept = index_sequence(x, ids_shuffle[:keep_len], impl=gather_impl)
        shuffled_mask = (jnp.arange(length) >= keep_len).astype(jnp.float32)
        mask = jnp.broadcast_to(shuffled_mask[ids_restore], (batch, length))
        return kept, mask, ids_restore

    if mode == "per_sample":
        if noise is None:
            noise = jax.random.uniform(rng, (batch, length), dtype=jnp.float32)
        ids_shuffle = jnp.argsort(noise, axis=1)
        ids_restore = jnp.argsort(ids_shuffle, axis=1)
        kept = index_sequence(x, ids_shuffle[:, :keep_len], impl=gather_impl)
        shuffled_mask = jnp.broadcast_to(
            (jnp.arange(length) >= keep_len).astype(jnp.float32), (batch, length)
        )
        mask = jnp.take_along_axis(shuffled_mask, ids_restore, axis=1)
        return kept, mask, ids_restore

    raise ValueError(f"unknown masking mode: {mode!r}")


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
    *,
    impl: GatherImpl = "take",
) -> jax.Array:
    """Restore the full sequence from visible tokens + a learned mask token.

    ``visible`` is ``(batch, keep_len, dim)``; ``mask_token`` broadcastable to
    ``(batch, length - keep_len, dim)``; ``ids_restore`` the inverse
    permutation from :func:`random_masking`. The number of mask tokens is
    derived as ``length - keep_len`` (the reference instead recomputes it as
    ``int(length * mask_ratio)``, which disagrees with ``keep_len`` for some
    ratios — ``/root/reference/src/pretraining.py:100-103``; fixed here).

    ``impl="onehot"`` skips the concat entirely: output rows whose restore
    index lands in the visible range come from a (length, keep_len) 0/1
    matmul against ``visible`` on the MXU; the rest add the broadcast mask
    token — the full-length intermediate is written once, not twice.
    """
    batch, keep_len, dim = visible.shape
    length = ids_restore.shape[-1]
    _check_impl(impl)
    if impl == "onehot":
        # rows selecting a masked slot have an all-zero one-hot row (index
        # >= keep_len matches nothing), so the matmul contributes 0 there
        # and the mask-token term fills it in
        sel = jax.nn.one_hot(ids_restore, keep_len, dtype=visible.dtype)
        eq = "nk,bkd->bnd" if ids_restore.ndim == 1 else "bnk,bkd->bnd"
        from_visible = jnp.einsum(eq, sel, visible, precision=_EXACT)
        masked = (ids_restore >= keep_len).astype(visible.dtype)[..., :, None]
        token = jnp.asarray(mask_token, visible.dtype).reshape(1, 1, dim)
        return from_visible + masked * token
    mask_tokens = jnp.broadcast_to(mask_token, (batch, length - keep_len, dim))
    full = jnp.concatenate([visible, mask_tokens.astype(visible.dtype)], axis=1)
    return index_sequence(full, ids_restore)
