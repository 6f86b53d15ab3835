"""Memory-efficient attention for long sequences, in two forms.

**Non-causal**, ``flash_attention(q, k, v)`` over (B, N, H, D) tensors (the
ViT's): the reference materializes full (B,H,N,N) score tensors
(``/root/reference/src/modeling.py:136-137``) — fine at N=197, fatal for
long-context.

- on TPU, a Pallas blockwise-softmax kernel (``ops/pallas/attention.py``)
  that never materializes the N×N score matrix in HBM — any sequence length
  (the kernel pads to lane tiles and masks pad keys internally);
- elsewhere, an XLA fallback that is numerically identical to the naive
  path (blockwise-chunked above 2048 tokens).

**Causal**, ``causal_attention(q_a, q_b, k_a, k_b, v, impl=, window=)`` over
head-major (B, H, N, D) tensors (the language models'): a score of one part
or of two (the second with a key all heads share: latent attention's rotary
columns), key/value heads that a group of query heads shares, and an optional
window of tokens a query looks back over. ``impl="flash"`` is the Pallas
kernels over the visible block pairs (``ops/pallas/attention.py``'s causal
section), ``"einsum"`` the form that builds the (N, N) scores: the CPU's.

Inputs are expected pre-scaled (queries already multiplied by head_dim**-0.5,
matching the callers in ``models/layers.py`` and ``models/lm.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """Softmax(q·kᵀ)·v without materializing the score matrix.

    q, k, v: (batch, seq, heads, head_dim). Returns the same shape as q.
    Block defaults follow ``pallas_flash_attention`` (big requests, clamped
    per shape — see its docstring for the round-5 measurements).
    """
    seq_q, seq_k = q.shape[1], k.shape[1]
    if jax.default_backend() != "tpu":
        if max(seq_q, seq_k) >= 2048:
            from jumbo_mae_tpu_tpu.ops.blockwise_attention import (
                blockwise_attention,
            )

            return blockwise_attention(q, k, v, block_k=min(block_k, seq_k))
        return xla_attention(q, k, v)
    from jumbo_mae_tpu_tpu.ops.pallas.attention import pallas_flash_attention

    return pallas_flash_attention(q, k, v, block_q, block_k)


def xla_causal_attention(q_a, q_b, k_a, k_b, v, window: int | None = None) -> jax.Array:
    """The einsum form of :func:`causal_attention`: the (seq, seq) scores
    exist, so it is for the CPU's tests and short sequences only."""
    group = q_a.shape[1] // k_a.shape[1]
    if group > 1:  # each key/value head once a query head of its group
        k_a, v = jnp.repeat(k_a, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q_a, k_a, preferred_element_type=jnp.float32)
    if q_b is not None:
        s = s + jnp.einsum("bhqd,bkd->bhqk", q_b, k_b, preferred_element_type=jnp.float32)
    keep = jnp.tril(jnp.ones(s.shape[-2:], bool))
    if window is not None:
        keep = keep & ~jnp.tril(keep, -window)  # row − col < window
    probs = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def causal_attention(q_a, q_b, k_a, k_b, v, *, impl: str, window: int | None = None) -> jax.Array:
    """Causal softmax(q_a·k_aᵀ + q_b·k_bᵀ)·v, head-major: ``q_a`` (batch,
    heads, seq, d_a), ``k_a`` (batch, kv heads, seq, d_a) and ``v`` (batch, kv
    heads, seq, d_v) with ``kv heads`` a divisor of ``heads`` (query head ``h``
    reads key/value head ``h // (heads / kv heads)``); ``q_b`` (batch, heads,
    seq, d_b) and ``k_b`` (batch, seq, d_b) shared by all heads, or both None
    for a score of one part; queries pre-scaled. With ``window``, query ``i``
    sees keys ``i − window + 1 .. i``. ``impl`` is ``"flash"`` (the Pallas
    kernels, scores never materialised) or ``"einsum"``, as
    ``resolve_attn_impl`` resolved it."""
    if impl == "flash":
        from jumbo_mae_tpu_tpu.ops.pallas.attention import pallas_causal_attention

        return pallas_causal_attention(q_a, q_b, k_a, k_b, v, window=window)
    return xla_causal_attention(q_a, q_b, k_a, k_b, v, window)
