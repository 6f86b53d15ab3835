"""Memory-efficient attention for long sequences.

The reference materializes full (B,H,N,N) score tensors
(``/root/reference/src/modeling.py:136-137``) — fine at N=197, fatal for
long-context. This module provides ``flash_attention(q, k, v)`` over
(B, N, H, D) tensors:

- on TPU, a Pallas blockwise-softmax kernel (``ops/pallas/attention.py``)
  that never materializes the N×N score matrix in HBM — any sequence length
  (the kernel pads to lane tiles and masks pad keys internally);
- elsewhere, an XLA fallback that is numerically identical to the naive
  path (blockwise-chunked above 2048 tokens).

Inputs are expected pre-scaled (queries already multiplied by head_dim**-0.5,
matching the callers in ``models/layers.py``).
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


def xla_causal_attention(q_a, q_b, k_a, k_b, v) -> jax.Array:
    """The einsum form of :func:`causal_attention`: the (seq, seq) scores
    exist, so it is for the CPU's tests and short sequences only."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q_a, k_a, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhqd,bkd->bhqk", q_b, k_b, preferred_element_type=jnp.float32)
    keep = jnp.tril(jnp.ones(s.shape[-2:], bool))
    probs = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def causal_attention(q_a, q_b, k_a, k_b, v, *, impl: str) -> jax.Array:
    """Causal softmax(q_a·k_aᵀ + q_b·k_bᵀ)·v, head-major: ``q_a``/``k_a``
    (batch, heads, seq, d_a), ``q_b`` (batch, heads, seq, d_b), ``k_b``
    (batch, seq, d_b) shared by all heads, ``v`` (batch, heads, seq, d_v);
    queries pre-scaled. ``impl`` is ``"flash"`` (the Pallas kernels, scores
    never materialised) or ``"einsum"``, as ``resolve_attn_impl`` resolved it."""
    if impl == "flash":
        from jumbo_mae_tpu_tpu.ops.pallas.attention import pallas_causal_attention

        return pallas_causal_attention(q_a, q_b, k_a, k_b, v)
    return xla_causal_attention(q_a, q_b, k_a, k_b, v)
