"""Ring attention: sequence/context parallelism over a mesh axis.

The reference had no long-context story at all (SURVEY §5 — attention
materializes (B,H,N,N) on one device, ``/root/reference/src/modeling.py:136-137``).
Here sequences shard over the ``seq`` mesh axis; each device holds a local
query block and the K/V blocks ROTATE around the ring via ``ppermute`` over
ICI neighbors, one hop per step, while a running online-softmax (m, l, acc)
merges each visiting block — exactly one full pass of K/V past every Q shard
in ``seq_parallel`` hops, with O(S/n) memory per device and compute that
overlaps the next hop's transfer (the collective-permute is issued before the
block's einsums, so XLA can run them concurrently).

API:
- :func:`ring_attention` — per-shard body (call inside ``shard_map``);
- :func:`ring_attention_sharded` — convenience wrapper that builds the
  ``shard_map`` over a mesh for globally-(B, S, H, D) inputs sharded on S.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: jax.Array | None = None,
    *,
    axis_name: str,
    inner: str = "einsum",
    interpret: bool = False,
) -> jax.Array:
    """Online-softmax attention with K/V ring rotation over ``axis_name``.

    Shapes (per shard): (batch, local_seq, heads, head_dim); queries
    pre-scaled. ``kv_mask`` is an optional (batch, local_seq) bool marking
    which local *keys* are real — it rotates around the ring with its K/V
    block, so padded tokens (uneven sequence splits) never receive weight.
    Must run inside ``shard_map``/``pmap`` with ``axis_name`` bound. Returns
    the local query block's exact global attention output.

    ``inner="flash"`` computes each hop's local block with the Pallas
    flash kernels (``ops/pallas/attention.py``) and merges hops in
    log-sum-exp space — per-device score memory drops from
    O((S/n)²) to O(S/n), the right memory class for exactly the
    long-context regime ring attention targets (and the kernels are
    faster than einsum at those chunk lengths — PERF_ARCHIVE.md §Decisions 1).
    Requires ``kv_mask=None`` (even splits): the kernels mask trailing
    pad only, not arbitrary key masks.
    """
    if inner == "flash":
        if kv_mask is not None:
            raise ValueError(
                "inner='flash' supports even sequence splits only "
                "(kv_mask must be None — pad-free sharding)"
            )
        if jax.default_backend() == "tpu" or interpret:
            return _ring_attention_flash(
                q, k, v, axis_name=axis_name, interpret=interpret
            )
        # off-TPU there are no Mosaic kernels; silently running the Pallas
        # INTERPRETER would be orders of magnitude slower than the einsum
        # inner — fall back to it (``interpret=True`` keeps the kernel path
        # for CPU tests).
    n = jax.lax.psum(1, axis_name)
    bq, sq, h, d = q.shape

    m0 = jnp.full((bq, h, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, h, sq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, sq, h, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    masked = kv_mask is not None
    # The bias joins the scan carry and ring-rotates with its K/V block —
    # only pay that extra ppermute when a mask actually exists.
    bias0 = (
        jnp.where(kv_mask, 0.0, NEG_INF)[:, None, None, :] if masked else None
    )  # (b,1,1,k)

    def hop(carry, _):
        m, l, acc, k_cur, v_cur, bias = carry
        # issue the rotation FIRST so the compiler MAY overlap the transfer
        # with this block's math (standard ring-attention scheduling; actual
        # ICI/compute overlap is up to XLA's scheduler and has not been
        # profiled on multi-chip hardware — this sandbox has one chip, so
        # only the numerics/gradients of the ring are verified here)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        bias_nxt = (
            jax.lax.ppermute(bias, axis_name, perm) if masked else None
        )

        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_cur, preferred_element_type=jnp.float32
        )
        if masked:
            s = s + bias
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1, keepdims=True)
        acc = acc * alpha.transpose(0, 2, 1, 3) + jnp.einsum(
            "bhqk,bkhd->bqhd",
            p.astype(v_cur.dtype),
            v_cur,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l, acc, k_nxt, v_nxt, bias_nxt), None

    (m, l, acc, *_), _ = jax.lax.scan(
        hop, (m0, l0, acc0, k, v, bias0), None, length=n
    )
    return (acc / l.transpose(0, 2, 1, 3)).astype(q.dtype)


def _ring_attention_flash(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    interpret: bool = False,
) -> jax.Array:
    """Flash-kernel hop body for :func:`ring_attention` (``inner="flash"``).

    Each visiting K/V block is attended with the O(chunk)-memory Pallas
    kernels via :func:`pallas_flash_attention_with_lse` — DIFFERENTIABLE
    in both outputs, so autodiff through the merge below produces the lse
    cotangents the weights depend on (a stopped-lse merge would silently
    drop the softmax-denominator gradient path). Hops combine in lse
    space: with ``out_h`` softmax-normalized over its block and
    ``exp(lse_h) = Σ_j exp(s_j)``, the running ``(out, lse)`` pair merges
    as a two-way log-sum-exp — numerically stable and exact. Per-device
    score memory is O(local_seq), the memory class ring attention exists
    for; the kernels are also faster than einsum at long chunk lengths
    (PERF_ARCHIVE.md §Decisions 1).
    """
    from jumbo_mae_tpu_tpu.ops.pallas.attention import (
        pallas_flash_attention_with_lse,
    )

    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    bq, sq, h, d = q.shape

    def hop(carry, _):
        out, lse, k_cur, v_cur = carry
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        out_h, lse_h = pallas_flash_attention_with_lse(
            q, k_cur, v_cur, 1024, 1024, interpret
        )
        lse_h = lse_h.reshape(bq, h, sq).transpose(0, 2, 1)[..., None]
        m_new = jnp.maximum(lse, lse_h)  # (b, sq, h, 1)
        w_prev = jnp.exp(lse - m_new)
        w_h = jnp.exp(lse_h - m_new)
        denom = w_prev + w_h
        out = out * (w_prev / denom) + out_h.astype(jnp.float32) * (
            w_h / denom
        )
        lse = m_new + jnp.log(denom)
        return (out, lse, k_nxt, v_nxt), None

    out0 = jnp.zeros((bq, sq, h, d), jnp.float32)
    lse0 = jnp.full((bq, sq, h, 1), NEG_INF, jnp.float32)
    (out, _, _, _), _ = jax.lax.scan(hop, (out0, lse0, k, v), None, length=n)
    return out.astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    seq_axis: str = "seq",
    batch_axes=("data", "fsdp"),
    inner: str = "einsum",
    interpret: bool = False,
) -> jax.Array:
    """Explicit-mesh alias of :func:`ring_self_attention`: global
    (B, S, H, D) inputs with S sharded over ``seq_axis`` (and batch over
    ``batch_axes``); emits the identically sharded attention output."""
    return ring_self_attention(
        q, k, v, seq_axis=seq_axis, batch_axes=batch_axes, mesh=mesh,
        inner=inner, interpret=interpret,
    )


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    seq_axis: str = "seq",
    batch_axes=("data", "fsdp"),
    mesh: Mesh | None = None,
    inner: str = "einsum",
    interpret: bool = False,
) -> jax.Array:
    """Sequence-parallel self-attention, for use inside model code under
    ``jit``. Uses the *ambient* mesh by default (activate with
    ``jax.sharding.set_mesh``) or an explicitly passed ``mesh``. Handles
    sequence lengths that don't divide the ``seq`` axis by zero-padding K/V
    and masking the pad keys (the mask ring-rotates with its block). Falls
    back to plain attention when no mesh is active or its ``seq`` axis is
    trivial.

    q, k, v: (batch, seq, heads, head_dim), queries pre-scaled.
    """
    shape = (mesh or jax.sharding.get_abstract_mesh()).shape
    n = shape.get(seq_axis, 1)
    if not n or n <= 1:
        from jumbo_mae_tpu_tpu.ops.attention import xla_attention

        return xla_attention(q, k, v)

    b, s, h, d = q.shape
    s_pad = -(-s // n) * n
    pad = s_pad - s
    bspec = tuple(a for a in batch_axes if shape.get(a, 1) > 1) or None
    qkv_spec = P(bspec, seq_axis, None, None)
    if not pad:
        return jax.shard_map(
            partial(
                ring_attention,
                axis_name=seq_axis,
                inner=inner,
                interpret=interpret,
            ),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec,
            check_vma=False,
        )(q, k, v)
    if inner == "flash":
        raise ValueError(
            "inner='flash' requires the sequence length to divide the "
            f"'{seq_axis}' axis ({s} over {n} shards needs padding, and "
            "the flash kernels mask trailing pad only)"
        )
    widths = ((0, 0), (0, pad), (0, 0), (0, 0))
    q, k, v = (jnp.pad(x, widths) for x in (q, k, v))
    kv_mask = jnp.broadcast_to(jnp.arange(s_pad) < s, (b, s_pad))
    out = jax.shard_map(
        partial(ring_attention, axis_name=seq_axis),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, P(bspec, seq_axis)),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v, kv_mask)
    return out[:, :s]
