"""Pallas TPU flash-attention kernels — forward AND backward.

Forward: grid (batch·heads, seq_q/block_q). Each program holds one query
block in VMEM and streams the full key/value sequence for its batch-head
through a ``fori_loop`` of ``block_k`` chunks with the online-softmax
recurrence — the (seq, seq) score matrix never exists in HBM, scores are
accumulated on the MXU in float32. The per-row logsumexp is written as a
second output and saved for the backward.

Backward (FlashAttention-style, two kernels so no cross-program
accumulation is needed):

- ``_bwd_dq_kernel``   — grid over q blocks; recomputes P = exp(qkᵀ − lse)
  per k chunk and accumulates dQ = Σ (P ∘ (dO·Vᵀ − D))·K;
- ``_bwd_dkv_kernel``  — grid over k blocks; loops over q chunks and
  accumulates dV = Σ Pᵀ·dO and dK = Σ (P ∘ (dO·Vᵀ − D))ᵀ·Q,

where D = rowsum(dO ∘ O) is precomputed outside the kernels. Memory stays
O(seq) end to end — the residuals are just (q, k, v, o, lse).

Ragged sequence lengths are first-class: inputs pad to the 128-lane tile
and pad *keys* are masked to −inf wherever scores are (re)computed. Pad
*query* rows need no masking anywhere: their forward output is sliced off,
so their incoming dO is zero and every backward contribution vanishes.

Heads are folded into the batch/grid dimension, so per-program tiles are 2-D
(block, head_dim) — aligned with the (8/16, 128) sublane×lane tiling as long
as head_dim is a multiple of 128 (64/32-dim heads are padded by Mosaic
automatically, at some efficiency cost).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Minor-dim width for the per-row scalar residuals (lse, D). 8 (one f32
# sublane tile) rather than 128: Mosaic accepts sub-lane-width minor dims
# with masked loads, and the 16× slimmer HBM buffers matter at scale — at
# the ViT-H bench shapes the 128-wide broadcast was ~840 MB of transient
# per buffer; gradient parity at width 8 is verified on-device (v5e).
# Mosaic's acceptance of sub-128 minor dims varies by TPU generation and
# compiler version: a Mosaic layout/lane error pointing at the lse/delta
# buffers on another device kind means this width (128 is always accepted:
# identical numerics, fatter HBM transients).
LANE = 8

# Matmul operand dtype inside the kernels: the INPUT dtype (bf16 in
# production) rather than an f32 upcast. bf16 operands feed the MXU at its
# native rate — the prior unconditional f32 upcast cost multiple MXU passes
# per dot, a plausible root cause of round 4's "flash loses to einsum
# everywhere both fit". The einsum path materializes bf16 scores AND bf16
# probs, so bf16 operands here are numerically comparable (scores still
# accumulate f32 via preferred_element_type, softmax math stays f32, and
# flash keeps its f32 online-softmax accumulation). f32 inputs (parity
# oracles) are untouched.


def _mask_cols(s, col0: int, valid_k: int):
    """Set score columns at global key index ≥ valid_k to −inf."""
    rows, cols = s.shape
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return jnp.where(col < valid_k, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *, block_k: int, valid_k: int):
    mm = q_ref.dtype
    q = q_ref[0].astype(mm)  # (block_q, d)
    block_q, d = q.shape
    seq_k = k_ref.shape[1]

    def body(i, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(i * block_k, block_k), :].astype(mm)
        vb = v_ref[0, pl.ds(i * block_k, block_k), :].astype(mm)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k), f32 accumulation
        if valid_k != seq_k:
            s = _mask_cols(s, i * block_k, valid_k)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(mm), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, seq_k // block_k, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    if lse_ref is not None:
        # per-row scalar broadcast over an 8-wide (one f32 sublane tile)
        # minor dim — see the LANE constant for why not 128
        lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), (block_q, LANE))


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, *, block_k: int, valid_k: int
):
    mm = q_ref.dtype
    q = q_ref[0].astype(mm)  # (block_q, d)
    do = do_ref[0].astype(mm)
    lse = lse_ref[0][:, :1]  # (block_q, 1) — scalar replicated over lanes
    dd = dd_ref[0][:, :1]
    block_q, d = q.shape
    seq_k = k_ref.shape[1]

    def body(i, dq):
        kb = k_ref[0, pl.ds(i * block_k, block_k), :].astype(mm)
        vb = v_ref[0, pl.ds(i * block_k, block_k), :].astype(mm)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if valid_k != seq_k:
            s = _mask_cols(s, i * block_k, valid_k)
        p = jnp.exp(s - lse)  # (block_q, block_k), f32
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - dd)).astype(mm)
        return dq + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = jax.lax.fori_loop(
        0, seq_k // block_k, body, jnp.zeros((block_q, d), jnp.float32)
    )
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
    *, block_q: int, valid_k: int, masked: bool,
):
    mm = k_ref.dtype
    k = k_ref[0].astype(mm)  # (block_k, d)
    v = v_ref[0].astype(mm)
    block_k, d = k.shape
    seq_q = q_ref.shape[1]
    col0 = pl.program_id(1) * block_k

    def body(i, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * block_q, block_q), :].astype(mm)
        dob = do_ref[0, pl.ds(i * block_q, block_q), :].astype(mm)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :1]
        dd = dd_ref[0, pl.ds(i * block_q, block_q), :1]
        s = jax.lax.dot_general(
            qb, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k)
        if masked:
            s = _mask_cols(s, col0, valid_k)
        p = jnp.exp(s - lse)
        dv = dv + jax.lax.dot_general(
            p.astype(mm), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            dob, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - dd)).astype(mm)
        dk = dk + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk, dv

    zeros = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, seq_q // block_q, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pad_seq(x, to: int):
    pad = to - x.shape[1]
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def _largest_dividing_block(requested: int, seq_pad: int) -> int:
    """Largest block ≤ requested that divides ``seq_pad``. Production blocks
    stay on 128 multiples (seq_pad is one, so 128 always qualifies);
    sub-128 requests (interpreter tests) fall back to any exact divisor."""
    block = min(requested, seq_pad)
    if block >= 128:
        block = block // 128 * 128
        while seq_pad % block:
            block -= 128
    else:
        while seq_pad % block:
            block -= 1
    return block


def _fold(x, b, h, s, d):
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x, b, h, s, d):
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _plan(q, k, block_q, block_k):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    # pad ragged lengths only up to the 128-lane tile, then pick the largest
    # block ≤ requested that divides the padded length (at seq 787 → 896 a
    # requested 256 collapses to 128)
    sq_pad = _round_up(sq, 128)
    sk_pad = _round_up(sk, 128)
    return (
        b, sq, h, d, sk, sq_pad, sk_pad,
        _largest_dividing_block(block_q, sq_pad),
        _largest_dividing_block(block_k, sk_pad),
    )


def _flash_fwd(q, k, v, block_q, block_k, interpret, with_lse: bool):
    b, sq, h, d, sk, sq_pad, sk_pad, block_q, block_k = _plan(q, k, block_q, block_k)
    qf = _fold(_pad_seq(q, sq_pad), b, h, sq_pad, d)
    kf = _fold(_pad_seq(k, sk_pad), b, h, sk_pad, d)
    vf = _fold(_pad_seq(v, sk_pad), b, h, sk_pad, d)

    o_spec = pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0))
    o_shape = jax.ShapeDtypeStruct((b * h, sq_pad, d), q.dtype)
    if with_lse:
        # the lse output rides a LANE-wide (8, one sublane tile) minor dim
        # inside the kernel; only the first column is kept as residual
        out_specs = [o_spec, pl.BlockSpec((1, block_q, LANE), lambda bh, i: (bh, i, 0))]
        out_shape = [o_shape, jax.ShapeDtypeStruct((b * h, sq_pad, LANE), jnp.float32)]
    else:
        out_specs, out_shape = o_spec, o_shape

    res = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=block_k, valid_k=sk),
        grid=(b * h, sq_pad // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, sk_pad, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, sk_pad, d), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(qf, kf, vf)
    out, lse = res if with_lse else (res, None)
    out = _unfold(out, b, h, sq_pad, d)
    out = out[:, :sq] if sq_pad != sq else out
    return (out, lse[..., 0]) if with_lse else (out, None)


def _flash_bwd(q, k, v, o, lse, g, block_q, block_k, interpret, g_lse=None):
    b, sq, h, d, sk, sq_pad, sk_pad, block_q, block_k = _plan(q, k, block_q, block_k)
    qf = _fold(_pad_seq(q, sq_pad), b, h, sq_pad, d)
    kf = _fold(_pad_seq(k, sk_pad), b, h, sk_pad, d)
    vf = _fold(_pad_seq(v, sk_pad), b, h, sk_pad, d)
    dof = _fold(_pad_seq(g, sq_pad), b, h, sq_pad, d)
    of = _fold(_pad_seq(o, sq_pad), b, h, sq_pad, d)
    # D = rowsum(dO ∘ O): tiny and elementwise — jnp, not a kernel. Pad q
    # rows have dO = 0 ⇒ D = 0 ⇒ all their backward contributions vanish.
    # Both per-row scalars are replicated over the lane dim only here, at
    # kernel entry (the lse residual is stored compact, (b*h, sq_pad)).
    dd = (dof.astype(jnp.float32) * of.astype(jnp.float32)).sum(-1)
    if g_lse is not None:
        # lse cotangent (pallas_flash_attention_with_lse): ∂lse/∂s_j = p_j,
        # so it folds into the score cotangent as ds = p·(dp − (D − g_lse))
        # — shift D per row, kernels unchanged. Pad rows get 0 (no-op).
        dd = dd - jnp.pad(
            g_lse.astype(jnp.float32),
            ((0, 0), (0, sq_pad - g_lse.shape[1])),
        )
    dd = jnp.broadcast_to(dd[..., None], (b * h, sq_pad, LANE))
    lse = jnp.broadcast_to(lse[..., None], (b * h, sq_pad, LANE))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=block_k, valid_k=sk),
        grid=(b * h, sq_pad // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, sk_pad, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, sk_pad, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, block_q, LANE), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, block_q, LANE), lambda bh, i: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_pad, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, dd)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=block_q, valid_k=sk, masked=sk != sk_pad
        ),
        grid=(b * h, sk_pad // block_k),
        in_specs=[
            pl.BlockSpec((1, sq_pad, d), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, sq_pad, d), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, sq_pad, LANE), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, sq_pad, LANE), lambda bh, j: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk_pad, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk_pad, d), v.dtype),
        ],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, dd)

    dq = _unfold(dq, b, h, sq_pad, d)[:, :sq]
    dk = _unfold(dk, b, h, sk_pad, d)[:, :sk]
    dv = _unfold(dv, b, h, sk_pad, d)[:, :sk]
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def pallas_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over (batch, seq, heads, head_dim); q pre-scaled.

    Arbitrary sequence lengths: inputs are padded to lane tiles and the pad
    keys are masked to -inf inside the kernels (MAE shapes like 199 are
    first-class). Forward and backward are both Pallas kernels with O(seq)
    memory. ``interpret=True`` runs them in the Pallas interpreter (CPU
    tests).

    Default blocks are 1024 (clamped per shape by ``_plan``): round-5
    microbenches (PERF_ARCHIVE.md, v5e) showed the requested-256
    default collapsing to 128 at seq 787 (896 tile-pad) and doubling the
    streaming passes — big requests resolve to full-row or near-full-row
    blocks (256@199, 896@787, 640@3139) and beat the einsum path at every
    long-context shape (9.0 vs 15.3 ms at 787, 24.7 vs 45.8 at 3139).
    """
    out, _ = _flash_fwd(q, k, v, block_q, block_k, interpret, with_lse=False)
    return out


def _vjp_fwd(q, k, v, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, block_q, block_k, interpret, with_lse=True)
    return out, (q, k, v, out, lse)


def _vjp_bwd(block_q, block_k, interpret, residuals, g):
    q, k, v, o, lse = residuals
    return _flash_bwd(q, k, v, o, lse, g, block_q, block_k, interpret)


pallas_flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def pallas_flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Like :func:`pallas_flash_attention` but also returns the per-row
    logsumexp ``lse`` with shape (batch·heads, seq_q) — DIFFERENTIABLE in
    both outputs, which block-merging callers (ring attention's flash
    inner) need: the merge weights are functions of lse, so its cotangent
    must reach q and k.

    The lse cotangent costs nothing extra in the backward: with
    ``p = exp(s − lse)``, ``∂lse/∂s_j = p_j``, so the score cotangent
    becomes ``ds = p·(dp − (D − g_lse))`` — the existing kernels run
    unchanged with ``D`` shifted by ``−g_lse`` per row.
    """
    out, lse = _flash_fwd(q, k, v, block_q, block_k, interpret, with_lse=True)
    return out, lse[:, : q.shape[1]]


def _vjp_lse_fwd(q, k, v, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, block_q, block_k, interpret, with_lse=True)
    return (out, lse[:, : q.shape[1]]), (q, k, v, out, lse)


def _vjp_lse_bwd(block_q, block_k, interpret, residuals, gs):
    q, k, v, o, lse = residuals
    g, g_lse = gs
    return _flash_bwd(
        q, k, v, o, lse, g, block_q, block_k, interpret, g_lse=g_lse
    )


pallas_flash_attention_with_lse.defvjp(_vjp_lse_fwd, _vjp_lse_bwd)


# ---------------------------------------------------------------------------
# Causal form, with query/key and value head widths that differ, key/value
# heads that a group of query heads shares, and one of three visibility
# patterns: every earlier key, a window of them, or block diffusion's.
#
# The score of a (query, key) pair is ``q_a·k_aᵀ``, a per-head part of width
# ``d_a``, plus — where ``q_b``/``k_b`` are given — a part whose key is one
# vector a token shared by every head (``q_b·k_bᵀ``, width ``d_b``; multi-head
# latent attention's rotary columns). ``k_b`` is never replicated per head:
# its BlockSpec ignores the head index. The value width ``d_v`` is
# independent. ``k_a`` and ``v`` may have fewer heads than the queries
# (grouped-query attention): query head ``h`` reads key/value head ``h //
# group``, again through the BlockSpec alone, and the key/value gradients of a
# group are summed inside the backward kernel, whose accumulators outlive a
# member (below).
#
# Layout is head-major, (batch, heads, seq, width): the projections that
# feed the kernel write it directly, so there is no fold/transposition here.
#
# Causality and the window are in the grid, not in a mask applied after the
# fact: the third grid axis walks only the (query block, key block) pairs
# that hold a visible entry, in an order two scalar-prefetched tables give
# (``_lower_triangle``: the whole triangle on or below the diagonal, or with a
# window of ``w`` tokens the band of it that ``w`` reaches back into), so a
# block outside the band costs neither a DMA nor a grid step: a windowed
# layer is O(seq · window). Only the band's two edges are cut by a mask: the
# diagonal blocks (``row >= col``) and the trailing blocks that the window's
# far edge cuts (``row − col < w`` in absolute positions), at distances from
# the diagonal known when a kernel is built (``_cuts``). A cut pair is not
# computed whole under its mask: its grid step, tables and DMAs are a whole
# pair's, but the step's body runs as strips of query rows, each over only
# the sub-tiles that hold an entry it sees (``_strips``; the side from the
# block, ``_sub_tile``), and only the sub-tiles the mask's edge crosses are
# compared with a local iota — a hidden entry's probability and ``ds`` are
# exact zeros, so leaving it out only reorders a row's float32 sums. A pair
# no mask cuts is one strip with no compare. Running maximum, denominator and
# the output accumulator live in VMEM scratch across the key blocks of a
# query block; scores never leave VMEM.
#
# The third pattern is block diffusion's (``diffusion`` = the diffusion
# block's length ``B``, a power of two): the row holds a clean copy of a
# sequence of ``L`` tokens and then a noisy copy of it, ``n = L / block`` key
# blocks each, and with ``b(i) = (i mod L) // B`` a clean query sees the clean
# keys of ``b(j) <= b(i)``, a noisy query the clean keys of ``b(j) < b(i)`` and
# the noisy keys of ``b(j) == b(i)``, its own diffusion block both ways. The
# same kernels walk another set of pairs (``_two_copies``: a clean query block
# its ``i + 1`` clean key blocks, a noisy one those and its own: ``n (n + 1) +
# n`` pairs, all on or below the diagonal of the ``2 n`` x ``2 n`` grid, the
# diagonal a query block's last, so the walk's order, the accumulators and the
# spans below carry over) and cut three kinds of pair (``_diffusion_cuts``): a
# clean block against itself (the staircase ``col // B <= row // B``), a noisy
# block against the clean block at its own place (``col // B < row // B``) and
# against itself (``col // B == row // B``). None is a function of ``row −
# col``; each is ``lo <= row // B − col // B < hi``, the causal cuts' form with
# the distance counted in diffusion blocks, so ``_strips`` plans a cut pair in
# units of ``B`` (a staircase pair runs 10 of its 16 sub-tiles, a noisy
# block's own 4) and ``_masked`` compares the same local iotas shifted by
# ``log2 B``. Which pair is cut how is decided where a kernel is built, from
# ``diffusion`` alone (``_off_diagonal``, ``_diagonal``); ``B`` has to divide
# the sub-tile, and a longer diffusion block is refused.
#
# A sequence that is no multiple of the block is padded with zero rows: a
# pad key lies after every real query, so causality already hides it, and a
# pad query's output is sliced off, so its cotangent is zero and every
# backward contribution from it vanishes. (Two copies are padded each by
# itself: ``_causal_band``.)
#
# The backward pass is one kernel (``causal_attention_bwd``): a visited block
# pair's scores, probabilities, ``dO·Vᵀ`` and ``ds`` are formed once and all of
# dQ, dK (both parts) and dV accumulated from them. Its grid is (batch,
# key/value heads, steps), and four scalar-prefetched tables give a step its
# query block, key block, member of the group and what it opens and closes
# (``_backward_walk``): for each member in turn the pairs in the forward
# kernel's order, a query block's key blocks rising. dQ of the current
# (member, query block) lives in block-sized float32 scratch, zeroed at the
# row's first key block and written at its last. dK_a and dV live in float32
# scratch as long as the (padded) sequence, a key block's rows addressed by a
# dynamic slice, zeroed at a (batch, key/value head)'s first step and written
# at its last into output blocks that are the whole sequence, so the group's
# sum costs nothing and a key block is written once. dK_b, a query head's own,
# accumulates in its float32 output block (the whole sequence too) and is
# summed over the heads outside. What decides whether the sequence-long
# accumulators fit is their bytes, from the operands' shapes
# (``_causal_span``): where they outgrow VMEM beside a block pair's tiles
# (past 16 384 tokens at latent attention's widths) the key blocks are walked
# in the fewest spans that fit, one after the other in the same grid axis of
# the same kernel, each span's accumulators as long as the span and its share
# of dQ a slab of its own, summed outside with one add a span.
#
# Of the seven residuals the backward kernel reads, five are the caller's
# arguments; the forward kernel itself makes two, the output ``o`` and the
# log-sum-exp ``lse``, and the forward rule names both (``CAUSAL_OUT_NAME``,
# ``CAUSAL_LSE_NAME``). A ``jax.checkpoint`` whose policy saves those names
# (``models/config.checkpoint_policy`` does, under every policy) keeps the two
# arrays across rematerialisation, so the recomputed forward kernel has no
# consumer left and is dead code: one forward run a layer, not two. ``lse``
# is kept compact, one float32 a (head, token), and widened to ``LANE`` only
# at the backward kernel's entry: in HBM the ``LANE``-wide form is tiled to
# 128 lanes, 128 float32 a (head, token). Outside a remat a name is an
# identity.
# ---------------------------------------------------------------------------


# what one causal kernel may hold in VMEM (half of a v5e's): a 1024-block's
# float32 scores, probabilities and their bf16 copies pass the 16 MiB default,
# and the backward kernel's sequence-long accumulators are sized against it
CAUSAL_VMEM_BYTES = 64 * 1024 * 1024
CAUSAL_BLOCK = 1024
# checkpoint names of the two residuals the forward kernel makes
CAUSAL_OUT_NAME = "causal_attention_out"
CAUSAL_LSE_NAME = "causal_attention_lse"


def _reach(window: int, block: int) -> int:
    """How many key blocks before its own a query block's window reaches
    into: query ``r`` sees keys ``r − window + 1 .. r``."""
    return (window + block - 2) // block


def _lower_triangle(n: int, *, reach: int | None = None):
    """The block pairs that hold a visible entry as two int32 tables: those
    on or below the diagonal, and with ``reach`` only those at most ``reach``
    blocks below it (the band). Query block outer, its key blocks inner in
    rising order (the diagonal comes last)."""
    reach = n if reach is None else reach
    pairs = [(i, j) for i in range(n) for j in range(max(i - reach, 0), i + 1)]
    qi, kj = zip(*pairs)
    return np.asarray(qi, np.int32), np.asarray(kj, np.int32)


def _two_copies(n: int):
    """The block pairs of the block-diffusion pattern as two int32 tables over
    the ``2 n`` blocks of a row that holds a clean copy of a sequence, ``n``
    blocks, and then a noisy one: a clean query block ``i`` its clean key
    blocks ``j <= i``; a noisy query block ``n + i`` the clean key blocks ``j
    <= i`` and then its own, ``n + i``. Query block outer, its key blocks
    rising: every pair lies on or below the diagonal of the ``2 n`` x ``2 n``
    grid, a query block's first key block is 0 and its last the diagonal's,
    as in ``_lower_triangle``."""
    pairs = [(i, j) for i in range(n) for j in range(i + 1)]
    pairs += [(n + i, j) for i in range(n) for j in (*range(i + 1), n + i)]
    qi, kj = zip(*pairs)
    return np.asarray(qi, np.int32), np.asarray(kj, np.int32)


def _pair_tables(n: int, *, reach: int | None, diffusion: int | None):
    """The walk's tables over the ``n`` blocks of a (padded) row: the
    triangle or its band, or with ``diffusion`` the two copies' pattern."""
    return _lower_triangle(n, reach=reach) if diffusion is None else _two_copies(n // 2)


def _whole(block: int) -> tuple[int, int]:
    """The ``(lo, hi)`` no entry of a block pair falls outside: no mask."""
    return 1 - block, block


def _diffusion_cuts(block: int, unit: int) -> dict[str, tuple[int, int, int]]:
    """The three kinds of pair a block-diffusion mask cuts, each with the
    ``(lo, hi, unit)`` of its mask: an entry is visible iff ``lo <= row // unit
    − col // unit < hi``, ``unit`` the diffusion block's length, a power of two.
    ``"clean"``, a clean block against itself: the staircase ``col // unit <=
    row // unit``; ``"strict"``, a noisy block against the clean block at its
    own place: ``col // unit < row // unit``; ``"own"``, a noisy block against
    itself: the block diagonal ``col // unit == row // unit``. None is a
    function of ``row − col``: a pair's ``unit`` says in what the distance is
    counted, and 1 is the causal kinds'."""
    far = block // unit  # past every entry's distance
    return {"clean": (0, far, unit), "strict": (1, far, unit), "own": (0, 1, unit)}


def _cuts(block: int, window: int | None) -> dict[int, tuple[int, int]]:
    """The block pairs a mask cuts, by their distance ``i − j`` from the
    diagonal, each with the ``(lo, hi)`` of its mask in the pair's own rows and
    columns: an entry is visible iff ``lo <= row − col < hi``. The diagonal
    (distance 0: ``row >= col``, and the window's far edge too where the
    window is shorter than the block) and the trailing pairs the window's far
    edge cuts, at most two distances (``window // block … _reach``). Every
    other pair the tables walk is visible whole."""
    if window is None:
        return {0: (0, block)}
    cuts = {0: (0, min(window, block))}
    for apart in range(max(window // block, 1), _reach(window, block) + 1):
        cuts[apart] = 1 - block, window - apart * block
    return cuts


def _sub_tile(block: int) -> int:
    """The side of the sub-tiles a masked block pair is computed in
    (``_strips``), from the block alone: a quarter of the block where that is
    a whole number of 128-lane tiles (a strip's rows are then whole sublane
    tiles of float32 and of bf16 too, and its score columns whole lanes),
    else half of it, else the block (one strip: the whole pair under its
    mask, as the interpreter's tests at blocks of 16 and the 128-blocks of a
    short window run)."""
    return next((block // n for n in (4, 2) if block % (n * 128) == 0), block)


class _Strip(NamedTuple):
    """Query rows ``row .. row_end`` of a block pair and the key columns ``col
    .. col_end`` of the sub-tiles that hold an entry they see; of those,
    ``clear .. clear_end`` lie in sub-tiles visible whole, the mask crosses
    the rest."""
    row: int
    row_end: int
    col: int
    col_end: int
    clear: int
    clear_end: int


def _strips(block: int, tile: int, lo: int, hi: int, unit: int = 1) -> tuple[_Strip, ...]:
    """How a block pair under the mask ``lo <= row − col < hi`` (with ``unit``
    > 1: ``lo <= row // unit − col // unit < hi``, the same plan in units of
    ``unit`` rows and columns, which ``tile`` is whole numbers of) is computed:
    as strips of ``tile`` query rows, each over only the ``tile``-wide
    sub-tiles that hold a visible entry (the visible ``row − col`` are one
    interval, so they are consecutive, and so are those visible whole). A
    strip that sees nothing in the pair is left out. On a diagonal block
    strip ``r`` holds columns ``0 .. (r + 1) · tile`` and its last sub-tile is
    masked; under a far edge of 0 columns ``r · tile .. block`` and its first.
    A pair the mask leaves whole is one strip, the block. Static: both kernels
    and ``causal_pairs`` read it."""
    if unit > 1:
        if tile % unit:
            raise ValueError(f"a diffusion block of {unit} tokens is no divisor of the "
                             f"{tile}-wide sub-tiles a masked pair is computed in")
        return tuple(_Strip(*(unit * at for at in strip))
                     for strip in _strips(block // unit, tile // unit, lo, hi))
    if (lo, hi) == _whole(block):
        return (_Strip(0, block, 0, block, 0, block),)
    strips = []
    for row in range(0, block, tile):
        # the sub-tile at ``col`` holds row − col in row − col ± (tile − 1)
        held = [col for col in range(0, block, tile)
                if row - col + tile - 1 >= lo and row - col - tile + 1 < hi]
        if not held:
            continue
        clear = [col for col in held if row - col - tile + 1 >= lo and row - col + tile - 1 < hi]
        end = held[-1] + tile
        strips.append(_Strip(row, row + tile, held[0], end,
                             *((clear[0], clear[-1] + tile) if clear else (end, end))))
    return tuple(strips)


def _masked(s, row: int, col: int, lo: int, hi: int, unit: int = 1):
    """``s`` with −inf where ``lo <= row − col < hi`` fails (with ``unit`` > 1,
    a power of two that ``s``'s place and shape are whole numbers of: where
    ``lo <= row // unit − col // unit < hi`` fails); its first entry is the
    pair's (``row``, ``col``). A bound no entry of ``s`` reaches is not
    compared."""
    rows, cols = (n // unit for n in s.shape)
    row, col = row // unit, col // unit  # the place and the shape in units
    below, beyond = row - (col + cols - 1) < lo, row + rows - 1 - col >= hi
    if not (below or beyond):
        return s
    # row − col of an entry, less that of s[0, 0]; in units, the local iotas shifted
    at = [jax.lax.broadcasted_iota(jnp.int32, s.shape, axis) for axis in (0, 1)]
    if unit > 1:
        at = [jnp.right_shift(a, unit.bit_length() - 1) for a in at]
    apart = at[0] - at[1]
    keep = apart < hi - (row - col) if beyond else None
    if below:
        near = apart >= lo - (row - col)
        keep = near if keep is None else keep & near
    return jnp.where(keep, s, NEG_INF)


def _scores(qa, qb, ka, kb, strip: _Strip, lo: int, hi: int, unit: int = 1):
    """Float32 scores of one strip of a block pair: its query rows against
    the key columns ``strip`` holds, −inf where the pair's mask hides an
    entry. Only the sub-tiles the mask crosses are compared."""
    dims = (((1,), (1,)), ((), ()))
    s = jax.lax.dot_general(qa, ka, dims, preferred_element_type=jnp.float32)
    if qb is not None:
        s = s + jax.lax.dot_general(qb, kb, dims, preferred_element_type=jnp.float32)
    row, _, col, col_end, clear, clear_end = strip
    # the columns before, in and after the sub-tiles visible whole, which
    # ``_masked`` finds no bound to compare in
    pieces = [(a, b) for a, b in [(col, clear), (clear, clear_end), (clear_end, col_end)] if b > a]
    if len(pieces) == 1:
        return _masked(s, row, col, lo, hi, unit)
    return jnp.concatenate([_masked(s[:, a - col:b - col], row, a, lo, hi, unit)
                            for a, b in pieces], axis=1)


def _parts(refs, two_part: bool):
    """A kernel's refs as ``(qa, qb, ka, kb, v, *rest)``, ``qb`` and ``kb``
    None where the score has one part."""
    if two_part:
        return refs
    qa, ka, v = refs[:3]
    return (qa, None, ka, None, v, *refs[3:])


def _read(ref, dtype, rows):
    return None if ref is None else ref[rows, :].astype(dtype)


def _first_key_block(i, *, block: int, window: int | None):
    """The first key block a query block's row of the tables visits (0 under
    the block-diffusion pattern too, which has no window)."""
    return 0 if window is None else jnp.maximum(i - _reach(window, block), 0)


def _off_diagonal(i, j, step, *, block: int, window: int | None,
                  diffusion: tuple[int, int] | None = None):
    """Run ``step(lo, hi)`` for a pair below the diagonal (``j < i``): with
    bounds no entry falls outside where the whole block is visible, and where
    the window ends inside it one branch a distance it does so at
    (``_cuts``), so that each has bounds known when the kernel is built. With
    ``diffusion`` = (the diffusion block's length, key blocks a copy ``n``):
    a query block at place ``i % n`` of its copy sees the clean blocks before
    that place whole, and a noisy one (``i >= n``) the clean block at its own
    place, ``j == i − n``, under the strict staircase (``_diffusion_cuts``)."""
    whole = functools.partial(step, *_whole(block))
    if diffusion is not None:
        unit, n = diffusion
        pl.when(j < i % n)(whole)
        pl.when(j == i - n)(functools.partial(step, *_diffusion_cuts(block, unit)["strict"]))
        return
    if window is None:
        pl.when(j < i)(whole)
        return
    clear = window // block  # pairs fewer blocks apart than this lie wholly inside the window
    if clear > 1:
        pl.when((j < i) & (i - j < clear))(whole)
    for apart, bounds in _cuts(block, window).items():
        if apart:
            pl.when(i - j == apart)(functools.partial(step, *bounds))


def _diagonal(i, step, *, block: int, window: int | None,
              diffusion: tuple[int, int] | None = None):
    """Run ``step`` for a query block's last pair, the diagonal one (``j ==
    i``), under its mask: the causal cut (``_cuts``), or with ``diffusion`` a
    clean block's staircase or a noisy block's block diagonal."""
    if diffusion is None:
        step(*_cuts(block, window)[0])
        return
    unit, n = diffusion
    cuts = _diffusion_cuts(block, unit)
    pl.when(i < n)(functools.partial(step, *cuts["clean"]))
    pl.when(i >= n)(functools.partial(step, *cuts["own"]))


def _causal_fwd_kernel(qi_ref, kj_ref, *refs, two_part: bool, block: int, tile: int,
                       window: int | None, diffusion: tuple[int, int] | None = None):
    qa_ref, qb_ref, ka_ref, kb_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc = _parts(
        refs, two_part)
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]
    mm = qa_ref.dtype

    @pl.when(j == _first_key_block(i, block=block, window=window))
    def _():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def step(lo: int, hi: int, unit: int = 1):
        # every strip's scores before the first softmax: in this order the
        # compiler overlaps the strips' chains (maximum, exponential, product)
        # better than a strip at a time (PERF.md §6, PR 45)
        strips = _strips(block, tile, lo, hi, unit)
        at = [(pl.ds(strip.row, strip.row_end - strip.row),
               pl.ds(strip.col, strip.col_end - strip.col)) for strip in strips]
        scores = [_scores(_read(qa_ref, mm, rows), _read(qb_ref, mm, rows), _read(ka_ref, mm, keys),
                          _read(kb_ref, mm, keys), strip, lo, hi, unit)
                  for strip, (rows, keys) in zip(strips, at)]
        for s, (rows, keys) in zip(scores, at):
            m_prev = m_sc[rows, :]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[rows, :] = alpha * l_sc[rows, :] + p.sum(axis=-1, keepdims=True)
            acc_sc[rows, :] = alpha * acc_sc[rows, :] + jax.lax.dot_general(
                p.astype(mm), _read(v_ref, mm, keys), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[rows, :] = m_new

    # a row that a trailing block hides whole, in a strip that is run, leaves
    # 1s in p at the running maximum −1e30; the diagonal block, always visited
    # and never all hidden, scales them away by alpha = 0. A strip that sees
    # nothing of the pair is not run and leaves its rows' state as it was.
    # Under the block-diffusion pattern the first rows of a noisy block see
    # nothing in the clean block at their own place, and their own block,
    # the diagonal one, comes last in the same way
    _off_diagonal(i, j, step, block=block, window=window, diffusion=diffusion)

    @pl.when(j == i)  # the diagonal is the last key block of a query block
    def _():
        _diagonal(i, step, block=block, window=window, diffusion=diffusion)
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[...] = jnp.broadcast_to(m_sc[...] + jnp.log(l), lse_ref.shape)


# what a step of the backward kernel's walk opens and closes: bits of its
# fourth table
ROW_FIRST, ROW_LAST, HEAD_FIRST, SPAN_FIRST, SPAN_LAST = 1, 2, 4, 8, 16


def _backward_walk(n: int, *, reach: int | None, group: int, span: int,
                   diffusion: int | None = None):
    """The backward kernel's grid steps as four int32 tables: query block,
    key block, the group's member, and the step's bits. The ``n`` key blocks
    lie in spans of ``span``; a span's pairs are walked once a member in the
    forward tables' order (``_pair_tables``: a query block's key blocks
    rising), so the steps of a span are consecutive, within it a query
    head's, and within those a query block's."""
    visible = list(zip(*(table.tolist() for table in
                         _pair_tables(n, reach=reach, diffusion=diffusion))))
    steps = []
    for at_span in range(-(-n // span)):
        pairs = [(i, j) for i, j in visible if j // span == at_span]
        last = len(pairs) - 1
        for member in range(group):
            for t, (i, j) in enumerate(pairs):
                at = (ROW_FIRST * (t == 0 or pairs[t - 1][0] != i)
                      | ROW_LAST * (t == last or pairs[t + 1][0] != i)
                      | HEAD_FIRST * (t == 0)
                      | SPAN_FIRST * (t == 0 and member == 0)
                      | SPAN_LAST * (t == last and member == group - 1))
                steps.append((i, j, member, at))
    return tuple(np.asarray(column, np.int32) for column in zip(*steps))


def _causal_bwd_kernel(qi_ref, kj_ref, _member_ref, at_ref, *refs, two_part: bool, block: int,
                       tile: int, window: int | None, span: int,
                       diffusion: tuple[int, int] | None = None):
    # the member is the index maps' alone
    (qa_ref, qb_ref, ka_ref, kb_ref, v_ref, do_ref, lse_ref, dd_ref, *outs) = _parts(
        refs, two_part)
    if two_part:
        dqa_ref, dqb_ref, dka_ref, dkb_ref, dv_ref, dqa_sc, dqb_sc, dka_sc, dv_sc = outs
    else:
        dqa_ref, dka_ref, dv_ref, dqa_sc, dka_sc, dv_sc = outs
    t = pl.program_id(2)
    i, j, at = qi_ref[t], kj_ref[t], at_ref[t]
    mm = qa_ref.dtype

    @pl.when(at & ROW_FIRST != 0)
    def _():
        dqa_sc[...] = jnp.zeros(dqa_sc.shape, jnp.float32)
        if two_part:
            dqb_sc[...] = jnp.zeros(dqb_sc.shape, jnp.float32)

    if two_part:  # a query head's own: float32 on its way out, summed in place
        @pl.when(at & HEAD_FIRST != 0)
        def _():
            dkb_ref[...] = jnp.zeros(dkb_ref.shape, jnp.float32)

    @pl.when(at & SPAN_FIRST != 0)
    def _():
        dka_sc[...] = jnp.zeros(dka_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    to_keys, to_queries = (((0,), (0,)), ((), ())), (((1,), (0,)), ((), ()))
    dot = functools.partial(jax.lax.dot_general, preferred_element_type=jnp.float32)

    def step(lo: int, hi: int, unit: int = 1):
        for strip in _strips(block, tile, lo, hi, unit):
            side = strip.row_end - strip.row  # the tile, or the block: both offsets' divisor
            rows = pl.ds(strip.row, side)
            cols = pl.ds(strip.col, strip.col_end - strip.col)
            # the strip's key rows of the span-long accumulators
            keys = pl.ds(pl.multiple_of(j % span * block + strip.col, side),
                         strip.col_end - strip.col)
            qa, qb, do = (_read(ref, mm, rows) for ref in (qa_ref, qb_ref, do_ref))
            ka, kb, v = (_read(ref, mm, cols) for ref in (ka_ref, kb_ref, v_ref))
            s = _scores(qa, qb, ka, kb, strip, lo, hi, unit)
            p = jnp.exp(s - lse_ref[rows, :][:, :1])
            dp = dot(do, v, (((1,), (1,)), ((), ())))
            ds = (p * (dp - dd_ref[rows, :][:, :1])).astype(mm)
            dv_sc[keys, :] += dot(p.astype(mm), do, to_keys)
            dka_sc[keys, :] += dot(ds, qa, to_keys)
            dqa_sc[rows, :] += dot(ds, ka, to_queries)
            if two_part:
                dkb_ref[keys, :] += dot(ds, qb, to_keys)
                dqb_sc[rows, :] += dot(ds, kb, to_queries)

    _off_diagonal(i, j, step, block=block, window=window, diffusion=diffusion)
    pl.when(j == i)(functools.partial(_diagonal, i, step, block=block, window=window,
                                      diffusion=diffusion))

    @pl.when(at & ROW_LAST != 0)
    def _():
        dqa_ref[...] = dqa_sc[...].astype(dqa_ref.dtype)
        if two_part:
            dqb_ref[...] = dqb_sc[...].astype(dqb_ref.dtype)

    @pl.when(at & SPAN_LAST != 0)
    def _():
        dka_ref[...] = dka_sc[...].astype(dka_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def causal_block(seq: int, window: int | None) -> int:
    """The kernels' block for a sequence and a window: ``CAUSAL_BLOCK`` where
    the whole triangle is walked; under a window the block that costs a
    windowed layer least on the chip. One layer's forward + backward at 64
    heads over 8, 2 x 8192 tokens, a 512-token window, at blocks 1024 · 512 ·
    256: with every masked pair computed whole (3.9 x, 2.0 x, 1.5 x the score
    entries the mask keeps) 25.34 · 21.09 · 30.80 ms (PERF.md §6, PR 45; PR 37
    read 25.36 · 21.15 · 30.78, PR 33's two backward kernels 31.05 · 25.12 ·
    37.65), so 512 won; with a masked pair run as strips over its sub-tiles
    that hold a visible entry (1.5 x, 1.25 x, 1.25 x) **15.46** · 18.66 ·
    29.57: the strips take most of a large block's waste and none of a small
    block's grid steps, so from a window of half a block on (timed at 512 and
    at 4096) the block is ``CAUSAL_BLOCK``. A shorter window, timed under
    neither form, keeps the whole 128-lane tiles of its own length."""
    if window is None or window >= min(seq, CAUSAL_BLOCK // 2):
        return CAUSAL_BLOCK
    return max(128, window // 128 * 128)


def _causal_plan(seq: int, block: int) -> tuple[int, int]:
    """(padded length, block): the block is clamped to the 128-padded length
    and the sequence padded up to a multiple of it. Sub-128 blocks are for
    the interpreter's tests."""
    block = min(block, _round_up(seq, 128)) if block >= 128 else block
    return _round_up(seq, block), block


def _causal_band(seq: int, window: int | None, block: int | None, diffusion: int | None = None):
    """``(padded seq, block, window, reach)`` of a call: a window the sequence
    never reaches is none, and ``block`` None is ``causal_block``'s. With
    ``diffusion`` the row is two copies of ``seq / 2`` tokens, each padded to
    whole blocks by itself (a pad key of the clean copy lies in a later
    diffusion block than every real query, of either copy: hidden; one of the
    noisy copy in no real query's own)."""
    if diffusion is not None:
        if window is not None or diffusion & (diffusion - 1) or seq % (2 * diffusion):
            raise ValueError(f"a block-diffusion row is two copies of whole diffusion blocks "
                             f"of a power of two, with no window: {seq} rows, blocks of "
                             f"{diffusion}, window {window}")
        copy_pad, block = _causal_plan(seq // 2, block or CAUSAL_BLOCK)
        return 2 * copy_pad, block, None, None
    window = None if window is None or window >= seq else window
    s_pad, block = _causal_plan(seq, block or causal_block(seq, window))
    return s_pad, block, window, None if window is None else _reach(window, block)


def causal_pairs(seq: int, window: int | None = None, block: int | None = None,
                 diffusion: int | None = None) -> tuple[int, int]:
    """(visited, needed) score entries of one (head, sequence): those the
    kernels compute — the block pairs their tables walk, whole where no mask
    cuts them and else the sub-tiles of ``_strips``' plan — and those the mask
    keeps (``min(i + 1, window)`` keys for query ``i``; with ``diffusion``,
    where ``seq`` counts a copy's tokens, ``seq² + seq · diffusion`` over the
    pair of copies: module comment). Static, from the tables and the plan the
    kernels read."""
    area = lambda cut: sum((strip.row_end - strip.row) * (strip.col_end - strip.col)
                           for strip in _strips(block, tile, *cut))
    if diffusion is not None:
        s_pad, block, _, _ = _causal_band(2 * seq, None, block, diffusion)
        n, tile, cuts = s_pad // block // 2, _sub_tile(block), _diffusion_cuts(block, diffusion)
        # which pair is cut how: ``_off_diagonal``'s and ``_diagonal``'s rule, statically
        kind = lambda i, j: (("clean" if i < n else "own") if i == j
                             else "strict" if j == i - n else None)
        visited = sum(area(cuts.get(kind(i, j), _whole(block)))
                      for i, j in zip(*(table.tolist() for table in _two_copies(n))))
        return visited, seq * seq + seq * diffusion
    s_pad, block, window, reach = _causal_band(seq, window, block)
    qi, kj = _lower_triangle(s_pad // block, reach=reach)
    cuts, tile = _cuts(block, window), _sub_tile(block)
    visited = sum(area(cuts.get(apart, _whole(block))) for apart in (qi - kj).tolist())
    w = seq if window is None else window
    return visited, w * (w + 1) // 2 + (seq - w) * w


def _pad_rows(x, to: int, copies: int = 1):
    """``x`` with its rows (the axis before the last) padded to ``to`` with
    zeros: at the end, or with ``copies`` at the end of each of the equal
    copies the rows hold one after the other."""
    pad = to - x.shape[-2]
    if not pad:
        return x
    if copies > 1:
        lead, rows, width = x.shape[:-2], x.shape[-2] // copies, x.shape[-1]
        x = _pad_rows(x.reshape(*lead, copies, rows, width), to // copies)
        return x.reshape(*lead, to, width)
    return jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, pad), (0, 0)))


def _real_rows(x, rows: int, padded: int, copies: int = 1):
    """``_pad_rows``' inverse: the first ``rows`` rows of ``x``, or with
    ``copies`` the first ``rows / copies`` of each of the copies its first
    ``padded`` rows hold (a result may run past them: whole spans)."""
    if copies == 1:
        return x[..., :rows, :]
    lead, width = x.shape[:-2], x.shape[-1]
    x = x[..., :padded, :].reshape(*lead, copies, padded // copies, width)
    return x[..., : rows // copies, :].reshape(*lead, rows, width)


def _pair_spec(width: int, kind: str, *, block: int, group: int, members: bool,
               span: int | None = None):
    """The BlockSpec of an operand or output of a walk over block pairs, whose
    first two tables are the step's query and key block. The second grid axis
    walks the query heads, and head ``hi`` reads key/value head ``hi //
    group``; with ``members`` it walks the key/value heads and the third table
    gives the group's member. ``kind``: ``"q"`` (blocked by the query index, a
    query head), ``"k"`` (by the key index, a key/value head), ``"k_shared"``
    (by the key index, no head axis); and of the backward kernel's outputs,
    whose keys lie in spans of ``span`` blocks, ``"dq"`` (a ``"q"`` that leads
    with the key's span), ``"dk"`` (a key/value head's whole span) and
    ``"dk_head"`` (a query head's whole span)."""
    def index(bi, hi, t, qi, kj, *more):
        q_head = hi * group + more[0][t] if members else hi
        k_head = hi if members else hi // group
        return {"q": lambda: (bi, q_head, qi[t], 0),
                "k": lambda: (bi, k_head, kj[t], 0),
                "k_shared": lambda: (bi, kj[t], 0),
                "dq": lambda: (kj[t] // span, bi, q_head, qi[t], 0),
                "dk": lambda: (bi, k_head, kj[t] // span, 0),
                "dk_head": lambda: (bi, q_head, kj[t] // span, 0)}[kind]()

    rows = span * block if kind in ("dk", "dk_head") else block
    lead = {"k_shared": 1, "dq": 3}.get(kind, 2)
    return pl.BlockSpec((*(None,) * lead, rows, width), index)


def _causal_call(kernel, tables, grid, operands, outputs, scratch, *, interpret, name):
    """One ``pallas_call`` over ``grid`` = (batch, heads, steps of a walk over
    the visible block pairs), the walk's ``tables`` scalar-prefetched.
    ``operands`` are ``(array, BlockSpec)``, ``outputs`` ``(ShapeDtypeStruct,
    BlockSpec)``."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=grid,
            in_specs=[spec for _, spec in operands],
            out_specs=[spec for _, spec in outputs],
            scratch_shapes=scratch,
        ),
        out_shape=[shape for shape, _ in outputs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=CAUSAL_VMEM_BYTES,
        ),
        interpret=interpret,
        name=name,
    )(*tables, *(x for x, _ in operands))


def _traced_once(fn, static_argnums):
    """``fn`` whose calls under a trace go through an inlined ``jit``: a step
    program calls a kernel once a layer with the same shapes and static
    arguments, and tracing a kernel's body costs tenths of a second of set-up
    (the strips of its masked steps twice the whole pairs'), so the layers
    after the first take the first's jaxpr, eqn for eqn what tracing them
    again would give. A call on arrays runs ``fn`` as it is, op by op."""
    staged = jax.jit(fn, static_argnums=static_argnums, inline=True)

    @functools.wraps(fn)
    def call(*args):
        traced = any(isinstance(x, jax.core.Tracer) for x in args)
        return (staged if traced else fn)(*args)

    call.clear_cache = staged.clear_cache
    return call


def _causal_shape(qa, ka, block, window, diffusion=None):
    """``(batch, query heads, group, seq, padded seq, block, window, reach)``
    of a call."""
    b, h, s, _ = qa.shape
    return b, h, h // ka.shape[1], s, *_causal_band(s, window, block, diffusion)


def _causal_operands(named, s_pad, spec, copies: int = 1):
    """``(padded array, BlockSpec)`` of the ``(array, kind)`` in ``named``
    that are there."""
    return [(_pad_rows(x, s_pad, copies), spec(x.shape[-1], kind))
            for x, kind in named if x is not None]


def _in_kernel(diffusion: int | None, s_pad: int, block: int):
    """What the kernels are told of a block-diffusion call: (the diffusion
    block's length, key blocks a copy); None for the causal kinds."""
    return None if diffusion is None else (diffusion, s_pad // block // 2)


@functools.partial(_traced_once, static_argnums=(5, 6, 7, 8))
def _causal_fwd(qa, qb, ka, kb, v, block, interpret, window=None, diffusion=None):
    b, h, group, s, s_pad, block, window, reach = _causal_shape(qa, ka, block, window, diffusion)
    spec = functools.partial(_pair_spec, block=block, group=group, members=False)
    tables = _pair_tables(s_pad // block, reach=reach, diffusion=diffusion)
    d_v = v.shape[-1]
    out = lambda w, dtype: (jax.ShapeDtypeStruct((b, h, s_pad, w), dtype), spec(w, "q"))
    o, lse = _causal_call(
        functools.partial(_causal_fwd_kernel, two_part=qb is not None, block=block,
                          tile=_sub_tile(block), window=window,
                          diffusion=_in_kernel(diffusion, s_pad, block)),
        tables, (b, h, len(tables[0])),
        _causal_operands([(qa, "q"), (qb, "q"), (ka, "k"), (kb, "k_shared"), (v, "k")],
                         s_pad, spec, 1 if diffusion is None else 2),
        [out(d_v, qa.dtype), out(LANE, jnp.float32)],
        [pltpu.VMEM((block, 1), jnp.float32), pltpu.VMEM((block, 1), jnp.float32),
         pltpu.VMEM((block, d_v), jnp.float32)],
        interpret=interpret, name="causal_attention_fwd",
    )
    return _real_rows(o, s, s_pad, 1 if diffusion is None else 2), lse[..., 0]


def _causal_span(n: int, block: int, widths, itemsize: int,
                 budget: int = CAUSAL_VMEM_BYTES) -> int:
    """How many of its ``n`` key blocks a span of the backward kernel holds:
    all of them where their accumulators fit ``budget`` bytes of VMEM beside
    what a block pair takes, else the fewest spans that do, as equal as ``n``
    allows. ``widths`` are ``(d_a, d_v)`` or ``(d_a, d_v, d_b)``, each a whole
    number of 128-lane tiles there. A key row of a span holds dK_a's and dV's
    float32 accumulators, two copies of their output blocks (Pallas
    double-buffers a block) and two of dK_b's float32 one; a row of a block q,
    k, v and dO twice, ``lse`` and ``D`` twice, dQ's accumulators and two
    copies of its (at most float32) blocks; and the compiler's own scratch
    for a pair's scores and their products is under two ``block²`` float32
    tiles (for a 1024-block of bf16 at widths 128 + 64 and 128 the TPU
    compiler counts 13.4 MiB beside the accumulators, this rule 16: 16 384
    tokens fit one span, 32 768 two)."""
    d_a, d_v, *d_b = (_round_up(w, 128) for w in widths)
    d_b = sum(d_b)
    key_row = (d_a + d_v) * (4 + 2 * itemsize) + d_b * 2 * 4
    block_row = 2 * itemsize * 2 * (d_a + d_b + d_v) + 2 * 2 * 4 * 128 + (d_a + d_b) * 3 * 4
    beside = block * block_row + 2 * block * block * 4
    fits = max((budget - beside) // (block * key_row), 1)
    return -(-n // -(-n // fits))


@functools.partial(_traced_once, static_argnums=(8, 9, 10, 11))
def _causal_bwd(qa, qb, ka, kb, v, o, lse, g, block, interpret, window=None, diffusion=None):
    b, h, group, s, s_pad, block, window, reach = _causal_shape(qa, ka, block, window, diffusion)
    copies = 1 if diffusion is None else 2
    two_part = qb is not None
    d_a, d_v = qa.shape[-1], v.shape[-1]
    second = [qb.shape[-1]] if two_part else []
    n = s_pad // block
    span = _causal_span(n, block, (d_a, d_v, *second), qa.dtype.itemsize)
    spans = -(-n // span)
    o, g = _pad_rows(o, s_pad, copies), _pad_rows(g, s_pad, copies)
    # D = rowsum(dO ∘ O), as for the non-causal kernels: tiny, elementwise
    dd = (g.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1, keepdims=True)
    dd = jnp.broadcast_to(dd, (b, h, s_pad, LANE))
    lse = jnp.broadcast_to(lse[..., None], (b, h, s_pad, LANE))
    spec = functools.partial(_pair_spec, block=block, group=group, members=True, span=span)
    tables = _backward_walk(n, reach=reach, group=group, span=span, diffusion=diffusion)

    def out(kind, w, dtype=qa.dtype):
        shape = {"dq": (spans, b, h), "dk": (b, h // group), "dk_head": (b, h)}[kind]
        rows = s_pad if kind == "dq" else spans * span * block  # whole spans
        return jax.ShapeDtypeStruct((*shape, rows, w), dtype), spec(w, kind)

    # a span's share of dQ leaves in float32 where there is more than one to
    # sum; the shared key part's gradient leaves per query head, in float32
    partial = jnp.float32 if spans > 1 else qa.dtype
    f32 = lambda rows, w: pltpu.VMEM((rows, w), jnp.float32)
    outs = _causal_call(
        functools.partial(_causal_bwd_kernel, two_part=two_part, block=block,
                          tile=_sub_tile(block), window=window, span=span,
                          diffusion=_in_kernel(diffusion, s_pad, block)),
        tables, (b, h // group, len(tables[0])),
        _causal_operands([(qa, "q"), (qb, "q"), (ka, "k"), (kb, "k_shared"), (v, "k"),
                          (g, "q"), (lse, "q"), (dd, "q")], s_pad, spec, copies),
        [*(out("dq", w, partial) for w in [d_a, *second]), out("dk", d_a),
         *(out("dk_head", w, jnp.float32) for w in second), out("dk", d_v)],
        [*(f32(block, w) for w in [d_a, *second]), f32(span * block, d_a), f32(span * block, d_v)],
        interpret=interpret, name="causal_attention_bwd",
    )
    if two_part:
        dqa, dqb, dka, dkb, dv = outs
    else:
        (dqa, dka, dv), dqb, dkb = outs, None, None

    def whole(dq):
        """dQ of its spans' shares: a span never meets the query blocks before
        its own (nor, under a window, those past its reach), and their blocks
        of its share were never written."""
        if spans == 1:
            return dq[0, ..., :s, :] if diffusion is None else rows(dq[0])
        met = np.zeros((spans, n), bool)
        met[tables[1] // span, tables[0]] = True
        met = jnp.asarray(np.repeat(met, block, axis=1))[:, None, None, :, None]
        return rows(jnp.where(met, dq, 0.0).sum(0).astype(qa.dtype))

    rows = functools.partial(_real_rows, rows=s, padded=s_pad, copies=copies)
    if two_part:
        dqb, dkb = whole(dqb), rows(dkb.sum(axis=1).astype(kb.dtype))
    return whole(dqa), dqb, rows(dka), dkb, rows(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def pallas_causal_attention(
    q_a: jax.Array,
    q_b: jax.Array | None,
    k_a: jax.Array,
    k_b: jax.Array | None,
    v: jax.Array,
    block: int | None = None,
    interpret: bool = False,
    window: int | None = None,
    diffusion: int | None = None,
) -> jax.Array:
    """Causal softmax(q_a·k_aᵀ + q_b·k_bᵀ)·v; queries pre-scaled. With
    ``window``, query ``i`` sees keys ``i − window + 1 .. i`` only. With
    ``diffusion`` = ``B``, the ``seq`` rows are a clean copy of ``seq / 2``
    tokens and then a noisy one, and visibility is the block-diffusion
    pattern's over diffusion blocks of ``B`` tokens (the section comment
    above; ``B`` a power of two that divides ``seq / 2`` and the sub-tiles).

    ``q_a``: (batch, heads, seq, d_a); ``k_a``: (batch, kv heads, seq, d_a),
    ``kv heads`` a divisor of ``heads`` (query head ``h`` reads ``h // (heads
    / kv heads)``); ``q_b``: (batch, heads, seq, d_b) and ``k_b``: (batch,
    seq, d_b), one vector a token for all heads, or both None; ``v``:
    (batch, kv heads, seq, d_v). Returns (batch, heads, seq, d_v). ``block``
    None takes ``causal_block``'s for the shape. Forward and backward are
    Pallas kernels over the visible block pairs (see the section comment
    above)."""
    return _causal_fwd(q_a, q_b, k_a, k_b, v, block, interpret, window, diffusion)[0]


def _causal_vjp_fwd(q_a, q_b, k_a, k_b, v, block=None, interpret=False, window=None,
                    diffusion=None):
    o, lse = _causal_fwd(q_a, q_b, k_a, k_b, v, block, interpret, window, diffusion)
    # the primal output and the residual are the one named array
    o = checkpoint_name(o, CAUSAL_OUT_NAME)
    lse = checkpoint_name(lse, CAUSAL_LSE_NAME)
    return o, (q_a, q_b, k_a, k_b, v, o, lse)


def _causal_vjp_bwd(block, interpret, window, diffusion, residuals, g):
    return _causal_bwd(*residuals, g, block, interpret, window, diffusion)


pallas_causal_attention.defvjp(_causal_vjp_fwd, _causal_vjp_bwd)
