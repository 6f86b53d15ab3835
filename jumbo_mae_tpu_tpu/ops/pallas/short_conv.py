"""Pallas TPU kernels for what stands between the linear-attention layers'
projections and their chunk kernels (``ops/kda.short_conv``): the depth-wise
causal filter of a few taps, SiLU and, for q and k, the L2 norm over the
head's width with its scale — one pass over a head-major (batch, heads, seq,
d) array each way, read once and written once in its own dtype, float32
inside.

``y = unit(round(silu(Σ_j w_j ⊙ x_{t−K+1+j}))) · scale`` with zero history
before position 0, ``unit(y) = y / sqrt(Σ y² + eps)`` over the last axis
(left out where ``scale`` is None: v), and ``round`` the operand's dtype —
the plain form's rounding point (``ops/kda.causal_conv`` returns that dtype
and the norm reads it), so the two agree to the last bit but for a sum's
order.

**A grid step** takes a block of (heads, positions, d) and walks it a strip
at a time (one head, ``STRIP`` positions: 16 vector registers a value), so
that a strip's whole chain — the shifts, the filter, the sigmoid, the norm —
stays in registers between its one load and its one store. A shift by ``s``
positions is a sublane rotation of the strip with its first ``s`` rows taken
from the 8 rows before it: the block's own rows, or for the block's first
strip the tail of a second view of the same operand, a 16-row block that
ends where this block starts (masked to zero before position 0). No padded
copy exists anywhere.

**Forward**: grid (batch, head blocks, seq blocks), every axis parallel.

**Backward**, from ``x``, ``w`` and ``dy`` alone: the same grid with the
sequence innermost, sequential and **in reverse**, and the strips of a block
in reverse. A strip rebuilds ``z``, the sigmoid and the norm for its rows,
forms ``dz``, and needs ``dz`` of the ``K − 1`` rows after it for ``dx_t =
Σ_j w_j ⊙ dz_{t+K−1−j}``: those are the first rows of the strip it has just
left, kept 8 rows a head in a VMEM scratch (zero past the sequence's end),
so nothing is rebuilt twice and no following halo is read. ``dx`` is written
once; the taps' ``dw`` accumulate in float32, 8 partial rows a head and tap,
in an output block that stays resident along the sequence axis; the (batch,
8) partials are summed outside. The cotangent that leaves the norm is
rounded to the operand's dtype, as the plain form's is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FWD_NAME, BWD_NAME = "kda_short_conv_fwd", "kda_short_conv_bwd"
# elements a grid step at most and positions a block at most: the rope
# kernel's (a 1 MiB bfloat16 block a ref, double-buffered, six of them in the
# backward kernel: inside the compiler's own VMEM limit; PERF.md §6, PR 35)
BLOCK_ELEMENTS, SEQ_BLOCK = 512 * 1024, 512
# positions a strip at most (16 float32 vector registers a value at 128 lanes:
# the TPU compiler's own schedule, bundles a register, read 24.0 · 20.4 · 18.5
# forward and 36.0 · 27.6 · 25.4 backward at 32 · 64 · 128, PERF.md §6, PR 46);
# rows of history a strip reads before itself (a float32 sublane tile: the
# filter may have 9 taps) and rows of a history block (a sublane tile of any
# dtype)
STRIP, HISTORY, HALO = 128, 8, 16

F32 = jnp.float32


def short_conv_blocks(heads: int, seq: int, d: int) -> tuple[int, int] | None:
    """``(heads, positions)`` of a grid step for a (·, heads, seq, d) operand,
    or None where the kernels do not take the shape: the last axis must be
    whole 128-lane tiles and the sequence must cut into blocks of whole
    sublane tiles of any dtype (16 rows)."""
    if d % 128 or seq % HALO:
        return None
    sb = max(n for n in range(HALO, min(seq, SEQ_BLOCK) + 1, HALO) if seq % n == 0)
    hb = max((n for n in range(1, heads + 1) if heads % n == 0 and n * sb * d <= BLOCK_ELEMENTS),
             default=1)
    return hb, sb


def _strip(sb: int, d: int) -> int:
    """Positions a strip of a block of ``sb``: ``STRIP`` at 128 lanes, halved
    until a value is no more registers at ``d`` and the strip cuts the block
    whole, down to a history block."""
    strip = STRIP
    while strip > HALO and (strip * d > STRIP * 128 or sb % strip):
        strip //= 2
    return strip


def _sigmoid(z):
    """``1 / (1 + e^{−z})`` as XLA forms it on the chip (the exponential, the
    approximate reciprocal and one Newton step), without the divide's care
    for an infinite or zero divisor: the exponent is held to 80 instead, so
    that ``1 + e`` stays finite (``sigmoid(−80) = 1.8e−35``)."""
    d = 1.0 + jnp.exp(jnp.minimum(-z, 80.0))
    r = pl.reciprocal(d, approx=True)
    return r * (2.0 - d * r)


def _rows(x):
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 2)


def _behind(cur, before, s: int):
    """``cur[t − s]`` down the rows, the rows before the first from
    ``before``, the ``HISTORY`` rows that precede ``cur``."""
    if s == 0:
        return cur
    axis = cur.ndim - 2
    rolled = pltpu.roll(cur, s, axis)
    head = jnp.where(_rows(before) < s, pltpu.roll(before, s, axis), rolled[..., :HISTORY, :])
    return jnp.concatenate([head, rolled[..., HISTORY:, :]], axis=-2)


def _ahead(cur, after, s: int):
    """``cur[t + s]`` down the rows, the rows past the last from ``after``,
    the ``HISTORY`` rows that follow ``cur``."""
    if s == 0:
        return cur
    axis, n = cur.ndim - 2, cur.shape[-2]
    rolled = pltpu.roll(cur, n - s, axis)
    tail = jnp.where(_rows(after) >= HISTORY - s, pltpu.roll(after, HISTORY - s, axis),
                     rolled[..., n - HISTORY:, :])
    return jnp.concatenate([rolled[..., :n - HISTORY, :], tail], axis=-2)


def _filtered(w, behind):
    """``Σ_j w_j ⊙ x_{t−K+1+j}`` from the shifted strips, in the plain form's
    order."""
    return sum(w_j * x_j for w_j, x_j in zip(w, behind))


def _walk(x_ref, prev_ref, w_ref, first_block, strip: int, reverse: bool, body):
    """``body(heads, rows, w, cur, before)`` for every strip of the block:
    ``heads`` and ``rows`` its slices of the block, ``w`` the taps' filters
    for its head, (1, 1, d) float32 each, ``cur`` its rows of ``x`` and
    ``before`` the ``HISTORY`` rows that precede them, float32 (zero where
    ``first_block`` says that position 0 is this block's first)."""
    hb, sb, _ = x_ref.shape
    taps, strips = w_ref.shape[0], sb // strip

    def per_head(h, carry):
        heads = pl.ds(h, 1)
        w = [w_ref[j, heads] for j in range(taps)]
        halo = prev_ref[heads]
        halo = jnp.where(first_block, jnp.zeros_like(halo), halo)

        def per_strip(n, carry):
            t = strips - 1 - n if reverse else n
            rows = pl.ds(pl.multiple_of(t * strip, strip), strip)
            back = pl.ds(pl.multiple_of(jnp.maximum(t * strip - HALO, 0), HALO), HALO)
            before = jnp.where(t == 0, halo, x_ref[heads, back, :])
            body(heads, rows, w, x_ref[heads, rows, :].astype(F32),
                 before.astype(F32)[..., HALO - HISTORY:, :])
            return carry

        return jax.lax.fori_loop(0, strips, per_strip, carry)

    jax.lax.fori_loop(0, hb, per_head, 0)


def _fwd_kernel(x_ref, prev_ref, w_ref, o_ref, *, scale, eps: float, strip: int):
    taps = w_ref.shape[0]

    def body(heads, rows, w, cur, before):
        z = _filtered(w, [_behind(cur, before, taps - 1 - j) for j in range(taps)])
        y = (z * _sigmoid(z)).astype(o_ref.dtype)
        if scale is not None:
            y = y.astype(F32)
            y = y * jax.lax.rsqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True) + eps)
            if scale != 1.0:
                y = y * scale
        o_ref[heads, rows, :] = y.astype(o_ref.dtype)

    _walk(x_ref, prev_ref, w_ref, pl.program_id(2) == 0, strip, False, body)


def _bwd_kernel(x_ref, prev_ref, dy_ref, w_ref, dx_ref, dw_ref, after_ref, *, scale, eps: float,
                strip: int):
    taps = w_ref.shape[0]
    i = pl.program_id(2)  # the sequence's blocks from its last

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        after_ref[...] = jnp.zeros_like(after_ref)  # nothing follows the sequence's end

    def body(heads, rows, w, cur, before):
        z = _filtered(w, [_behind(cur, before, taps - 1 - j) for j in range(taps)])
        s = _sigmoid(z)
        dy = dy_ref[heads, rows, :].astype(F32)
        if scale is not None:
            y = (z * s).astype(dx_ref.dtype).astype(F32)
            r = jax.lax.rsqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True) + eps)
            if scale != 1.0:
                dy = dy * scale
            dy = r * (dy - y * (r * r * jnp.sum(dy * y, axis=-1, keepdims=True)))
            dy = dy.astype(dx_ref.dtype).astype(F32)
        dz = dy * s * (1.0 + z * (1.0 - s))
        # dz_{t+K−1−j}: what dx sums under w_j, and what x_t meets in dw_j
        ahead = [_ahead(dz, after_ref[heads], taps - 1 - j) for j in range(taps)]
        after_ref[heads] = dz[..., :HISTORY, :]
        dx_ref[heads, rows, :] = _filtered(w, ahead).astype(dx_ref.dtype)
        for j in range(taps):
            part = cur * ahead[j]
            dw_ref[j, heads] += sum(part[..., r0:r0 + HISTORY, :]
                                    for r0 in range(0, part.shape[-2], HISTORY))

    _walk(x_ref, prev_ref, w_ref, i == pl.num_programs(2) - 1, strip, True, body)


def _specs(x, w, reverse: bool):
    """The grid and the block specs both kernels share: ``x``'s block, its
    history block, the filters' block; the sequence's blocks from the last
    where ``reverse``."""
    batch, heads, seq, d = x.shape
    hb, sb = short_conv_blocks(heads, seq, d)
    blocks = seq // sb
    at = (lambda i: blocks - 1 - i) if reverse else (lambda i: i)
    block = pl.BlockSpec((None, hb, sb, d), lambda b, h, i: (b, h, at(i), 0))
    halo = pl.BlockSpec((None, hb, HALO, d),
                        lambda b, h, i: (b, h, jnp.maximum(at(i) * (sb // HALO) - 1, 0), 0))
    filters = pl.BlockSpec((w.shape[0], hb, 1, d), lambda b, h, i: (0, h, 0, 0))
    return (batch, heads // hb, blocks), block, halo, filters, _strip(sb, d)


def short_conv_forward(x, w, scale: float | None, eps: float, *, interpret: bool = False):
    """The module docstring's ``y``: ``x`` (batch, heads, seq, d) of a shape
    ``short_conv_blocks`` takes, ``w`` (taps, heads, d) with at most
    ``HISTORY + 1`` taps; ``scale`` None leaves the norm out."""
    grid, block, halo, filters, strip = _specs(x, w, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, eps=eps, strip=strip),
        grid=grid,
        in_specs=[block, halo, filters],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name=FWD_NAME,
    )(x, x, w.astype(F32)[:, :, None, :])


def short_conv_backward(x, w, dy, scale: float | None, eps: float, *, interpret: bool = False):
    """``(dx, dw)`` of ``short_conv_forward`` at ``(x, w)`` under the
    cotangent ``dy``: ``dx`` in ``x``'s dtype, ``dw`` (taps, heads, d)
    float32."""
    batch, heads, _, d = x.shape
    taps = w.shape[0]
    grid, block, halo, filters, strip = _specs(x, w, True)
    hb = block.block_shape[1]
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, eps=eps, strip=strip),
        grid=grid,
        in_specs=[block, halo, block, filters],
        out_specs=[block, pl.BlockSpec((None, taps, hb, HISTORY, d),
                                       lambda b, h, i: (b, 0, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((batch, taps, heads, HISTORY, d), F32)],
        scratch_shapes=[pltpu.VMEM((hb, HISTORY, d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=BWD_NAME,
    )(x, x, dy, w.astype(F32)[:, :, None, :])
    return dx, dw.sum(axis=(0, 3))
