"""Pallas TPU kernel for the rotate-half rotary embedding
(``models/lm.rope_half``): one pass over a head-major (batch, heads, seq, d)
array, read once and written once in its own dtype.

``out = x · C + partner(x) · S`` with ``C``, ``S`` (seq, d) float32 tables the
caller makes (``C = [cos, cos, 1 …]``, ``S = [−sin, +sin, 0 …]``: the ``d −
r`` dimensions past the rotary part meet 1 and 0 and pass through) and
``partner(x)[j] = x[j + r/2]`` below ``r/2``, ``x[j − r/2]`` from there to
``r``: a rotation of the lanes by ``r/2``, which is its own inverse where ``r
= d``; where ``r < d`` one rotation each way and a select on the lane. Float32
inside, rounded at the store. The map's transpose is the same map with ``S``
negated, so the caller's backward pass is one more call.

Grid (seq blocks, batch, head blocks), the sequence outermost: a block of the
tables is fetched once for every head that meets it. The output takes the
input's buffer where the caller's value is dead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# elements a grid step at most (a 1 MiB bfloat16 block in, one out, both
# double-buffered beside 4 MiB of float32 working set), and positions a block
# at most, so that the heads fill the step: from the chip (PERF.md §6, PR 35)
BLOCK_ELEMENTS, SEQ_BLOCK = 512 * 1024, 512
VMEM_BYTES = 32 * 1024 * 1024


def rope_blocks(heads: int, seq: int, d: int) -> tuple[int, int] | None:
    """``(heads, positions)`` of a grid step for a (·, heads, seq, d) operand,
    or None where the kernel does not take the shape: the last axis must be
    whole 128-lane tiles, or the half tile of a 64-wide head (Mosaic rotates
    a 64-lane row within its own 64 lanes; a block then fills half of each
    vector register it takes, and the element bound below counts it double),
    and the sequence must cut into blocks of whole sublane tiles of any dtype
    (16 rows)."""
    if (d % 128 and d != 64) or seq % 16:
        return None
    d = max(d, 128)  # what a row takes of VMEM
    sb = max(n for n in range(16, min(seq, SEQ_BLOCK) + 1, 16) if seq % n == 0)
    hb = max((n for n in range(1, heads + 1) if heads % n == 0 and n * sb * d <= BLOCK_ELEMENTS),
             default=1)
    return hb, sb


def _kernel(x_ref, c_ref, s_ref, o_ref, *, r: int):
    x = x_ref[...].astype(jnp.float32)
    d = x.shape[-1]
    lanes = x.ndim - 1
    partner = pltpu.roll(x, r // 2, lanes)  # x[j − r/2]
    if r < d:
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, lanes)
        partner = jnp.where(lane < r // 2, pltpu.roll(x, d - r // 2, lanes), partner)
    o_ref[...] = (x * c_ref[...] + partner * s_ref[...]).astype(o_ref.dtype)


def rotate_half(x, c, s, r: int, *, interpret: bool = False):
    """``x · c + partner(x) · s`` (module docstring): ``x`` (batch, heads,
    seq, d) of a shape ``rope_blocks`` takes, ``c`` and ``s`` (seq, d)
    float32, ``r`` the even width of the rotary part."""
    batch, heads, seq, d = x.shape
    hb, sb = rope_blocks(heads, seq, d)
    table = pl.BlockSpec((sb, d), lambda i, b, h: (i, 0))
    block = pl.BlockSpec((None, hb, sb, d), lambda i, b, h: (b, h, i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, r=r),
        grid=(seq // sb, batch, heads // hb),
        in_specs=[block, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_BYTES,
        ),
        interpret=interpret,
        name="rope_half",
    )(x, c, s)
