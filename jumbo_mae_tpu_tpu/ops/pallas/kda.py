"""Pallas TPU kernels for the chunked gated delta rule (``ops/kda.py``): the
forward and the backward pass over whole sequences with the state, or its
cotangent, in VMEM.

**Forward.** Grid (batch, heads / ``hb``, chunks), the chunks innermost and
sequential. A grid step takes one chunk of ``hb`` heads: its ``q``, ``k``,
``v`` (compute dtype) and ``g`` (float32) arrive as (hb, C, d) blocks
straight from the (batch, heads, seq, d) layout; ``beta`` is resident a head
group, (hb, chunks, C), and the step reads its row. The float32 state (hb,
d_k, d_v) lives in a VMEM scratch across the chunk axis; nothing a chunk
builds (``G``, the decay-ratio products, the inverse, ``W``, ``U``) leaves
VMEM. Several heads a step, batched in every operation, so that the chain of
small dependent products (the inverse is six deep, then ``W``, ``U``, ``O``,
``S``) overlaps across heads and the grid step's fixed cost is shared.

**Backward.** The same grid with the chunks in reverse, the state's
cotangent in the scratch. A step rebuilds its chunk from its inputs and its
kept starting state and transposes it, all in VMEM: the body is ``jax.vjp``
of ``chunk_step`` taken while the kernel is traced, so no gradient of the
inverse or of the decay ratios is written by hand; the five input gradients
are written once.

The arithmetic is ``ops/kda._chunk``'s: ``g``, ``G``, the products ``A`` and
``B``, the inverse and ``S`` are float32, their products in three bfloat16
passes (operands split by hand into a high and a low half: Mosaic has
``DEFAULT`` and ``HIGHEST`` only; where the shapes allow, one MXU pass over
the stacked halves gives all the partial products); ``W``, ``U`` and the
products with the state take operands in the compute dtype and accumulate in
float32. Each kind of product has its transposes written out
(``_with_transposes``), so that a gradient is the same kind of product as
XLA would give it. What differs from ``_chunk`` is the layout of the work,
chosen for the (8, 128) tiling:

- ``G`` is a lower-triangular product with ``g`` cut into three bfloat16
  parts (24 bits: exact against a 0/1 matrix); ``Γ_C`` down the state's
  rows is ``G``'s last row turned on its side;
- the decay ratios are built a sub-block of **rows** at a time (sublane
  slices and concatenations, no 16-lane strip): rows ``I`` take their
  reference in the middle of ``I``, so neither the row factor
  ``e^{G_t − G_ref}`` nor the column factor ``e^{G_ref − G_i}`` has an
  exponent beyond ``sub / 2`` steps of decay either way inside ``I``
  (``e^{±40}`` at ``g = −5``: within the module's overflow rule, and far
  enough from float32's floor that a factor's low bfloat16 half is not
  flushed — a reference at ``I``'s first position cost four digits of ``o``
  at the bound), and the column factor is at most 1 before ``I``; columns
  after ``I`` are masked before the exponential;
- the inverse is the Neumann product over the whole chunk, ``[P; N^k]``
  stacked so that one product a stage gives both ``P N^k`` and ``N^{2k}``.

**Decays with no floor** (``bounded=False``: ``ops/kda.kda_chunked`` knows no
bound under ``g``, or one too deep for a sub-block). A reference in the
middle of ``I`` would raise ``e`` to ``sub / 2`` steps of decay either way,
and one step of ``g = −100`` makes that infinite beside a zero. Rows ``I``
then take their reference at ``I``'s **first** position against the columns
before ``I`` (both factors at most 1), and the (sub, sub) block of ``I``
against itself is built pair by pair, a column at a time: column ``j`` is
``Σ_c r_tc k_jc e^{min(G_tc − G_jc, 0)}``, a lane reduction of a (sub, d_k)
product, placed by a select on the column index; the rows above ``j`` (whose
true exponent would be positive) are held to ``e^0`` and masked with the
rest of the upper triangle. Elementwise float32, no product in halves. It
costs 64 such columns a chunk where the bounded form has four products, so
a configuration that states its floor keeps the form above.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KDA_FWD_NAME, KDA_BWD_NAME = "kda_chunk_fwd", "kda_chunk_bwd"
# heads a grid step at most, from the chip (PERF.md, PR 32; ms a layer at the
# Ling cell's shapes): forward 15.61 · 10.32 · 7.22 · 6.20 at 1 · 2 · 4 · 8
# heads before the high and low passes were stacked, 4.99 · 4.70 at 8 · 16
# after; backward 23.76 · 15.42 · 12.29 at 2 · 4 · 8. The TPU compiler's own
# schedule says the same off the chip (bundles a head and grid step: forward
# 856 · 832 · 836 at 8 · 16 · 32, backward 2336 · 2135 · 2188 at 4 · 8 · 16)
HEADS_PER_STEP = {"fwd": 16, "bwd": 8}
VMEM_BYTES = 64 * 1024 * 1024

F32, BF16 = jnp.float32, jnp.bfloat16


def _contract(a, b, ca: int, cb: int):
    """``a`` · ``b`` contracting axis ``ca`` of ``a``'s last two with axis
    ``cb`` of ``b``'s, any leading axes batched; float32 accumulation."""
    lead = a.ndim - 2
    batch = tuple(range(lead))
    return jax.lax.dot_general(a, b, (((lead + ca,), (lead + cb,)), (batch, batch)),
                               preferred_element_type=F32)


def _parts(x, n: int):
    """``x`` as a sum of ``n`` bfloat16 arrays, the largest first (two carry
    16 bits of a float32, three all 24); a bfloat16 ``x`` is its own one."""
    if x.dtype == BF16:
        return (x,)
    out = []
    for _ in range(n - 1):
        out.append(x.astype(BF16))
        x = x - out[-1].astype(F32)
    return (*out, x.astype(BF16))


def _with_transposes(product, *, round_cotangent: bool = False):
    """``product(a, b, ca, cb)`` (the contraction of ``_contract``) with the
    same kind of product as its two gradients: what XLA gives a
    ``dot_general`` at one precision. ``round_cotangent`` casts the
    cotangent to the other operand's dtype first (a product of compute-dtype
    operands). Left to itself a product of bfloat16 halves would round its
    float32 operands' cotangents to bfloat16."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
    def dot(a, b, ca, cb):
        return product(a, b, ca, cb)

    def fwd(a, b, ca, cb):
        return product(a, b, ca, cb), (a, b)

    def bwd(ca, cb, operands, ct):
        a, b = operands
        to_a, to_b = (ct.astype(b.dtype), ct.astype(a.dtype)) if round_cotangent else (ct, ct)
        d_a = dot(to_a, b, 1, 1 - cb) if ca else dot(b, to_a, 1 - cb, 1)
        d_b = dot(to_b, a, 0, 1 - ca) if cb else dot(a, to_b, 1 - ca, 0)
        return d_a.astype(a.dtype), d_b.astype(b.dtype)

    dot.defvjp(fwd, bwd)
    return dot


# operands in the compute dtype, one pass
_dot = _with_transposes(_contract, round_cotangent=True)


@_with_transposes
def _dot3(a, b, ca: int, cb: int):
    """A float32 product in three bfloat16 passes (XLA's ``HIGH``)."""
    (ah, al), (bh, bl) = _parts(a, 2), _parts(b, 2)
    m, n = a.shape[-1 - ca], b.shape[-1 - cb]  # the output's (rows, columns)
    if ca == 1 and (cb or 2 * n <= 128):
        # one pass over [ah; al] against [bh | bl] (stacked along the rows
        # they keep, or along lanes with room) gives all four quadrants
        both = _contract(jnp.concatenate([ah, al], axis=-2),
                         jnp.concatenate([bh, bl], axis=-1 - cb), ca, cb)
        return (both[..., :m, :n] + both[..., :m, n:]) + both[..., m:, :n]
    return _contract(ah, bh, ca, cb) + (_contract(ah, bl, ca, cb) + _contract(al, bh, ca, cb))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) + axis)


def _triangle_sums(x, *, after: bool):
    """``Σ_{j<=t} x_j`` down the axis before the last (``after``: ``Σ_{j>=t}``),
    float32 to the last bit: three bfloat16 parts against a 0/1 triangle."""
    c = x.shape[-2]
    square = (*x.shape[:-2], c, c)
    row, col = _iota(square, -2), _iota(square, -1)
    triangle = (col >= row if after else col <= row).astype(BF16)
    return sum(_contract(triangle, part, 1, 0) for part in _parts(x, 3))


@jax.custom_vjp
def _cumsum(x):
    return _triangle_sums(x, after=False)


_cumsum.defvjp(lambda x: (_cumsum(x), None),
               lambda _, ct: (_triangle_sums(ct, after=True),))


def _pair_by_pair(qf, kf, cum, lo: int, sub: int, before):
    """Rows ``lo .. lo + sub`` of the decay-ratio products of ``q`` and of
    ``k`` against ``k``, (..., sub, C) each, with no exponent above 0: the
    columns before ``lo`` from ``before`` (..., 2 sub, C), the rows' product
    about the reference ``G_lo`` (None where ``lo`` is 0); the sub-block
    against itself a column at a time (module docstring); columns after it
    are left for the caller's triangle mask."""
    c = cum.shape[-2]
    q_i, k_i, g_i = (x[..., lo:lo + sub, :] for x in (qf, kf, cum))
    column = _iota((*cum.shape[:-2], sub, c), -1)
    zero = jnp.zeros((*cum.shape[:-2], sub, c), F32)
    qk, kk = (zero, zero) if before is None else (before[..., :sub, :], before[..., sub:, :])
    for j in range(sub):
        ratio = jnp.exp(jnp.minimum(g_i - g_i[..., j:j + 1, :], 0.0))
        k_j = kf[..., lo + j:lo + j + 1, :] * ratio
        here = column == lo + j
        qk = jnp.where(here, jnp.sum(q_i * k_j, axis=-1, keepdims=True), qk)
        kk = jnp.where(here, jnp.sum(k_i * k_j, axis=-1, keepdims=True), kk)
    return qk, kk


def chunk_step(state, q, k, v, g, beta, *, sub: int, bounded: bool = True):
    """One chunk of ``ops/kda._chunk`` in operations Mosaic lowers: ``state``
    (..., d_k, d_v) float32; ``q``, ``k`` (..., C, d_k), ``v`` (..., C, d_v)
    in the compute dtype; ``g`` (..., C, d_k) float32; ``beta`` (..., 1, C)
    float32, a row. Returns ``(state at the end, o (..., C, d_v) float32)``.
    ``bounded``: whether a sub-block's decays stay inside float32 about a
    reference position (module docstring)."""
    dtype = v.dtype
    c, d_k = k.shape[-2:]
    lead = k.shape[:-2]
    sq = (*lead, c, c)
    row, col = _iota(sq, -2), _iota(sq, -1)

    cum = _cumsum(g)  # G_t = Σ_{j<=t} g_j
    # Γ_C down the state's rows: G's last row, turned on its side
    last = jnp.broadcast_to(cum[..., c - 1:, :], (*lead, 8, d_k))
    total_col = jnp.swapaxes(last, -1, -2)[..., :1]  # (..., d_k, 1)

    qf, kf = q.astype(F32), k.astype(F32)
    position = _iota(cum.shape, -2)
    qk_rows, kk_rows = [], []
    for lo in range(0, c, sub):
        hi = lo + sub
        if not bounded:
            before = None
            if lo:  # against the columns before I, about G_lo: no factor above 1
                ref = cum[..., lo:lo + 1, :]
                away = jnp.exp(cum[..., lo:hi, :] - ref)
                rows = jnp.concatenate([qf[..., lo:hi, :] * away, kf[..., lo:hi, :] * away],
                                       axis=-2)
                early = position < lo
                cols = jnp.where(early, kf, 0.0) * jnp.exp(jnp.where(early, ref - cum, 0.0))
                before = _dot3(rows, cols, 1, 1)
            qk_i, kk_i = _pair_by_pair(qf, kf, cum, lo, sub, before)
            qk_rows.append(qk_i)
            kk_rows.append(kk_i)
            continue
        ref = cum[..., lo + sub // 2:lo + sub // 2 + 1, :]
        away = jnp.exp(cum[..., lo:hi, :] - ref)
        rows = jnp.concatenate([qf[..., lo:hi, :] * away, kf[..., lo:hi, :] * away], axis=-2)
        cols = kf * jnp.exp(jnp.where(position < hi, ref - cum, 0.0))
        both = _dot3(rows, cols, 1, 1)  # (..., 2 sub, C)
        qk_rows.append(both[..., :sub, :])
        kk_rows.append(both[..., sub:, :])
    qk = jnp.where(col <= row, jnp.concatenate(qk_rows, axis=-2), 0.0)
    kk = jnp.where(col < row, jnp.concatenate(kk_rows, axis=-2), 0.0)

    # T = (I + Diag(β) kk)⁻¹ Diag(β): (I + N)⁻¹ = (I − N)(I + N²)(I + N⁴) ...
    eye = col == row
    beta_col = jnp.sum(jnp.where(eye, beta, 0.0), axis=-1, keepdims=True)
    nil = beta_col * kk
    inv = jnp.where(eye, 1.0, 0.0) - nil
    power, reach = _dot3(nil, nil, 1, 0), 2
    while reach < c:
        if 2 * reach < c:
            both = _dot3(jnp.concatenate([inv, power], axis=-2), power, 1, 0)
            inv, power = inv + both[..., :c, :], both[..., c:, :]
        else:
            inv = inv + _dot3(inv, power, 1, 0)
        reach *= 2
    ut = (inv * beta).astype(dtype)

    decay = jnp.exp(cum)
    lo_ = lambda x: x.astype(dtype)
    s = lo_(state)
    w = _dot(ut, lo_(kf * decay), 1, 0)
    u = lo_(_dot(ut, v, 1, 0) - _dot(lo_(w), s, 1, 0))  # U = U_0 − W S_0
    o = _dot(lo_(qf * decay), s, 1, 0) + _dot(lo_(qk), u, 1, 0)
    k_out = lo_(kf * jnp.exp(cum[..., c - 1:, :] - cum))
    state = jnp.exp(total_col) * state + _dot(k_out, u, 0, 0)
    return state, o


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state_ref, *rest, sub: int,
                bounded: bool = True):
    *starts_ref, s_sc = rest
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_sc[...] = jnp.zeros(s_sc.shape, F32)

    state = s_sc[...]
    if starts_ref:
        starts_ref[0][...] = state
    state, o = chunk_step(state, q_ref[...], k_ref[...], v_ref[...], g_ref[...],
                          beta_ref[:, pl.ds(c, 1), :], sub=sub, bounded=bounded)
    o_ref[...] = o.astype(o_ref.dtype)
    s_sc[...] = state

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        state_ref[...] = state


def suits(d_k: int, d_v: int, chunk: int, sub: int) -> bool:
    """Whether the kernel takes these shapes: full lanes a head, and
    sub-blocks of whole sublane tiles of the compute dtype."""
    return d_k % 128 == 0 and d_v % 128 == 0 and chunk % sub == 0 and sub % 16 == 0


def heads_per_step(heads: int, kernel: str) -> int:
    """The largest divisor of ``heads`` within ``HEADS_PER_STEP[kernel]``."""
    return max(n for n in range(1, HEADS_PER_STEP[kernel] + 1) if heads % n == 0)


def kda_forward(q, k, v, g, beta, *, chunk: int, sub: int, with_starts: bool,
                interpret: bool = False, bounded: bool = True):
    """The chunked gated delta rule from a zero state, ``ops/kda.kda_chunked``'s
    arguments with ``seq`` a multiple of ``chunk``. Returns ``(o, state)``,
    and with ``with_starts`` also the state at every chunk's start,
    (chunks, batch, heads, d_k, d_v) float32: what the backward pass
    differentiates a chunk from."""
    batch, heads, seq, d_k = k.shape
    d_v, n = v.shape[-1], seq // chunk
    hb = heads_per_step(heads, "fwd")
    at_chunk = lambda width: pl.BlockSpec((None, hb, chunk, width),
                                          lambda b, h, c: (b, h, c, 0))
    state_spec = pl.BlockSpec((None, hb, d_k, d_v), lambda b, h, c: (b, h, 0, 0))
    out_specs = [at_chunk(d_v), state_spec]
    out_shape = [jax.ShapeDtypeStruct((batch, heads, seq, d_v), v.dtype),
                 jax.ShapeDtypeStruct((batch, heads, d_k, d_v), F32)]
    if with_starts:
        out_specs.append(pl.BlockSpec((None, None, hb, d_k, d_v),
                                      lambda b, h, c: (c, b, h, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, batch, heads, d_k, d_v), F32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sub=sub, bounded=bounded),
        grid=(batch, heads // hb, n),
        in_specs=[at_chunk(d_k), at_chunk(d_k), at_chunk(d_v), at_chunk(d_k),
                  pl.BlockSpec((None, hb, n, chunk), lambda b, h, c: (b, h, 0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, d_k, d_v), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES,
        ),
        interpret=interpret,
        name=KDA_FWD_NAME,
    )(q, k, v, g.astype(F32), beta.astype(F32).reshape(batch, heads, n, chunk))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, do_ref, dstate_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_sc, *, sub: int,
                bounded: bool = True):
    step = pl.program_id(2)
    c = pl.num_programs(2) - 1 - step

    @pl.when(step == 0)
    def _():
        ds_sc[...] = dstate_ref[...]

    _, transpose = jax.vjp(
        functools.partial(chunk_step, sub=sub, bounded=bounded), starts_ref[...], q_ref[...],
        k_ref[...],
        v_ref[...], g_ref[...], beta_ref[:, pl.ds(c, 1), :])
    d_start, dq, dk, dv, dg, dbeta = transpose((ds_sc[...], do_ref[...].astype(F32)))
    ds_sc[...] = d_start
    dq_ref[...], dk_ref[...], dv_ref[...], dg_ref[...] = dq, dk, dv, dg
    dbeta_ref[:, pl.ds(c, 1), :] = dbeta


def kda_backward(q, k, v, g, beta, starts, d_o, d_state, *, chunk: int, sub: int,
                 interpret: bool = False, bounded: bool = True):
    """The five input gradients of ``kda_forward`` from its kept chunk-start
    states: the chunks in reverse, the state's cotangent in VMEM, each chunk
    rebuilt from its inputs and its start and transposed in VMEM
    (``jax.vjp`` of ``chunk_step`` at trace time)."""
    batch, heads, seq, d_k = k.shape
    d_v, n = v.shape[-1], seq // chunk
    hb = heads_per_step(heads, "bwd")
    at_chunk = lambda width: pl.BlockSpec((None, hb, chunk, width),
                                          lambda b, h, c: (b, h, n - 1 - c, 0))
    a_group = pl.BlockSpec((None, hb, n, chunk), lambda b, h, c: (b, h, 0, 0))
    shape = lambda x, dtype=None: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub, bounded=bounded),
        grid=(batch, heads // hb, n),
        in_specs=[at_chunk(d_k), at_chunk(d_k), at_chunk(d_v), at_chunk(d_k), a_group,
                  pl.BlockSpec((None, None, hb, d_k, d_v), lambda b, h, c: (n - 1 - c, b, h, 0, 0)),
                  at_chunk(d_v),
                  pl.BlockSpec((None, hb, d_k, d_v), lambda b, h, c: (b, h, 0, 0))],
        out_specs=[at_chunk(d_k), at_chunk(d_k), at_chunk(d_v), at_chunk(d_k), a_group],
        out_shape=[shape(q), shape(k), shape(v), shape(g, F32),
                   jax.ShapeDtypeStruct((batch, heads, n, chunk), F32)],
        scratch_shapes=[pltpu.VMEM((hb, d_k, d_v), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES,
        ),
        interpret=interpret,
        name=KDA_BWD_NAME,
    )(q, k, v, g.astype(F32), beta.astype(F32).reshape(batch, heads, n, chunk), starts, d_o,
      d_state.astype(F32))
    return dq, dk, dv, dg, dbeta.reshape(batch, heads, seq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def kda_kernels(q, k, v, g, beta, chunk: int, sub: int, interpret: bool = False,
                bounded: bool = True):
    """``ops/kda.kda_chunked`` (``seq`` a multiple of ``chunk``, ``g`` and
    ``beta`` float32) as the two kernels: ``(o, state)``, differentiable in
    all five inputs."""
    return kda_forward(q, k, v, g, beta, chunk=chunk, sub=sub, with_starts=False,
                       interpret=interpret, bounded=bounded)


def _kernels_fwd(q, k, v, g, beta, chunk, sub, interpret, bounded):
    o, state, starts = kda_forward(q, k, v, g, beta, chunk=chunk, sub=sub, with_starts=True,
                                   interpret=interpret, bounded=bounded)
    return (o, state), (q, k, v, g, beta, starts)


def _kernels_bwd(chunk, sub, interpret, bounded, residuals, cotangents):
    return kda_backward(*residuals, *cotangents, chunk=chunk, sub=sub, interpret=interpret,
                        bounded=bounded)


# under a ``jax.checkpoint`` the block's first forward pass needs no residual:
# ``optimize_remat`` runs the primal function there, the kernel that keeps none
kda_kernels.defvjp(_kernels_fwd, _kernels_bwd, optimize_remat=True)
