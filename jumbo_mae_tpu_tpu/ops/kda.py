"""The gated delta rule with a per-channel decay (Kimi Delta Attention's
core), in chunked form, and the causal depthwise convolution that feeds it —
``causal_conv``, one function with the activation an argument, which serves
two families of layer: with SiLU the linear-attention layers' q, k and v
(``models/lm.KdaAttention``), with none the gated short-convolution mixer's
three-tap filter (``models/lm.ShortConv``). ``short_conv`` is what the
linear-attention layers call: that filter, SiLU and q's and k's L2 norm, on
the TPU one Pallas pass a tensor each way (``ops/pallas/short_conv.py``).

Per head, with a state ``S`` of shape (d_k, d_v) that starts at zero::

    S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t−1} + β_t k_t v_tᵀ,   o_t = S_tᵀ q_t

``α_t = exp(g_t)``, ``g_t <= 0`` one log-decay a key channel. Work and memory
are linear in the sequence: nothing sized (tokens, d_k, d_v) is ever built.

**Chunked form.** Inside a chunk of ``C`` positions that starts from ``S_0``,
with ``G_t = Σ_{j<=t} g_j`` (float32) and ``Γ_t = exp(G_t)``, write
``S_t = Diag(α_t) S_{t−1} + k_t u_tᵀ`` with the pseudo-value
``u_t = β_t (v_t − S_{t−1}ᵀ (α_t ⊙ k_t))``. Unrolled over the chunk::

    (I + A) U = Diag(β) (V − (Γ ⊙ K) S_0),  A_ti = β_t Σ_c k_tc k_ic e^{G_tc − G_ic}  (i < t)
    O = (Γ ⊙ Q) S_0 + B U,                   B_ti =     Σ_c q_tc k_ic e^{G_tc − G_ic}  (i <= t)
    S_C = Diag(Γ_C) S_0 + (K ⊙ e^{G_C − G})ᵀ U

so with ``T = (I + A)⁻¹ Diag(β)`` (the UT transform: ``I + A`` is unit lower
triangular), ``W = T (Γ ⊙ K)`` and ``U_0 = T V``, a chunk costs three products
with the state, ``U = U_0 − W S_0``, ``O = (Γ ⊙ Q) S_0 + B U`` and the update.

**Decay ratios without overflow.** ``e^{G_t − G_i}`` is at most 1, but its two
factors are not: with ``g >= −5`` a chunk of 64 reaches ``e^{±320}``. ``A`` and
``B`` are therefore built over sub-blocks of ``sub`` (16) positions. A pair of
sub-blocks ``I > J`` takes its reference at the last position of ``J``: both
``e^{G_t − G_ref}`` and ``e^{G_ref − G_i}`` are then at most 1. A diagonal pair
takes it at its first position: the column factor is at most ``e^{5·15}``,
inside float32. That rests on a floor under ``g`` (the safe gate's
``lower_bound``): where the caller names none (``floor=None``: a softplus
gate), or one too low for a sub-block (``−floor · sub > 80``), a diagonal
pair is built **pair by pair** instead, ``Σ_c r_tc k_ic e^{G_tc − G_ic}`` over
(sub, sub, d_k) with the exponent taken after the subtraction, so that every
exponent is ``<= 0`` whatever one step's decay: a single ``g = −100`` inside a
sub-block would make the reference form's column factor infinite beside a
row factor of zero. ``g``, ``G``, ``A``, ``B``, the inverse and ``S`` are float32
(the products in three bfloat16 passes) whatever the compute dtype; ``W``, ``U``
and the products with the state take operands in the compute dtype and
accumulate in float32.

**Schedule.** One algorithm, two schedules of it, chosen by what the code
can observe (as ``ops/attention.lowering`` and ``grouped_matmul(impl="auto")``
choose): on a TPU, where ``d_k`` and ``d_v`` are multiples of 128 and the
sub-block one of 16, two Pallas kernels (``ops/pallas/kda.py``) under a VJP
of their own; elsewhere (the CPU's tests, toy widths) a ``lax.scan`` over
the chunks that JAX differentiates, a step one chunk of every head and
batch row, rematerialised in the backward pass (``jax.checkpoint``).

- Forward kernel: walks the chunks with ``S`` in VMEM, a few heads a grid
  step, reading each chunk's operands straight from the (batch, heads, seq,
  ·) layout; nothing a chunk builds leaves VMEM.
- Kept across the backward pass: the five inputs and the state at every
  chunk's start, (tokens / C, d_k, d_v) float32 a head and batch row — 537 MB
  a layer at the Ling cell's shapes, live only during that layer's backward
  pass; the scan keeps the same. A plain forward pass, and a rematted
  block's first one (``optimize_remat``), runs the variant that keeps nothing.
- Backward kernel: the chunks in reverse with ``dS`` in VMEM; each chunk is
  rebuilt from its inputs and its kept start and transposed there
  (``jax.vjp`` of the kernel's own chunk function, taken while the kernel is
  traced), the five gradients written once.

So on the TPU a rematted block runs the forward kernel twice and the
backward kernel once, and no loop is left in the program.

Why kernels (PERF.md, PR 31–32; one layer at the Ling cell's shapes on a
v5e): as XLA the scan's step is ≈ 60 small fusions over (64 head·rows, 64,
128) arrays, 176 µs a step, bound by their number and not their size —
steps that batch several chunks lost (126.7 ms a layer forward and backward
at 8 chunks a step, 93.8 at 4, 63.2 at one), and inside the real step the
same scan cost half as much again as alone (22.5 against 14.9 ms).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# float32 products in three bfloat16 passes: 16 bits of mantissa, more than
# the compute dtype keeps of their results; six passes (HIGHEST) cost 18% of
# the layer's forward and backward time and moved its output by 1.2e-4 of its
# norm (PERF.md, PR 31)
PRECISE = jax.lax.Precision.HIGH


def _causal_conv(x, w):
    """``Σ_j w_j ⊙ x_{t−K+1+j}`` in float32, zero history before position 0."""
    taps, seq = w.shape[0], x.shape[-2]
    padded = jnp.pad(x.astype(jnp.float32), [(0, 0)] * (x.ndim - 2) + [(taps - 1, 0), (0, 0)])
    w = w.astype(jnp.float32)
    return sum(padded[..., j:j + seq, :] * w[j][..., None, :] for j in range(taps))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def causal_conv(x, w, act: str | None = "silu"):
    """``act(Σ_j w_j ⊙ x_{t−K+1+j})`` along the axis before the last, zero
    history before the first position: ``x`` (..., seq, width), one filter of
    ``K`` taps a channel, ``w`` (K, ..., width) broadcast against ``x``
    without its sequence axis; ``act`` is ``"silu"`` (the linear-attention
    layers' q, k, v) or None (the gated short-convolution mixer's filter: no
    activation). Float32 inside. The gradient is written out (the same
    shifted sums run backwards, from ``x`` and ``w`` alone), so that the
    backward pass is a few fused loops and keeps no activation."""
    z = _causal_conv(x, w)
    return (jax.nn.silu(z) if act == "silu" else z).astype(x.dtype)


def _conv_fwd(x, w, act):
    return causal_conv(x, w, act), (x, w)


def _conv_bwd(act, residuals, dy):
    x, w = residuals
    taps, seq = w.shape[0], x.shape[-2]
    if act == "silu":
        z = _causal_conv(x, w)
        s = jax.nn.sigmoid(z)
        dz = dy.astype(jnp.float32) * s * (1.0 + z * (1.0 - s))
    else:
        dz = dy.astype(jnp.float32)
    ahead = jnp.pad(dz, [(0, 0)] * (x.ndim - 2) + [(0, taps - 1), (0, 0)])
    wf = w.astype(jnp.float32)
    dx = sum(ahead[..., taps - 1 - j:taps - 1 - j + seq, :] * wf[j][..., None, :]
             for j in range(taps))
    behind = jnp.pad(x.astype(jnp.float32), [(0, 0)] * (x.ndim - 2) + [(taps - 1, 0), (0, 0)])
    over = tuple(range(x.ndim - w.ndim)) + (x.ndim - 2,)  # batch axes and positions
    dw = jnp.stack([(behind[..., j:j + seq, :] * dz).sum(axis=over) for j in range(taps)])
    return dx.astype(x.dtype), dw.astype(w.dtype)


causal_conv.defvjp(_conv_fwd, _conv_bwd)

# under the root of q's and k's L2 norm; not the RMSNorms' rms_eps
UNIT_EPS = 1e-6


def short_conv_plain(x, w, unit_scale: float | None = None):
    """``short_conv`` as ``jax.numpy`` has it: the filter and SiLU rounded to
    ``x``'s dtype, then, where ``unit_scale`` is a number, ``y / sqrt(Σ y² +
    UNIT_EPS)`` over the last axis times it, float32 inside."""
    y = causal_conv(x, w, "silu")
    if unit_scale is None:
        return y
    yf = y.astype(jnp.float32)
    unit = yf * jax.lax.rsqrt(jnp.sum(jnp.square(yf), axis=-1, keepdims=True) + UNIT_EPS)
    return (unit * unit_scale).astype(x.dtype)


def short_conv(x, w, unit_scale: float | None = None, *, interpret: bool = False):
    """What a linear-attention layer does to q, k or v between its projection
    and the chunk kernels: ``causal_conv(x, w, "silu")`` and, where
    ``unit_scale`` is a number (q: ``d ** -0.5``, k: 1; v: None), the L2 norm
    over the last axis times it, in ``x``'s dtype. ``x`` (batch, heads, seq,
    d), ``w`` (taps, heads, d).

    The schedule follows the backend and the shapes, as ``kda_chunked``'s
    does: on a TPU, for a shape ``ops/pallas/short_conv.short_conv_blocks``
    takes, one Pallas pass forward and one backward (from ``x``, ``w`` and the
    cotangent alone, as ``causal_conv`` keeps them), the plain form's rounding
    points kept; elsewhere ``short_conv_plain``, which JAX differentiates.
    ``interpret`` runs the kernels in the Pallas interpreter (tests)."""
    from jumbo_mae_tpu_tpu.ops.pallas.short_conv import HISTORY, short_conv_blocks

    suits = (x.ndim == 4 and w.shape == (w.shape[0], x.shape[1], x.shape[3])
             and w.shape[0] <= HISTORY + 1 and short_conv_blocks(*x.shape[1:]) is not None)
    if interpret and not suits:
        raise ValueError(f"the kernels do not take x {x.shape} with w {w.shape}")
    if suits and (interpret or jax.default_backend() == "tpu"):
        return _short_conv_kernels(x, w, unit_scale, interpret)
    return short_conv_plain(x, w, unit_scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _short_conv_kernels(x, w, unit_scale, interpret):
    from jumbo_mae_tpu_tpu.ops.pallas.short_conv import short_conv_forward

    return short_conv_forward(x, w, unit_scale, UNIT_EPS, interpret=interpret)


def _short_conv_fwd(x, w, unit_scale, interpret):
    return _short_conv_kernels(x, w, unit_scale, interpret), (x, w)


def _short_conv_bwd(unit_scale, interpret, residuals, dy):
    from jumbo_mae_tpu_tpu.ops.pallas.short_conv import short_conv_backward

    x, w = residuals
    dx, dw = short_conv_backward(x, w, dy, unit_scale, UNIT_EPS, interpret=interpret)
    return dx, dw.astype(w.dtype)


_short_conv_kernels.defvjp(_short_conv_fwd, _short_conv_bwd)


def _unit_lower_inverse(lower, base: int = 16):
    """Inverse of unit lower-triangular matrices (..., n, n), float32. Up to
    ``base`` rows by the finite Neumann product of the nilpotent part,
    ``(I + N)⁻¹ = (I − N)(I + N²)(I + N⁴) ...``; above, by halves:
    ``[[X₁, 0], [−X₂ L₂₁ X₁, X₂]]``."""
    n = lower.shape[-1]
    mm = functools.partial(jnp.matmul, precision=PRECISE)
    if n <= base or n % 2:
        eye = jnp.eye(n, dtype=lower.dtype)
        nil = lower - eye
        inv, power, reach = eye - nil, mm(nil, nil), 2
        while reach < n:
            inv = inv + mm(inv, power)
            reach *= 2
            if reach < n:
                power = mm(power, power)
        return inv
    h = n // 2
    top = _unit_lower_inverse(lower[..., :h, :h], base)
    bottom = _unit_lower_inverse(lower[..., h:, h:], base)
    corner = -mm(mm(bottom, lower[..., h:, :h]), top)
    return jnp.concatenate([
        jnp.concatenate([top, jnp.zeros_like(lower[..., :h, h:])], axis=-1),
        jnp.concatenate([corner, bottom], axis=-1)], axis=-2)


def _decayed_products(rows, k, cum, sub: int, bounded: bool = True):
    """``out[..., r, t, i] = Σ_c rows[..., r, t, c] k[..., i, c]
    e^{cum[..., t, c] − cum[..., i, c]}`` for ``i <= t`` and zero above the
    diagonal, float32: ``rows`` (..., R, C, d) stacks the row operands (q and
    k), ``k`` and the cumulative log-decays ``cum`` are (..., C, d). Built
    one strip of ``sub`` columns at a time so that no exponent is positive
    beyond ``sub − 1`` steps of decay (module docstring) — and, where the
    decays are not ``bounded``, none at all: the strip's diagonal sub-block
    pair by pair; a strip computes nothing for the rows above it."""
    c = k.shape[-2]
    within = jnp.tril(jnp.ones((sub, sub), bool))
    strips = []
    for lo in range(0, c, sub):
        hi = lo + sub
        cum_j, k_j = cum[..., lo:hi, :], k[..., lo:hi, :]
        first, last = cum_j[..., :1, :], cum_j[..., -1:, :]
        if bounded:  # the sub-block against itself: the reference is its first position
            diag = jnp.einsum("...rtc,...ic->...rti",
                              rows[..., lo:hi, :] * jnp.exp(cum_j - first)[..., None, :, :],
                              k_j * jnp.exp(first - cum_j), precision=PRECISE)
        else:  # pair by pair; a pair above the diagonal (masked below) is held to e^0
            ratio = jnp.exp(jnp.minimum(cum_j[..., :, None, :] - cum_j[..., None, :, :], 0.0))
            diag = jnp.einsum("...rtc,...tic,...ic->...rti", rows[..., lo:hi, :], ratio, k_j,
                              precision=PRECISE)
        parts = [jnp.zeros((*diag.shape[:-2], lo, sub), diag.dtype), jnp.where(within, diag, 0.0)]
        if hi < c:  # the rows after it: the reference is its last position
            parts.append(jnp.einsum(
                "...rtc,...ic->...rti",
                rows[..., hi:, :] * jnp.exp(cum[..., hi:, :] - last)[..., None, :, :],
                k_j * jnp.exp(last - cum_j), precision=PRECISE))
        strips.append(jnp.concatenate(parts, axis=-2))
    return jnp.concatenate(strips, axis=-1)


def _chunk(state, xs, *, sub: int, dtype, bounded: bool = True):
    """One chunk: ``state`` (..., d_k, d_v) float32 at its start; ``xs`` = q,
    k (..., C, d_k), v (..., C, d_v), g (..., C, d_k) float32, beta (..., C)
    float32 -> (state at its end, o (..., C, d_v))."""
    q, k, v, g, beta = xs
    f32 = jnp.float32
    cum = jnp.cumsum(g, axis=-2)
    qf, kf = q.astype(f32), k.astype(f32)
    both = _decayed_products(jnp.stack([qf, kf], axis=-3), kf, cum, sub, bounded)
    c = k.shape[-2]
    qk = both[..., 0, :, :]
    kk = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), both[..., 1, :, :], 0.0)
    unit = jnp.eye(c, dtype=f32) + beta[..., :, None] * kk
    ut = _unit_lower_inverse(unit) * beta[..., None, :]  # T = (I + A)⁻¹ Diag(β)
    decay = jnp.exp(cum)
    mm = functools.partial(jnp.matmul, preferred_element_type=f32)
    lo = lambda x: x.astype(dtype)
    s = lo(state)
    u = mm(lo(ut), v) - mm(lo(mm(lo(ut), lo(kf * decay))), s)  # U = U_0 − W S_0
    o = mm(lo(qf * decay), s) + mm(lo(qk), lo(u))
    k_out = lo(kf * jnp.exp(cum[..., -1:, :] - cum))
    state = decay[..., -1, :, None] * state + mm(jnp.swapaxes(k_out, -1, -2), lo(u))
    return state, o.astype(dtype)


def _scan(q, k, v, g, beta, chunk: int, sub: int, bounded: bool = True):
    """``kda_chunked`` as a ``lax.scan`` over the chunks, for JAX to
    differentiate: a step is rematerialised in the backward pass."""
    def split(x):  # (batch, heads, seq, ...) -> (chunks, batch, heads, chunk, ...)
        return jnp.moveaxis(x.reshape(*x.shape[:2], -1, chunk, *x.shape[3:]), 2, 0)

    body = jax.checkpoint(functools.partial(_chunk, sub=sub, dtype=v.dtype, bounded=bounded))
    start = jnp.zeros((*k.shape[:2], k.shape[-1], v.shape[-1]), jnp.float32)
    state, o = jax.lax.scan(body, start, tuple(split(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 2).reshape(v.shape), state


# the largest exponent the reference form of a diagonal sub-block may reach
# (``−floor · sub``): e^80 is inside float32 with room for a row's 128 terms
MAX_SUB_BLOCK_DECAY = 80.0


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64, sub: int | None = None,
                floor: float | None = None, interpret: bool = False):
    """The gated delta rule over whole sequences from a zero state.

    ``q``, ``k`` (batch, heads, seq, d_k) — ``k`` of unit norm, ``q`` already
    scaled; ``v`` (batch, heads, seq, d_v); ``g`` (batch, heads, seq, d_k)
    float32 log-decays, ``<= 0``; ``beta`` (batch, heads, seq) float32 in
    (0, 2): ``I − β k kᵀ`` has the eigenvalue ``1 − β`` along ``k``, negative
    past 1. Returns ``(o, state)``: ``o`` (batch, heads, seq, d_v) in ``v``'s
    dtype and the float32 state after the last position (batch, heads, d_k,
    d_v). A sequence that is no multiple of ``chunk`` is padded with
    positions that leave the state as it is. ``sub`` is the sub-block of the
    decay ratios: 16 positions, or the whole of a chunk that is no multiple
    of 16. ``floor`` is a bound the caller knows under every ``g`` (a safe
    gate's ``lower_bound``), static: with ``−floor · sub <= 80`` a diagonal
    sub-block's ratios are two factors about a reference position
    (``16 · |g| <= 80`` stays inside float32 for ``g >= −5``); with no floor
    (None), or a deeper one, they are built pair by pair with every exponent
    ``<= 0`` (module docstring). The
    schedule follows the backend and the shapes (module docstring);
    ``interpret`` runs the kernels in the Pallas interpreter (tests)."""
    from jumbo_mae_tpu_tpu.ops.pallas.kda import kda_kernels, suits

    seq = k.shape[2]
    if sub is None:
        sub = 16 if chunk % 16 == 0 else chunk
    if chunk % sub:
        raise ValueError(f"chunk {chunk} is no multiple of the sub-block {sub}")
    on_kernels = interpret or jax.default_backend() == "tpu"
    if not suits(k.shape[-1], v.shape[-1], chunk, sub):
        if interpret:
            raise ValueError(f"the kernels do not take d_k {k.shape[-1]}, d_v {v.shape[-1]}, "
                             f"chunk {chunk}, sub-block {sub}")
        on_kernels = False
    pad = -seq % chunk
    if pad:  # k = 0, beta = 0, g = 0: the state passes through
        widen = lambda x: jnp.pad(x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3))
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    bounded = floor is not None and -floor * sub <= MAX_SUB_BLOCK_DECAY
    if on_kernels:
        o, state = kda_kernels(q, k, v, g, beta, chunk, sub, interpret, bounded)
    else:
        o, state = _scan(q, k, v, g, beta, chunk, sub, bounded)
    return o[:, :, :seq], state
