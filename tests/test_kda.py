"""The chunked gated delta rule (``ops/kda.py``) against the recurrence it
stands for, position by position, in float32: output, final state and every
input's gradient, with the log-decays pinned at the safe gate's bound for
whole chunks, near zero, and mixed, on both schedules of it (the scan, and
the Pallas kernel in the interpreter); with no floor under them and ``beta`` up to 2 (one step of
−100 in the middle of a sub-block); the causal convolution against a loop;
the triangular inverse against ``numpy``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jumbo_mae_tpu_tpu.ops import kda
from jumbo_mae_tpu_tpu.ops.kda import causal_conv, kda_chunked


def literal(q, k, v, g, beta):
    """``S_t = (I − β k kᵀ) Diag(α) S_{t−1} + β k vᵀ``, ``o_t = S_tᵀ q_t``."""
    def head(q, k, v, g, beta):
        def position(state, at):
            q_t, k_t, v_t, g_t, b_t = at
            state = jnp.exp(g_t)[:, None] * state
            state = state + b_t * jnp.outer(k_t, v_t - state.T @ k_t)
            return state, state.T @ q_t

        start = jnp.zeros((k.shape[-1], v.shape[-1]))
        state, o = jax.lax.scan(position, start, (q, k, v, g, beta))
        return o, state

    return jax.vmap(jax.vmap(head))(q, k, v, g, beta)


FLOOR = -5.0  # the safe gate's bound, which these inputs keep: the reference-position form


def inputs(decays: str, seq: int = 150, d_k: int = 8, d_v: int = 6, seed: int = 0):
    keys = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (2, 2, seq, d_k))) * d_k**-0.5
    k = unit(jax.random.normal(keys[1], (2, 2, seq, d_k)))
    v = jax.random.normal(keys[2], (2, 2, seq, d_v))
    u = jax.random.uniform(keys[3], (2, 2, seq, d_k))
    g = {"at_the_bound": -5.0 + 1e-3 * u,  # every position of every chunk at the floor
         "near_zero": -1e-3 * u,
         # most channels hardly decay, a fifth nearly at the floor, and one
         # whole chunk of 64 at the floor
         "mixed": (-5.0 * jax.nn.sigmoid(8.0 * (u - 0.8))).at[:, :, 64:128].set(-4.999)}[decays]
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (2, 2, seq)))
    return q, k, v, g, beta


FIVE = (0, 1, 2, 3, 4)


def loss(o, state, weight):
    """The output against ``weight`` plus the final state's squares: every one
    of the five inputs moves it, through both results."""
    return (o * weight).sum() + jnp.square(state).sum()


def scalar(fn, weight):
    return lambda *a: loss(*fn(*a), weight)


def weight_for(args):
    return jax.random.normal(jax.random.key(9), args[2].shape)


@functools.cache
def recurrence(decays: str, **size):
    """``(output, state, the five gradients of scalar(literal))`` at
    ``inputs(decays, **size)``, as one program: what every chunking and
    schedule of one set of inputs is held to."""
    args = inputs(decays, **size)
    weight = weight_for(args)

    def both(*a):
        o, state = literal(*a)
        return loss(o, state, weight), (o, state)

    (_, (o, state)), grads = jax.jit(jax.value_and_grad(both, FIVE, has_aux=True))(*args)
    return o, state, grads


@pytest.mark.parametrize("chunk,sub", [(64, 16), (128, 16), (32, 16), (8, 8)])
@pytest.mark.parametrize("decays", ["mixed", "at_the_bound", "near_zero"])
def test_chunked_form_is_the_recurrence(decays, chunk, sub):
    """150 positions: no multiple of any chunk, so the padded tail is in."""
    args = inputs(decays)
    chunked = lambda *a: kda_chunked(*a, chunk=chunk, sub=sub, floor=FLOOR)
    o, state = chunked(*args)
    want_o, want_state, want = recurrence(decays)
    assert o.shape == want_o.shape and state.shape == want_state.shape
    np.testing.assert_allclose(o, want_o, rtol=0, atol=2e-6 * float(jnp.abs(want_o).max()))
    np.testing.assert_allclose(state, want_state, rtol=0,
                               atol=2e-6 * float(jnp.abs(want_state).max()))
    got = jax.grad(scalar(chunked, weight_for(args)), argnums=FIVE)(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert float(jnp.abs(b).max()) > 0, name
        # at the floor a log-decay's gradient is e^-5 of the others': the
        # rounding of the order-one terms it is summed from is its floor
        np.testing.assert_allclose(a, b, rtol=0, atol=5e-6 * max(float(jnp.abs(b).max()), 0.1),
                                   err_msg=name)


def test_the_state_carries_across_chunks():
    """With hardly any decay the first chunk's keys are still in the state
    after 256 positions: zeroing what a chunk hands to the next is seen."""
    args = inputs("near_zero", seq=256)
    o, state = kda_chunked(*args, chunk=64)
    first = tuple(x[:, :, :64] for x in args)
    _, early = kda_chunked(*first, chunk=64)
    assert float(jnp.abs(early).max()) > 0.1
    alone = tuple(x[:, :, 128:] for x in args)
    o_alone, _ = kda_chunked(*alone, chunk=64)
    assert float(jnp.abs(o[:, :, 128:] - o_alone).max()) > 0.05 * float(jnp.abs(o).max())


def test_compute_dtype_operands_keep_a_float32_state():
    q, k, v, g, beta = inputs("mixed")
    low = lambda x: x.astype(jnp.bfloat16)
    o, state = kda_chunked(low(q), low(k), low(v), g, beta, floor=FLOOR)
    want = recurrence("mixed")[0]
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    gap = jnp.linalg.norm(o.astype(jnp.float32) - want) / jnp.linalg.norm(want)
    assert float(gap) < 2e-2


# ---------------------------------------------------------- the kernel path
# ``ops/pallas/kda.py`` in the Pallas interpreter at the narrowest widths it
# takes. Its float32 products are three real bfloat16 passes here (XLA's HIGH
# is plain float32 on the CPU), so it is held to 16 bits, not to 22.

WIDE = dict(seq=150, d_k=128, d_v=128)  # three chunks of 64, the last padded


def kernel_path(*a):
    return kda_chunked(*a, chunk=64, floor=FLOOR, interpret=True)


@pytest.mark.parametrize("decays", ["mixed", "at_the_bound", "near_zero"])
def test_kernel_path_is_the_recurrence_and_the_scan(decays):
    """Output, final state and all five gradients through the custom VJP,
    under a ``jax.checkpoint`` as the model's block runs it."""
    args = inputs(decays, **WIDE)
    weight = weight_for(args)
    scan = lambda *a: kda_chunked(*a, chunk=64, floor=FLOOR)
    *by_recurrence, recurrence_grads = recurrence(decays, **WIDE)
    o, state = kernel_path(*args)
    for want_o, want_state in (by_recurrence, scan(*args)):
        np.testing.assert_allclose(o, want_o, rtol=0, atol=3e-5 * float(jnp.abs(want_o).max()))
        np.testing.assert_allclose(state, want_state, rtol=0,
                                   atol=3e-5 * float(jnp.abs(want_state).max()))
    got = jax.grad(scalar(jax.checkpoint(kernel_path), weight), argnums=FIVE)(*args)
    for want in (recurrence_grads, jax.grad(scalar(scan, weight), FIVE)(*args)):
        for name, a, b in zip("q k v g beta".split(), got, want):
            assert bool(jnp.isfinite(a).all()), name
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * max(float(jnp.abs(b).max()), 0.1),
                                       err_msg=name)


@pytest.mark.parametrize("decays", ["mixed", "at_the_bound", "near_zero"])
def test_forward_kernel_keeps_the_state_at_every_chunks_start(decays):
    """What the backward kernel rebuilds a chunk from: the state the
    recurrence has after 0, 64 and 128 positions."""
    from jumbo_mae_tpu_tpu.ops.pallas.kda import kda_forward

    args = inputs(decays, seq=192, d_k=128, d_v=128)
    o, state, starts = kda_forward(*args, chunk=64, sub=16, with_starts=True, interpret=True)
    assert starts.shape == (3, 2, 2, 128, 128) and starts.dtype == jnp.float32
    assert float(jnp.abs(starts[0]).max()) == 0.0
    for n in (1, 2):
        want = literal(*(x[:, :, :64 * n] for x in args))[1]
        np.testing.assert_allclose(starts[n], want, rtol=0, atol=3e-5 * float(jnp.abs(want).max()))
    # the plain forward pass is the same kernel without that output
    o_plain, state_plain = kda_forward(*args, chunk=64, sub=16, with_starts=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(o_plain), np.asarray(o))
    np.testing.assert_array_equal(np.asarray(state_plain), np.asarray(state))


@pytest.mark.parametrize("decays", ["mixed", "at_the_bound", "near_zero"])
def test_backward_kernel_is_the_scans_transpose(decays):
    """From its own kept states and any cotangents (the state's too), the
    kernel that transposes ``chunk_step`` in VMEM gives what JAX's transpose
    of the scan gives."""
    from jumbo_mae_tpu_tpu.ops.pallas.kda import kda_backward, kda_forward

    args = inputs(decays, seq=192, d_k=128, d_v=128)
    o, state, starts = kda_forward(*args, chunk=64, sub=16, with_starts=True, interpret=True)
    d_o = jax.random.normal(jax.random.key(3), o.shape)
    d_state = jax.random.normal(jax.random.key(4), state.shape)
    got = kda_backward(*args, starts, d_o, d_state, chunk=64, sub=16, interpret=True)
    want = jax.vjp(lambda *a: kda_chunked(*a, chunk=64, floor=FLOOR), *args)[1]((d_o, d_state))
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * max(float(jnp.abs(b).max()), 0.1),
                                   err_msg=name)


@pytest.mark.parametrize("decays", ["mixed", "at_the_bound", "near_zero"])
def test_kernel_path_takes_compute_dtype_operands_and_keeps_a_float32_state(decays):
    q, k, v, g, beta = inputs(decays, **WIDE)
    low = lambda x: x.astype(jnp.bfloat16)
    o, state = kernel_path(low(q), low(k), low(v), g, beta)
    scan_o, scan_state = kda_chunked(low(q), low(k), low(v), g, beta, floor=FLOOR)
    want, want_state, _ = recurrence(decays, **WIDE)
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    gap = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b))
    assert gap(o, want) < 2e-2 and gap(state, want_state) < 2e-2
    # no further from the recurrence than the scan is, and beside it
    assert gap(o, want) < 1.1 * gap(scan_o, want) + 1e-4
    assert gap(o, scan_o.astype(jnp.float32)) < 5e-3 and gap(state, scan_state) < 5e-3
    # gradients in the operands' dtypes, beside the scan's
    weight = jax.random.normal(jax.random.key(9), v.shape)
    loss = lambda fn: lambda *a: (fn(*a)[0].astype(jnp.float32) * weight).sum()
    operands = (low(q), low(k), low(v), g, beta)
    got = jax.grad(loss(kernel_path), (0, 1, 2, 3, 4))(*operands)
    want = jax.grad(loss(lambda *a: kda_chunked(*a, floor=FLOOR)), (0, 1, 2, 3, 4))(*operands)
    for name, a, b, x in zip("q k v g beta".split(), got, want, operands):
        assert a.dtype == b.dtype == x.dtype, name
        assert gap(a, b.astype(jnp.float32)) < 2e-2, name


# ------------------------------------------------- decays with no floor
# A softplus gate bounds nothing: one step may decay by e^-100 in the middle
# of a sub-block, where the reference-position form raises e to +-8 steps of
# it. With no floor stated every diagonal pair is built pair by pair; beta
# runs to 2 (negative eigenvalues).

def unbounded_inputs(d: int):
    q, k, v, _, beta = inputs("near_zero", seq=100, d_k=d, d_v=d)
    g = -0.3 * jax.random.uniform(jax.random.key(5), k.shape)
    g = g.at[:, :, 71].set(-100.0)  # position 7 of the sub-block 64 .. 80, every channel
    g = g.at[:, 0, 7, : d // 2].set(-100.0)  # and in a first chunk, half the channels
    return q, k, v, g, 2.0 * beta


@pytest.mark.parametrize("schedule", ["scan", "kernels"])
def test_a_step_of_minus_100_inside_a_sub_block_is_finite_and_the_recurrence(schedule):
    """Output, state and the five gradients (the kernel pair through its VJP,
    under a ``jax.checkpoint``), with beta up to 2; the form that rests on a
    floor, given the same inputs, is not finite: the fault this form is for."""
    args = unbounded_inputs(8 if schedule == "scan" else 128)
    assert float(args[4].max()) > 1.9
    weight = weight_for(args)
    how = dict(chunk=64, interpret=schedule == "kernels")
    free = jax.checkpoint(lambda *a: kda_chunked(*a, **how))  # floor=None: none is known
    o, state = free(*args)
    want_o, want_state = literal(*args)
    tol = 3e-5 if schedule == "kernels" else 5e-6
    np.testing.assert_allclose(o, want_o, rtol=0, atol=tol * float(jnp.abs(want_o).max()))
    np.testing.assert_allclose(state, want_state, rtol=0,
                               atol=tol * float(jnp.abs(want_state).max()))
    for name, a, b in zip("q k v g beta".split(), jax.grad(scalar(free, weight), FIVE)(*args),
                          jax.grad(scalar(literal, weight), FIVE)(*args)):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(a, b, rtol=0, atol=4 * tol * max(float(jnp.abs(b).max()), 0.1),
                                   err_msg=name)
    bounded, _ = kda_chunked(*args, floor=FLOOR, **how)
    assert not bool(jnp.isfinite(bounded).all())


def test_a_floor_too_deep_for_a_sub_block_takes_the_pairwise_form_too():
    """``−floor · sub <= 80`` decides, from what the caller states: −5 at
    sub-blocks of 16 keeps the reference form, −6 does not, and no floor
    never does. At inputs both forms take they agree."""
    seen = []
    real = kda._scan
    args = inputs("mixed")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kda, "_scan", lambda *a: seen.append(a[-1]) or real(*a))
        outs = [kda_chunked(*args, floor=floor)[0] for floor in (-5.0, -6.0, None)]
    assert seen == [True, False, False]
    np.testing.assert_allclose(outs[1], outs[0], rtol=0, atol=2e-6 * float(jnp.abs(outs[0]).max()))
    np.testing.assert_array_equal(np.asarray(outs[1]), np.asarray(outs[2]))


def test_the_kernel_refuses_widths_it_does_not_take_and_the_scan_takes_them():
    args = inputs("mixed")  # d_k 8, d_v 6
    with pytest.raises(ValueError, match="kernels do not take"):
        kda_chunked(*args, floor=FLOOR, interpret=True)
    from jumbo_mae_tpu_tpu.ops.pallas.kda import heads_per_step, suits

    assert suits(128, 128, 64, 16) and suits(256, 128, 32, 16)
    assert not suits(64, 128, 64, 16) and not suits(128, 128, 8, 8)
    assert heads_per_step(32, "fwd") == 16 and heads_per_step(6, "bwd") == 6
    assert heads_per_step(17, "fwd") == 1 and heads_per_step(24, "fwd") == 12


def test_a_chunk_that_is_no_multiple_of_the_sub_block_is_refused():
    with pytest.raises(ValueError, match="sub-block"):
        kda_chunked(*inputs("mixed"), chunk=24, sub=16)


@pytest.mark.parametrize("n", [4, 16, 24, 64])
def test_unit_lower_inverse(n):
    lower = np.tril(np.random.default_rng(n).normal(size=(3, n, n)), -1) + np.eye(n)
    got = kda._unit_lower_inverse(jnp.asarray(lower, jnp.float32))
    want = np.linalg.inv(lower)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * np.abs(want).max())


def test_causal_convolution_against_a_loop():
    x = jax.random.normal(jax.random.key(0), (2, 3, 11, 5))
    w = jax.random.normal(jax.random.key(1), (4, 3, 5))
    got = causal_conv(x, w)
    want = np.zeros(x.shape, np.float32)
    for t in range(11):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, :, t] += np.asarray(w[j]) * np.asarray(x[:, :, t - 3 + j])
    want = want / (1.0 + np.exp(-want))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # its hand-written gradient is the plain form's
    plain = lambda x, w: jax.nn.silu(sum(
        jnp.pad(x, ((0, 0), (0, 0), (3 - j, 0), (0, 0)))[:, :, :11] * w[j][:, None, :]
        for j in range(4)))
    weight = jax.random.normal(jax.random.key(2), x.shape)
    for got_g, want_g in zip(jax.grad(lambda *a: (causal_conv(*a) * weight).sum(), (0, 1))(x, w),
                             jax.grad(lambda *a: (plain(*a) * weight).sum(), (0, 1))(x, w)):
        np.testing.assert_allclose(got_g, want_g, rtol=1e-5, atol=1e-5)
    # nothing of a later position reaches an earlier one
    moved = causal_conv(x.at[:, :, 6:].add(1.0), w)
    np.testing.assert_array_equal(np.asarray(moved[:, :, :6]), np.asarray(got[:, :, :6]))
