"""The causal flash kernels' generalisations — key/value heads that a group
of query heads shares, a window walked as a band of block pairs, a score of
one part — in interpret mode against the einsum form, forward and backward;
and the static count of what the band's tables visit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jumbo_mae_tpu_tpu.ops.flash_attention import causal_attention, xla_causal_attention
from jumbo_mae_tpu_tpu.ops.pallas.attention import (
    CAUSAL_BLOCK,
    _lower_triangle,
    _reach,
    causal_block,
    causal_pairs,
    pallas_causal_attention,
)


def _inputs(seed, b, h, g, s, two_part, d_a=16, d_b=8, d_v=12):
    ks = jax.random.split(jax.random.key(seed), 6)
    n = lambda k, shape: jax.random.normal(k, shape, jnp.float32)
    scale = (d_a + d_b * two_part) ** -0.5
    q_b = n(ks[1], (b, h, s, d_b)) * scale if two_part else None
    k_b = n(ks[3], (b, s, d_b)) if two_part else None
    return (n(ks[0], (b, h, s, d_a)) * scale, q_b, n(ks[2], (b, g, s, d_a)), k_b,
            n(ks[4], (b, g, s, d_v))), n(ks[5], (b, h, s, d_v))


def _dense(q_a, q_b, k_a, k_b, v, window):
    """Every (query, key) pair, one query head at a time, the two masks as
    comparisons of positions: nothing of the code under test."""
    group = q_a.shape[1] // k_a.shape[1]
    at = jnp.arange(q_a.shape[2])
    visible = at[None, :] <= at[:, None]
    if window is not None:
        visible &= at[:, None] - at[None, :] < window
    heads = []
    for h in range(q_a.shape[1]):
        s = jnp.einsum("bqd,bkd->bqk", q_a[:, h], k_a[:, h // group])
        if q_b is not None:
            s = s + jnp.einsum("bqd,bkd->bqk", q_b[:, h], k_b)
        probs = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("bqk,bkd->bqd", probs, v[:, h // group]))
    return jnp.stack(heads, axis=1)


# (query heads, key/value heads, seq, block, window): groups of 1, 3 and 4; a
# window that is no multiple of the block, one smaller than the block (and
# than a block that is clamped to the sequence), one of whole blocks, one the
# sequence never reaches, one of a single token; sequences that are no
# multiple of the block (40 and 50 at 16)
CASES = [(3, 3, 40, 16, None), (6, 2, 40, 16, 13), (8, 2, 24, 32, 5), (6, 2, 40, 16, 64),
         (3, 3, 40, 16, 1), (8, 2, 48, 16, 32), (6, 2, 50, 16, 33)]

# the score of two parts (latent attention's) under each generalisation once:
# a window inside a block, inside a clamped block, of whole blocks (with none of
# them it is ``tests/test_causal_attention.py``'s)
TWO_PART = [(6, 2, 40, 16, 13), (8, 2, 24, 32, 5), (8, 2, 48, 16, 32)]


@pytest.mark.parametrize("h,g,seq,block,window,two_part",
                         [(*c, False) for c in CASES] + [(*c, True) for c in TWO_PART])
def test_grouped_windowed_kernels_match_every_pair_forward_and_backward(h, g, seq, block, window,
                                                                         two_part):
    args, w = _inputs(seq + h, 2, h, g, seq, two_part)
    given = tuple(i for i, a in enumerate(args) if a is not None)
    kernel = lambda *xs: pallas_causal_attention(*xs, block, True, window)
    out, want = kernel(*args), jax.jit(lambda *xs: _dense(*xs, window))(*args)
    assert out.shape == want.shape == (2, h, seq, 12)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    # the CPU's einsum form takes the same three generalisations
    np.testing.assert_allclose(xla_causal_attention(*args, window), want, rtol=2e-5, atol=2e-6)
    # jitted: op by op, the head loop of ``_dense`` compiles a program an operation
    grad = lambda fn: jax.jit(jax.grad(lambda *xs: (fn(*xs) * w).sum(), argnums=given))(*args)
    got = grad(kernel)
    ref = grad(lambda *xs: _dense(*xs, window))
    ein = grad(lambda *xs: xla_causal_attention(*xs, window))
    for a, e, r in zip(got, ein, ref, strict=True):
        assert a.shape == e.shape == r.shape
        np.testing.assert_allclose(a, r, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(e, r, rtol=2e-4, atol=2e-5)


def test_a_windowed_kernel_sees_neither_the_future_nor_beyond_its_window():
    (q, _, k, _, v), _ = _inputs(4, 1, 4, 2, 48, False)
    run = lambda k, v: pallas_causal_attention(q, None, k, None, v, 16, True, 20)
    base = run(k, v)
    # keys 0..9: queries from 29 on no longer see them, queries before do
    moved = run(k.at[:, :, :10].add(5.0), v.at[:, :, :10].add(5.0))
    np.testing.assert_array_equal(base[:, :, 29:], moved[:, :, 29:])
    assert not np.allclose(base[:, :, :29], moved[:, :, :29])
    later = run(k.at[:, :, 30:].add(5.0), v.at[:, :, 30:].add(5.0))
    np.testing.assert_array_equal(base[:, :, :30], later[:, :, :30])


@pytest.mark.parametrize("by_key", [False, True])
@pytest.mark.parametrize("n,block,window", [(8, 16, 16), (8, 16, 17), (8, 16, 40), (5, 32, 5),
                                            (6, 8, 1), (4, 16, 1000)])
def test_the_band_holds_each_pair_with_a_visible_entry_once(n, block, window, by_key):
    reach = _reach(window, block)
    qi, kj = _lower_triangle(n, by_key=by_key, reach=reach)
    pairs = list(zip(qi.tolist(), kj.tolist()))
    # block pair (i, j) holds a visible entry iff some row r of i and column
    # c of j have 0 <= r - c < window
    seen = [(i, j) for i in range(n) for j in range(n)
            if any(0 <= r - c < window for r in range(i * block, (i + 1) * block)
                   for c in (j * block, (j + 1) * block - 1))
            or (i == j)]
    assert sorted(pairs) == sorted(seen) and len(set(pairs)) == len(pairs)
    outer = kj if by_key else qi
    assert list(outer) == sorted(outer)  # one visit of each output block
    inner = qi if by_key else kj
    assert all(inner[t] < inner[t + 1] for t in range(len(pairs) - 1) if outer[t] == outer[t + 1])


def test_the_pairs_the_tables_visit_against_those_the_mask_keeps():
    """The cell's shapes: a full layer's triangle of 1024-blocks, and a
    512-token window as a band of blocks of 1024, 512 and 256 against the
    whole triangle (ISSUE 33's readings 9.3, 3.9, 2.0, 1.5, 8.3)."""
    needed = 512 * 513 // 2 + (8192 - 512) * 512
    assert causal_pairs(8192) == (36 * 1024 * 1024, 8192 * 8193 // 2)
    for block, pairs, ratio in [(1024, 15, 3.87), (512, 31, 2.0), (256, 93, 1.5)]:
        visited, kept = causal_pairs(8192, 512, block)
        assert (visited, kept) == (pairs * block * block, needed)
        assert visited / kept == pytest.approx(ratio, rel=5e-3)
    assert causal_pairs(8192)[0] / needed == pytest.approx(9.29, rel=1e-3)
    assert 8192 * 8193 // 2 / needed == pytest.approx(8.26, rel=1e-3)
    # the block follows from what the code can observe; a window the sequence
    # never reaches is no window
    assert causal_block(8192, None) == causal_block(400, 512) == CAUSAL_BLOCK
    assert causal_pairs(8192, 512) == causal_pairs(8192, 512, causal_block(8192, 512))
    assert causal_pairs(400, 512) == causal_pairs(400)
    brute = sum(min(i + 1, 37) for i in range(300))
    assert causal_pairs(300, 37, 16)[1] == brute


def test_the_dispatcher_hands_the_window_and_the_groups_to_either_form(monkeypatch):
    (q, _, k, _, v), _ = _inputs(6, 1, 4, 2, 24, False)
    want = _dense(q, None, k, None, v, 7)
    np.testing.assert_allclose(causal_attention(q, None, k, None, v, impl="einsum", window=7),
                               want, rtol=2e-5, atol=2e-6)
    from jumbo_mae_tpu_tpu.ops.pallas import attention as pallas_attention

    real = pallas_attention.pallas_causal_attention
    monkeypatch.setattr(pallas_attention, "pallas_causal_attention",
                        lambda *xs, window=None: real(*xs, 8, True, window))
    np.testing.assert_allclose(causal_attention(q, None, k, None, v, impl="flash", window=7),
                               want, rtol=2e-5, atol=2e-6)
