"""The causal flash kernels (query/key and value widths that differ, the
shared key part not replicated per head) in interpret mode against the
einsum form, forward and backward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jumbo_mae_tpu_tpu.ops.flash_attention import xla_causal_attention
from jumbo_mae_tpu_tpu.ops.pallas.attention import (
    _lower_triangle,
    pallas_causal_attention,
)


def _inputs(seed, b, h, s, d_a, d_b, d_v, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 6)
    n = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)
    scale = (d_a + d_b) ** -0.5
    return (n(ks[0], (b, h, s, d_a)) * scale, n(ks[1], (b, h, s, d_b)) * scale,
            n(ks[2], (b, h, s, d_a)), n(ks[3], (b, s, d_b)), n(ks[4], (b, h, s, d_v)),
            n(ks[5], (b, h, s, d_v)))


@pytest.mark.parametrize("by_key", [False, True])
def test_lower_triangle_visits_each_pair_once(by_key):
    qi, kj = _lower_triangle(5, by_key=by_key)
    pairs = list(zip(qi.tolist(), kj.tolist()))
    assert sorted(pairs) == [(i, j) for i in range(5) for j in range(i + 1)]
    outer = kj if by_key else qi
    assert list(outer) == sorted(outer)  # one visit of each output block


# seq 40 at block 16 pads to 48: a sequence that is no multiple of the block
@pytest.mark.parametrize("seq,block", [(40, 16), (32, 16), (24, 32)])
def test_causal_kernel_matches_einsum_forward_and_backward(seq, block):
    *qkv, w = _inputs(3, 2, 3, seq, 16, 8, 12)

    def loss(fn, *xs):
        return (fn(*xs) * w).sum()

    kernel = lambda *xs: pallas_causal_attention(*xs, block, True)
    out = kernel(*qkv)
    want = xla_causal_attention(*qkv)
    assert out.shape == want.shape == (2, 3, seq, 12)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    got = jax.grad(lambda *xs: loss(kernel, *xs), argnums=range(5))(*qkv)
    ref = jax.grad(lambda *xs: loss(xla_causal_attention, *xs), argnums=range(5))(*qkv)
    for g, r in zip(got, ref):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5)


def test_causal_kernel_sees_no_future_token():
    *qkv, _ = _inputs(4, 1, 2, 32, 16, 8, 16)
    base = pallas_causal_attention(*qkv, 16, True)
    q_a, q_b, k_a, k_b, v = qkv
    moved = pallas_causal_attention(q_a, q_b, k_a.at[:, :, 20:].add(5.0),
                                    k_b.at[:, 20:].add(5.0), v.at[:, :, 20:].add(5.0), 16, True)
    np.testing.assert_array_equal(base[:, :, :20], moved[:, :, :20])
    assert not np.allclose(base[:, :, 20:], moved[:, :, 20:])
