"""The causal flash kernels (query/key and value widths that differ, the
shared key part not replicated per head) in interpret mode against the
einsum form, forward and backward; and under rematerialisation, where a
block keeps the forward kernel's output and log-sum-exp."""

from collections import Counter
from types import SimpleNamespace

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jumbo_mae_tpu_tpu.models.config import maybe_remat
from jumbo_mae_tpu_tpu.ops.attention import xla_causal_attention
from jumbo_mae_tpu_tpu.ops.pallas.attention import (
    CAUSAL_LSE_NAME,
    CAUSAL_OUT_NAME,
    _backward_walk,
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


@pytest.mark.parametrize("backward", [False, True])
def test_lower_triangle_visits_each_pair_once(backward):
    """The forward kernel's tables, and the backward kernel's where one span
    holds every key block and a group has one member: the same walk."""
    qi, kj, *more = _backward_walk(5, reach=None, group=1, span=5) if backward else \
        _lower_triangle(5)
    pairs = list(zip(qi.tolist(), kj.tolist()))
    assert sorted(pairs) == [(i, j) for i in range(5) for j in range(i + 1)]
    assert list(qi) == sorted(qi)  # one visit of each output block
    if backward:
        assert set(more[0].tolist()) == {0}


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


# ------------------------------------------------ under rematerialisation


class _Block(nn.Module):
    """Three projections, the causal kernels, an output projection and a
    residual: what a rematted block of ``models/lm.py`` has around its core."""

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        b, s, _ = x.shape
        heads = lambda t, d: t.reshape(b, s, 2, d).transpose(0, 2, 1, 3)
        q, k, v = (nn.Dense(48, use_bias=False, name=n)(x) for n in "qkv")
        o = pallas_causal_attention(
            heads(q[..., :32], 16), heads(q[..., 32:], 8), heads(k[..., :32], 16),
            k[..., 32:40], heads(v[..., :24], 12), 16, True)
        return x + nn.Dense(48, use_bias=False, name="out")(
            o.transpose(0, 2, 1, 3).reshape(b, s, 24))


def _two_blocks(block_cls):
    x = jax.random.normal(jax.random.key(0), (2, 32, 48))
    blocks = [block_cls(name=None) for _ in range(2)]
    params = [blk.init(jax.random.key(i + 1), x, True)["params"] for i, blk in enumerate(blocks)]

    def loss(params, x):
        for blk, p in zip(blocks, params):
            x = blk.apply({"params": p}, x, True)
        return (x ** 2).sum()

    return loss, params, x


def _kernel_calls(jaxpr, counts=None):
    """``{kernel name: calls}`` over a jaxpr and every jaxpr nested in it."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, counts)
    return counts


def _kept(capsys, loss, *args):
    """``print_saved_residuals``'s lines for what a backward pass keeps that
    is neither an argument nor a constant."""
    jax.ad_checkpoint.print_saved_residuals(loss, *args)
    return [ln for ln in capsys.readouterr().out.splitlines()
            if "from the argument" not in ln and "from a constant" not in ln]


@pytest.mark.parametrize("policy", ["none", "dots"])
def test_a_rematted_block_keeps_the_kernels_output_and_runs_the_forward_once(policy, capsys):
    """Under every remat policy the block keeps the forward kernel's output
    and log-sum-exp, so the gradient holds one forward kernel a block (two
    kernels a block, not three) and equals the un-rematted gradient to the
    bit. A remat that keeps nothing runs the forward kernel twice."""
    cfg = SimpleNamespace(grad_ckpt=True, remat_policy=policy)
    loss, params, x = _two_blocks(maybe_remat(_Block, cfg))
    grad = jax.grad(loss, argnums=(0, 1))
    assert _kernel_calls(jax.make_jaxpr(grad)(params, x).jaxpr) == {
        "causal_attention_fwd": 2, "causal_attention_bwd": 2}
    # the same remat without the two names: jax's own policy object
    unnamed = {"none": None, "dots": jax.checkpoint_policies.dots_saveable}[policy]
    base = _two_blocks(nn.remat(_Block, static_argnums=(2,), policy=unnamed))[0]
    assert _kernel_calls(jax.make_jaxpr(jax.grad(base))(params, x).jaxpr)[
        "causal_attention_fwd"] == 4

    # what is kept beyond that remat's, by shape (how JAX words a residual's
    # origin varies with its caches), is the kernel's two a block
    ours, theirs = _kept(capsys, loss, params, x), _kept(capsys, base, params, x)
    shapes = lambda lines: Counter(ln.split(" ")[0] for ln in lines)
    assert shapes(ours) - shapes(theirs) == {"f32[2,2,32,12]": 2, "f32[2,2,32]": 2}
    assert not shapes(theirs) - shapes(ours)
    assert sum(f"named '{CAUSAL_LSE_NAME}'" in ln for ln in ours) == 2
    assert not any(" named '" in ln for ln in theirs)

    plain = jax.grad(_two_blocks(_Block)[0], argnums=(0, 1))(params, x)
    for got, want in zip(jax.tree.leaves(grad(params, x)), jax.tree.leaves(plain), strict=True):
        np.testing.assert_array_equal(got, want)


def test_the_named_residuals_are_the_kernels_own():
    """Outside a remat a name is an identity: the forward rule's output is
    the primal's, and ``lse`` is stored compact, one float32 a (head, token)."""
    from jumbo_mae_tpu_tpu.ops.pallas.attention import _causal_vjp_fwd

    *qkv, _ = _inputs(5, 1, 2, 40, 16, 8, 12)
    out, residuals = _causal_vjp_fwd(*qkv, 16, True)
    np.testing.assert_array_equal(out, pallas_causal_attention(*qkv, 16, True))
    assert residuals[5] is out and residuals[6].shape == (1, 2, 48)
    jaxpr = str(jax.make_jaxpr(lambda *xs: _causal_vjp_fwd(*xs, 16, True))(*qkv))
    assert f"name={CAUSAL_OUT_NAME}" in jaxpr and f"name={CAUSAL_LSE_NAME}" in jaxpr
