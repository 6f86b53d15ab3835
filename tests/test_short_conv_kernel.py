"""The linear-attention layers' short-convolution kernels
(``ops/pallas/short_conv.py``) in the Pallas interpreter against the plain
composition they stand for — ``causal_conv(·, w, "silu")``, then the L2 norm
times its scale — in output, ``dx`` and ``dw``; and ``ops/kda.short_conv``'s
rule for which of the two a call runs."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jumbo_mae_tpu_tpu.ops import kda
from jumbo_mae_tpu_tpu.ops.pallas import short_conv as kernels


plain = kda.short_conv_plain  # causal_conv(·, w, "silu"), then the norm times its scale


def operands(shape, dtype, taps=4, seed=0):
    kx, kw, kd = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(kx, shape, jnp.float32).astype(dtype)
    w = jax.random.uniform(kw, (taps, shape[1], shape[3]), jnp.float32, -0.5, 0.5)
    return x, w, jax.random.normal(kd, shape, jnp.float32).astype(dtype)


def both(x, w, dy, scale):
    """(y, dx, dw) of the kernels and of the plain form, float32 arrays."""
    f32 = lambda tree: [np.asarray(a, np.float32) for a in tree]
    got, pull = jax.vjp(lambda x, w: kda.short_conv(x, w, scale, interpret=True), x, w)
    want, pull_plain = jax.vjp(lambda x, w: plain(x, w, scale), x, w)
    assert got.dtype == want.dtype == x.dtype
    return f32((got, *pull(dy))), f32((want, *pull_plain(dy)))


def assert_close(got, want, dtype):
    """The kernels' sigmoid takes the chip's approximate reciprocal and one
    Newton step, which the interpreter plays at bfloat16's precision: 2e-5 of
    a value, so a float32 result agrees to 1e-4; a bfloat16 one to a rounding
    step (2^-8 of the value) where the last bit falls the other way. Beside a
    value near zero (a cancelled sum of taps; ``dw``, a sum over every
    position) the measure is the array's largest."""
    rtol = 1e-4 if dtype == jnp.float32 else 2.0**-7
    for name, g, w in zip(("y", "dx", "dw"), got, want):
        atol = rtol * np.abs(w).max() * (1.0 if name == "dw" else 0.25)
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol, err_msg=name)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 32 positions and one head: a sequence of 96 is three blocks,
    two heads two head blocks a batch entry."""
    monkeypatch.setattr(kernels, "SEQ_BLOCK", 32)
    monkeypatch.setattr(kernels, "BLOCK_ELEMENTS", 32 * 128)


@pytest.mark.parametrize("scale", [None, 1.0, 128**-0.5], ids=["v", "k", "q"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_kernels_against_the_plain_form_across_block_edges(small_blocks, dtype, scale):
    """Three sequence blocks and two head blocks a batch entry: the history
    crosses a block's edge forward and ``dz`` crosses it backward."""
    shape = (2, 2, 96, 128)
    assert kernels.short_conv_blocks(*shape[1:]) == (1, 32)
    got, want = both(*operands(shape, dtype), scale)
    assert_close(got, want, dtype)


def test_a_block_walks_its_strips_and_its_heads(monkeypatch):
    """One grid step of two heads and four strips: the history and ``dz``
    cross strip edges inside a block, and a block's edge too."""
    monkeypatch.setattr(kernels, "SEQ_BLOCK", 64)
    monkeypatch.setattr(kernels, "STRIP", 16)
    shape = (1, 2, 128, 128)
    assert kernels.short_conv_blocks(*shape[1:]) == (2, 64)
    assert kernels._strip(64, 128) == 16
    got, want = both(*operands(shape, jnp.float32, seed=1), 1.0)
    assert_close(got, want, jnp.float32)


def test_nothing_comes_before_the_first_position_or_after_the_last(small_blocks):
    """Zero history before position 0 and no ``dz`` past the end: a sequence
    and the same sequence behind another give the same first block, and the
    gradient of the last rows sees nothing beyond them."""
    x, w, dy = operands((1, 1, 64, 128), jnp.float32, seed=2)
    run = lambda x: kda.short_conv(x, w, 1.0, interpret=True)
    alone = run(x[:, :, :32])
    np.testing.assert_array_equal(alone, run(x)[:, :, :32])  # causal: the future is unseen
    first_taps = plain(jnp.concatenate([jnp.zeros_like(x[:, :, :3]), x[:, :, :32]], axis=2),
                       w, 1.0)[:, :, 3:]
    np.testing.assert_allclose(alone, first_taps, rtol=1e-4, atol=1e-6)
    moved = run(x.at[:, :, 40:].add(1.0))
    np.testing.assert_array_equal(moved[:, :, :40], run(x)[:, :, :40])
    assert not np.allclose(moved[:, :, 40:44], run(x)[:, :, 40:44])
    pull = lambda dy: jax.vjp(run, x)[1](dy)[0]
    tail_only = pull(dy.at[:, :, :60].set(0.0))
    assert not np.any(np.asarray(tail_only[:, :, :57]))  # four taps reach three rows back
    assert np.any(np.asarray(tail_only[:, :, 57:60]))


def test_the_block_rule_and_the_way_out():
    """Whole 128-lane tiles and whole 16-row tiles, heads to fill a step; a
    shape the rule refuses runs the plain form, and ``interpret=True`` on it
    raises, as ``kda_chunked``'s does."""
    assert kernels.short_conv_blocks(32, 8192, 128) == (8, 512)
    assert kernels.short_conv_blocks(8, 8192, 128) == (8, 512)
    assert kernels.short_conv_blocks(4, 8192, 256) == (4, 512)
    assert kernels.short_conv_blocks(3, 48, 128) == (3, 48)
    assert kernels.short_conv_blocks(2, 96, 96) is None
    assert kernels.short_conv_blocks(2, 40, 128) is None
    assert (kernels._strip(512, 128), kernels._strip(512, 256), kernels._strip(48, 128)) == (
        128, 64, 16)
    for shape in ((1, 2, 96, 96), (1, 2, 40, 128)):
        x, w, dy = operands(shape, jnp.float32)
        got, pull = jax.vjp(lambda x, w: kda.short_conv(x, w, 1.0), x, w)
        want, pull_plain = jax.vjp(lambda x, w: plain(x, w, 1.0), x, w)
        np.testing.assert_array_equal(got, want)
        for g, p in zip(pull(dy), pull_plain(dy)):
            np.testing.assert_array_equal(g, p)
        with pytest.raises(ValueError, match="the kernels do not take"):
            kda.short_conv(x, w, 1.0, interpret=True)
    x, w, _ = operands((1, 2, 96, 128), jnp.float32, taps=10)  # more taps than the history holds
    with pytest.raises(ValueError, match="the kernels do not take"):
        kda.short_conv(x, w, None, interpret=True)


def test_off_the_chip_the_layer_runs_the_plain_form():
    """On the CPU a shape the kernels take still runs the plain composition,
    value for value, and keeps ``x`` and ``w`` alone of the filter."""
    x, w, dy = operands((1, 2, 32, 128), jnp.bfloat16)
    for scale in (None, 1.0, 128**-0.5):
        np.testing.assert_array_equal(kda.short_conv(x, w, scale), plain(x, w, scale))
    text = str(jax.make_jaxpr(lambda x, w: kda.short_conv(x, w, 1.0))(x, w))
    assert "pallas_call" not in text
