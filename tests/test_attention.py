"""Attention implementations must agree: einsum (parity oracle) vs blockwise
XLA vs the Pallas kernel (interpreter mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jumbo_mae_tpu_tpu.ops.blockwise_attention import blockwise_attention
from jumbo_mae_tpu_tpu.ops.flash_attention import xla_attention
from jumbo_mae_tpu_tpu.ops.pallas.attention import pallas_flash_attention


def qkv(b=2, s=128, h=4, d=32, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, s, h, d)
    q, k, v = (jax.random.normal(kk, shape, dtype) for kk in ks)
    return q * d**-0.5, k, v


class TestBlockwise:
    @pytest.mark.parametrize("block_k", [32, 64, 128])
    def test_matches_naive(self, block_k):
        q, k, v = qkv()
        ref = xla_attention(q, k, v)
        got = blockwise_attention(q, k, v, block_k=block_k)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)

    def test_ragged_seq_padding(self):
        q, k, v = qkv(s=100)  # not divisible by block
        ref = xla_attention(q, k, v)
        got = blockwise_attention(q, k, v, block_k=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)

    def test_gradients_match_naive(self):
        q, k, v = qkv(s=64)

        def loss_naive(q, k, v):
            return (xla_attention(q, k, v) ** 2).sum()

        def loss_block(q, k, v):
            return (blockwise_attention(q, k, v, block_k=16) ** 2).sum()

        g_ref = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
        g_got = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_got, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    def test_bias(self):
        q, k, v = qkv(s=64)
        bias = jax.random.normal(jax.random.key(7), (1, 1, 64, 64))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) + bias
        probs = jax.nn.softmax(logits, -1)
        ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        got = blockwise_attention(q, k, v, block_k=16, bias=bias)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)

    def test_bias_with_padding(self):
        # full-length key axis bias + seq_k not divisible by block_k
        q, k, v = qkv(s=100)
        bias = jax.random.normal(jax.random.key(8), (1, 1, 100, 100))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) + bias
        probs = jax.nn.softmax(logits, -1)
        ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        got = blockwise_attention(q, k, v, block_k=64, bias=bias)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


class TestPallasKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_forward_matches_naive_interpret(self, dtype):
        q, k, v = qkv(s=256, d=128, dtype=dtype)
        ref = xla_attention(q, k, v)
        got = pallas_flash_attention(q, k, v, 64, 64, True)
        atol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32), atol=atol
        )

    @pytest.mark.parametrize("s", [199, 55, 130])
    def test_forward_ragged_seq_interpret(self, s):
        """MAE shapes (decoder 196+3, encoder 49+3·…) don't divide the block:
        the kernel pads internally and masks pad keys."""
        q, k, v = qkv(s=s, d=32)
        ref = xla_attention(q, k, v)
        got = pallas_flash_attention(q, k, v, 128, 128, True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5
        )

    def test_backward_ragged_seq(self):
        q, k, v = qkv(s=199, d=32)

        def loss(q, k, v):
            return (pallas_flash_attention(q, k, v, 128, 128, True) ** 2).sum()

        def loss_ref(q, k, v):
            return (xla_attention(q, k, v) ** 2).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    def test_backward_kernel_matches_naive(self):
        q, k, v = qkv(s=128, d=128)

        def loss(q, k, v):
            return (pallas_flash_attention(q, k, v, 64, 64, True) ** 2).sum()

        def loss_ref(q, k, v):
            return (xla_attention(q, k, v) ** 2).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# every switch that once reached the kernels from outside the configuration
_HOSTILE_ENV = {
    "JUMBO_PALLAS_MM_F32": "1",
    "JUMBO_PALLAS_PAD_TO_BLOCK": "1",
    "JUMBO_PALLAS_LANE": "128",
    "JUMBO_AUTO_FLASH_MIN_SEQ": "1",
}

# forward + gradient as a jaxpr (kernel bodies, block shapes and residual
# buffers are in its text; nothing compiles), and what "auto" resolves to.
# seq 300 at block 256: 128-lane padding gives 384, padding to the block 512
_KERNEL_PROGRAM = {
    "flash": """
q = jnp.zeros((1, 300, 2, 32), jnp.bfloat16)
f = lambda q, k, v: A.pallas_flash_attention(q, k, v, 256, 256, True).astype(jnp.float32).sum()
print(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, q, q))
""",
    "causal": """
qa, qb = jnp.zeros((1, 2, 40, 16), jnp.bfloat16), jnp.zeros((1, 2, 40, 8), jnp.bfloat16)
f = lambda qa, qb, ka, kb, v: A.pallas_causal_attention(
    qa, qb, ka, kb, v, 16, True).astype(jnp.float32).sum()
print(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4)))(qa, qb, qa, qb[:, 0], qa))
""",
}


@pytest.mark.parametrize("kernel", sorted(_KERNEL_PROGRAM))
def test_kernel_program_ignores_the_environment(kernel):
    """The program is a function of the configuration and the shapes: a fresh
    interpreter under a hostile environment traces the same kernels as one
    under a clean environment."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    from jumbo_mae_tpu_tpu.utils.procenv import cpu_subprocess_env

    code = (
        "import jax, jax.numpy as jnp\n"
        "from jumbo_mae_tpu_tpu.models.layers import resolve_attn_impl\n"
        "from jumbo_mae_tpu_tpu.ops.pallas import attention as A\n"
        "print(resolve_attn_impl('auto', backend='tpu', seq_len=300, dropout=0.0,"
        " deterministic=True))\n" + _KERNEL_PROGRAM[kernel]
    )
    clean = {k: v for k, v in os.environ.items() if not k.startswith("JUMBO_")}
    texts = []
    for extra in ({}, _HOSTILE_ENV):
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env=cpu_subprocess_env(base={**clean, **extra}),
            cwd=Path(__file__).resolve().parent.parent,
            capture_output=True,
            text=True,
            timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        texts.append(proc.stdout)
    assert "pallas_call" in texts[0] and texts[0].startswith("einsum\n")
    assert texts[0] == texts[1]


def test_resolve_attn_impl_auto_policy():
    """The auto policy (round 5): flash on TPU at long sequence unless
    dropout is active in training; einsum otherwise; explicit impls pass
    through untouched."""
    from jumbo_mae_tpu_tpu.models.layers import (
        AUTO_FLASH_MIN_SEQ,
        resolve_attn_impl,
    )

    r = lambda **kw: resolve_attn_impl(
        kw.pop("impl", "auto"),
        backend=kw.pop("backend", "tpu"),
        seq_len=kw.pop("seq_len", AUTO_FLASH_MIN_SEQ),
        dropout=kw.pop("dropout", 0.0),
        deterministic=kw.pop("deterministic", False),
    )
    assert r() == "flash"                                   # long seq, tpu
    assert r(seq_len=AUTO_FLASH_MIN_SEQ - 1) == "einsum"    # short seq
    assert r(backend="cpu") == "einsum"                     # not tpu
    assert r(dropout=0.1) == "einsum"                       # train dropout
    assert r(dropout=0.1, deterministic=True) == "flash"    # eval dropout ok
    assert r(impl="einsum", seq_len=4096) == "einsum"       # explicit wins
    assert r(impl="flash", seq_len=8) == "flash"
    assert r(impl="ring", backend="cpu") == "ring"
