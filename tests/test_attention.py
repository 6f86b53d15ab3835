"""Attention implementations must agree: einsum (parity oracle) vs the Pallas
kernel (interpreter mode on CPU); and the rule that chooses between them."""

import ast
import itertools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jumbo_mae_tpu_tpu.ops.attention import AUTO_FLASH_MIN_SEQ, lowering, xla_attention
from jumbo_mae_tpu_tpu.ops.pallas.attention import pallas_flash_attention


def qkv(b=2, s=128, h=4, d=32, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, s, h, d)
    q, k, v = (jax.random.normal(kk, shape, dtype) for kk in ks)
    return q * d**-0.5, k, v


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
# buffers are in its text; nothing compiles), and what the rule answers.
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
        "from jumbo_mae_tpu_tpu.ops.attention import lowering\n"
        "from jumbo_mae_tpu_tpu.ops.pallas import attention as A\n"
        "print(lowering(backend='tpu', seq_len=300, probs_needed=False, seq_shards=1))\n"
        + _KERNEL_PROGRAM[kernel]
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


PACKAGE = Path(__file__).resolve().parent.parent / "jumbo_mae_tpu_tpu"


@pytest.mark.parametrize(
    "backend,seq_len,probs_needed,seq_shards",
    itertools.product(("tpu", "cpu"), (199, 511, 512, 787), (False, True), (1, 2)),
)
def test_the_rule_answers_from_what_a_call_can_see(backend, seq_len, probs_needed, seq_shards):
    """``ops/attention.lowering``, the one rule: the einsum form wherever the
    probabilities themselves are needed (a mask, or dropout active in the
    call); else the ring over a split sequence; else the flash kernels on the
    TPU from 512 tokens up (round 5's two measured points, 199 and 787, lie
    either side); else the einsum form. On an unsplit mesh that is what
    ``attn_impl="auto"`` resolved to before the option went (PR 43)."""
    got = lowering(backend=backend, seq_len=seq_len, probs_needed=probs_needed,
                   seq_shards=seq_shards)
    assert AUTO_FLASH_MIN_SEQ == 512
    if probs_needed:
        assert got == "einsum"
    elif seq_shards > 1:
        assert got == "ring"
    else:
        assert got == ("flash" if backend == "tpu" and seq_len >= 512 else "einsum")


def test_the_language_models_import_nothing_from_the_vits_layers():
    tree = ast.parse((PACKAGE / "models" / "lm.py").read_text())
    imported = [n.module if isinstance(n, ast.ImportFrom) else a.name
                for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names]
    assert imported and not [m for m in imported if m and m.endswith("models.layers")]


def test_no_configuration_names_a_lowering():
    """No class of the package (every configuration dataclass among them) has
    a field named for the removed options."""
    fields = {t.id for path in PACKAGE.rglob("*.py")
              for cls in ast.walk(ast.parse(path.read_text())) if isinstance(cls, ast.ClassDef)
              for stmt in cls.body if isinstance(stmt, ast.AnnAssign)
              for t in [stmt.target] if isinstance(t, ast.Name)}
    assert "dtype" in fields and not fields & {"attn_impl", "ring_inner"}
