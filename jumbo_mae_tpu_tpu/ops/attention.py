"""Softmax attention, and the one place that chooses how it is lowered.

Two entries, both over pre-scaled queries (already times ``head_dim**-0.5``):

- **non-causal**, ``attention(q, k, v)`` over (batch, seq, heads, head_dim)
  tensors (the ViT's; the reference materializes full (B,H,N,N) scores,
  ``/root/reference/src/modeling.py:136-137`` — fine at N=197, fatal for
  long context);
- **causal**, ``causal_attention(q_a, q_b, k_a, k_b, v, impl=None, window=,
  diffusion=)`` over head-major (batch, heads, seq, d) tensors (the language
  models'): a score of one part or of two (the second with a key all heads
  share: latent attention's rotary columns), key/value heads that a group of
  query heads shares, and one of three visibility patterns: every earlier
  key; an optional window of tokens a query looks back over; or, with
  ``diffusion`` = a block length, the block-diffusion pattern over a row
  that holds a clean and a noisy copy of a sequence
  (``block_diffusion_visible``).

Neither is handed a lowering by the program. ``lowering`` is the rule, a function of what a call
can observe; the entries read those things where the call is traced (the
backend at call time, the ambient mesh's ``seq`` axis). The lowerings:

- ``"einsum"``: the (seq, seq) scores exist. ``xla_attention`` /
  ``xla_causal_attention`` are its plain forms and the tests' oracle;
  ``models/layers.Attention`` has its own (head-major output, a mask,
  dropout on the probabilities), which is why it asks ``lowering_here``
  before it calls ``attention``.
- ``"flash"``: the Pallas kernels of ``ops/pallas/attention.py``, which
  never hold the scores in HBM (any sequence length: they pad to lane tiles
  and mask the pad keys themselves).
- ``"ring"``: ``parallel/ring_attention.py``, tokens split over the mesh's
  ``seq`` axis and K/V passed round it. Non-causal only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# The v5e-measured crossover (PERF_ARCHIVE.md, round 5, fwd+bwd ms) sits
# between 199 tokens (einsum 5.2 vs flash 8.7) and 787 (flash 9.0 vs einsum
# 15.3; at 3139, 24.7 vs 45.8); 512 splits it conservatively.
AUTO_FLASH_MIN_SEQ = 512


def lowering(*, backend: str, seq_len: int, probs_needed: bool, seq_shards: int) -> str:
    """The rule. ``probs_needed``: the caller needs the probabilities
    themselves (a mask is given, or dropout is active in this call), which
    only the einsum form has: the kernels and the ring take no mask operand
    and have no probability dropout. ``seq_shards``: the size of the ambient
    mesh's ``seq`` axis."""
    if probs_needed:
        return "einsum"
    if seq_shards > 1:
        return "ring"
    if backend == "tpu" and seq_len >= AUTO_FLASH_MIN_SEQ:
        return "flash"
    return "einsum"


def lowering_here(seq_len: int, *, probs_needed: bool = False) -> str:
    """``lowering`` of what a non-causal call can see where it is traced."""
    return lowering(backend=jax.default_backend(), seq_len=seq_len, probs_needed=probs_needed,
                    seq_shards=jax.sharding.get_abstract_mesh().shape.get("seq", 1))


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Softmax(q·kᵀ)·v. q, k, v: (batch, seq, heads, head_dim); returns the
    shape of q."""
    how = lowering_here(q.shape[1])
    if how == "ring":
        from jumbo_mae_tpu_tpu.parallel.ring_attention import ring_self_attention

        return ring_self_attention(q, k, v)
    if how == "flash":
        from jumbo_mae_tpu_tpu.ops.pallas.attention import pallas_flash_attention

        return pallas_flash_attention(q, k, v)
    return xla_attention(q, k, v)


def block_diffusion_visible(rows: int, block: int) -> jax.Array:
    """The (rows, rows) boolean mask of the block-diffusion pattern: the row
    holds a clean copy of a sequence of ``L = rows / 2`` tokens and then a
    noisy copy of it, and with ``b(i) = (i mod L) // block`` key ``j`` is
    visible to query ``i`` iff both are clean and ``b(j) <= b(i)``, or ``i``
    is noisy, ``j`` clean and ``b(j) < b(i)``, or both are noisy and ``b(j) ==
    b(i)``. A clean query sees no noisy key."""
    at = jnp.arange(rows)
    noisy, place = at >= rows // 2, at % (rows // 2) // block
    (q_noisy, k_noisy), (b_q, b_k) = ((x[:, None], x[None, :]) for x in (noisy, place))
    return jnp.where(q_noisy, jnp.where(k_noisy, b_k == b_q, b_k < b_q), ~k_noisy & (b_k <= b_q))


def xla_causal_attention(q_a, q_b, k_a, k_b, v, window: int | None = None,
                         diffusion: int | None = None) -> jax.Array:
    """The einsum form of :func:`causal_attention`: the (seq, seq) scores
    exist, so it is for the CPU's tests and short sequences only."""
    group = q_a.shape[1] // k_a.shape[1]
    if group > 1:  # each key/value head once a query head of its group
        k_a, v = jnp.repeat(k_a, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q_a, k_a, preferred_element_type=jnp.float32)
    if q_b is not None:
        s = s + jnp.einsum("bhqd,bkd->bhqk", q_b, k_b, preferred_element_type=jnp.float32)
    if diffusion is not None:
        keep = block_diffusion_visible(s.shape[-1], diffusion)
    else:
        keep = jnp.tril(jnp.ones(s.shape[-2:], bool))
    if window is not None:
        keep = keep & ~jnp.tril(keep, -window)  # row − col < window
    probs = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def causal_attention(q_a, q_b, k_a, k_b, v, *, impl: str | None = None,
                     window: int | None = None, diffusion: int | None = None) -> jax.Array:
    """Causal softmax(q_a·k_aᵀ + q_b·k_bᵀ)·v, head-major: ``q_a`` (batch,
    heads, seq, d_a), ``k_a`` (batch, kv heads, seq, d_a) and ``v`` (batch, kv
    heads, seq, d_v) with ``kv heads`` a divisor of ``heads`` (query head ``h``
    reads key/value head ``h // (heads / kv heads)``); ``q_b`` (batch, heads,
    seq, d_b) and ``k_b`` (batch, seq, d_b) shared by all heads, or both None
    for a score of one part; queries pre-scaled. With ``window``, query ``i``
    sees keys ``i − window + 1 .. i``. With ``diffusion`` = ``B`` (a block
    length, a power of two; no window then) the ``seq`` rows are a clean copy
    of ``seq / 2`` tokens and then a noisy copy, and visibility is
    ``block_diffusion_visible``'s: which of the three patterns a call runs
    under is its caller's configuration (``MlaMoeConfig.diffusion_block``),
    never an option here, and the Pallas kernels cut their block pairs by it
    where they are built (``ops/pallas/attention._diffusion_cuts``). The
    family has no lowering for a split sequence and no caller that needs the
    probabilities.

    ``impl`` is None everywhere in the program: the rule is asked here. The
    keyword stays because the benchmark's own mutation tests
    (``tests/benchmarks/test_bench_correct_gqa_lm.py``) stand in for this
    function as ``models/lm.py`` calls it and require it; a ``benchmark`` PR
    drops it there, then here (ROADMAP D4)."""
    if impl is None:
        impl = lowering(backend=jax.default_backend(), seq_len=q_a.shape[2],
                        probs_needed=False, seq_shards=1)
    if impl == "flash":
        from jumbo_mae_tpu_tpu.ops.pallas.attention import pallas_causal_attention

        return pallas_causal_attention(q_a, q_b, k_a, k_b, v, window=window, diffusion=diffusion)
    return xla_causal_attention(q_a, q_b, k_a, k_b, v, window, diffusion)
