"""Grouped matrix products for an expert layer: ``lhs`` holds the rows of
every group one after another (``group_sizes[g]`` rows of group ``g``), and
each group's rows meet that group's own matrix.

``grouped_matmul(lhs, rhs, group_sizes)``: ``lhs`` (m, k), ``rhs``
(groups, k, n) — (groups, n, k) with ``transpose_rhs`` —, ``group_sizes``
(groups,) int32 with ``sum <= m`` -> (m, n) in ``lhs``'s dtype. Rows past the
last group come out zero, in the result and in ``lhs``'s gradient. No row is
ever dropped: the work follows the sizes, not a capacity.

``grouped_outer(lhs, g, group_sizes, onto)``: ``lhs`` (m, k), ``g`` (m, n)
-> ``onto`` (groups, k, n) plus each group's ``lhs_gᵀ g_g``, in ``onto``'s
dtype: the matrices' gradient, summed onto what earlier rows gave.

``m`` is the caller's to choose. The expert layer (``models/lm.py``) hands
over one chunk of its sorted pairs at a time, with the sizes clipped to the
chunk, so ``m`` follows the rows the chip holds and not the worst routing.

- on the TPU, the Pallas grouped-matmul kernels that ship with JAX
  (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for the product and
  for ``lhs``'s gradient, ``tgmm`` for the matrices' gradient). Their grid is
  the row tiles the sizes make active: a chunk that is partly empty costs no
  product for its empty rows;
- elsewhere ``jax.lax.ragged_dot``, which XLA differentiates itself. Not on
  the TPU: there its gradient for ``lhs`` read 0.85 off a dense float32
  product where the kernels read 0.003 (PERF.md, PR 27).

``impl`` is resolved as attention's is (``resolve_grouped_impl``): the
explicit names pass through, ``auto`` takes the kernels on a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROW_TILE = 256  # rows a kernel tile takes: a weight tile is re-read per row tile

_OUTER = jax.lax.RaggedDotDimensionNumbers(  # the rows are what is summed over
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def resolve_grouped_impl(impl: str, *, backend: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if backend == "tpu" else "ragged_dot"


def _use_kernels(impl: str) -> bool:
    impl = resolve_grouped_impl(impl, backend=jax.default_backend())
    if impl not in ("pallas", "ragged_dot"):
        raise ValueError(f"unknown grouped matmul impl {impl!r}")
    return impl == "pallas"


def _zero_past(out, group_sizes):
    rows = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(rows < group_sizes.sum(), out, jnp.zeros((), out.dtype))


def _tile(size: int, want: int) -> int:
    """Largest multiple of 128 up to ``want`` that divides ``size``; the
    whole of a size the lanes do not divide (the interpreter's tests)."""
    if size % 128:
        return size
    tile = min(want, size) // 128 * 128
    while size % tile:
        tile -= 128
    return tile


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    return _tile(m, ROW_TILE), _tile(k, 1024), _tile(n, 1024)


def _pallas_product(lhs, rhs, group_sizes, transpose_rhs, interpret):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    out = gmm(lhs, rhs, group_sizes, lhs.dtype, _tiling(lhs.shape[0], lhs.shape[1], n),
              transpose_rhs=transpose_rhs, interpret=interpret)
    return _zero_past(out, group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pallas_grouped(lhs, rhs, group_sizes, interpret):
    return _pallas_product(lhs, rhs, group_sizes, False, interpret)


def _pallas_fwd(lhs, rhs, group_sizes, interpret):
    return _pallas_product(lhs, rhs, group_sizes, False, interpret), (lhs, rhs, group_sizes)


def _pallas_bwd(interpret, residuals, g):
    lhs, rhs, group_sizes = residuals
    d_lhs = _pallas_product(g, rhs, group_sizes, True, interpret)
    d_rhs = grouped_outer(lhs, g, group_sizes, jnp.zeros_like(rhs),
                          impl="pallas", interpret=interpret)
    return d_lhs, d_rhs, None


_pallas_grouped.defvjp(_pallas_fwd, _pallas_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
                   impl: str = "auto", interpret: bool = False):
    group_sizes = group_sizes.astype(jnp.int32)
    if _use_kernels(impl):
        if transpose_rhs:  # a gradient's own product: nothing differentiates it
            return _pallas_product(lhs, rhs, group_sizes, True, interpret)
        return _pallas_grouped(lhs, rhs, group_sizes, interpret)
    if transpose_rhs:
        rhs = rhs.swapaxes(1, 2)
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype)
    return _zero_past(out, group_sizes)


def grouped_outer(lhs, g, group_sizes, onto, *, impl: str = "auto", interpret: bool = False):
    group_sizes = group_sizes.astype(jnp.int32)
    if _use_kernels(impl):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

        # the kernel adds ``onto`` as it stores a group's tile, in place: a
        # tile of it comes and goes beside the float32 accumulator, and at
        # four bytes the three fit the 16 MiB of VMEM at half the depth only
        m, k = lhs.shape
        depth = 1024 if onto.dtype.itemsize <= 2 else 512
        return tgmm(lhs.swapaxes(0, 1), g, group_sizes, onto.dtype,
                    (_tile(m, ROW_TILE), _tile(k, depth), _tile(g.shape[1], 1024)),
                    existing_out=onto, interpret=interpret)
    return onto + jax.lax.ragged_dot_general(lhs, g, group_sizes, _OUTER,
                                             preferred_element_type=onto.dtype)
