"""Grouped matrix products for an expert layer: ``lhs`` holds the rows of
every group one after another (``group_sizes[g]`` rows of group ``g``), and
each group's rows meet that group's own matrix.

``grouped_matmul(lhs, rhs, group_sizes)``: ``lhs`` (m, k), ``rhs``
(groups, k, n), ``group_sizes`` (groups,) int32 with ``sum <= m`` -> (m, n)
in ``lhs``'s dtype. Rows past the last group come out zero, in the result
and in ``lhs``'s gradient, so a caller may size ``m`` for the worst routing
and drop nothing. No row is ever dropped: the work follows the sizes, not a
capacity.

- on the TPU, the Pallas grouped-matmul kernels that ship with JAX
  (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for the product and
  for ``lhs``'s gradient, ``tgmm`` for the matrices' gradient). Their grid is
  the row tiles the sizes make active, so a buffer sized for the worst case
  costs no product for its empty rows;
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


def resolve_grouped_impl(impl: str, *, backend: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if backend == "tpu" else "ragged_dot"


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pallas_grouped(lhs, rhs, group_sizes, interpret):
    return _pallas_fwd(lhs, rhs, group_sizes, interpret)[0]


def _pallas_fwd(lhs, rhs, group_sizes, interpret):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    out = gmm(lhs, rhs, group_sizes, lhs.dtype,
              _tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2]), interpret=interpret)
    return _zero_past(out, group_sizes), (lhs, rhs, group_sizes)


def _pallas_bwd(interpret, residuals, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, group_sizes = residuals
    m, k = lhs.shape
    n = rhs.shape[2]
    d_lhs = gmm(g, rhs, group_sizes, lhs.dtype, _tiling(m, n, k),
                transpose_rhs=True, interpret=interpret)
    d_rhs = tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                 _tiling(m, k, n), interpret=interpret)
    return _zero_past(d_lhs, group_sizes), d_rhs, None


_pallas_grouped.defvjp(_pallas_fwd, _pallas_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, impl: str = "auto", interpret: bool = False):
    impl = resolve_grouped_impl(impl, backend=jax.default_backend())
    group_sizes = group_sizes.astype(jnp.int32)
    if impl == "pallas":
        return _pallas_grouped(lhs, rhs, group_sizes, interpret)
    if impl != "ragged_dot":
        raise ValueError(f"unknown grouped matmul impl {impl!r}")
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype)
    return _zero_past(out, group_sizes)
