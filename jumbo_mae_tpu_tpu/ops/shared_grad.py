"""The gradient of a kernel that many layers share, formed once a step.

Autodiff of ``y_l = x_l @ W`` over layers ``l = 1..L`` gives
``dW = Σ_l x_lᵀ·dy_l``: L products with a contraction of ``rows``, each
writing a kernel-shaped partial that is then summed through HBM. The same
sum is one product, ``dW = X_catᵀ·dY_cat`` with the layers' rows stacked:
contraction ``L · rows``, the result written once from one float32
accumulation (PERF.md §6, PR 39: the L/16 step's shared 3072×12288 kernels
see 128 rows a layer).

Two functions with their own VJP rules carry it through ordinary autodiff:
:func:`open_slots` hands out one zero slot a layer, and its backward rule
receives the slots' cotangents and forms the product; :func:`record` is the
identity on a layer's ``y`` whose backward rule writes ``(x, dy)`` into that
layer's slot. The layer computes ``x @ stop_gradient(W)``, so dX is autodiff's
own and only the kernel's gradient takes this route.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def open_slots(kernel: jax.Array, layers: int, rows: int, dtype) -> tuple:
    """``layers`` slots for a shared ``(fan_in, fan_out)`` kernel: each a pair
    of zeros, ``(rows, fan_in)`` and ``(rows, fan_out)`` in ``dtype``. Nothing
    reads their values; their cotangents, filled by :func:`record`, come back
    to the backward rule here, which returns the kernel's gradient as one
    product over all layers' rows (``dtype`` operands, float32 accumulation)."""
    fan_in, fan_out = kernel.shape
    kernel_dtype = kernel.dtype

    def zeros():
        return tuple(
            (jnp.zeros((rows, fan_in), dtype), jnp.zeros((rows, fan_out), dtype))
            for _ in range(layers)
        )

    @jax.custom_vjp
    def slots(kernel):
        return zeros()

    def bwd(_, filled):
        xs, dys = zip(*filled)
        dw = lax.dot_general(
            jnp.concatenate(xs),
            jnp.concatenate(dys),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (dw.astype(kernel_dtype),)

    slots.defvjp(lambda kernel: (zeros(), None), bwd)
    return slots(kernel)


@jax.custom_vjp
def record(slot: tuple, x: jax.Array, y: jax.Array) -> jax.Array:
    """``y``, unchanged. Backward: ``dy`` passes through to ``y``, ``x`` gets
    none (its gradient is the product's that made ``y``), and ``(x, dy)`` is
    the cotangent of ``slot``."""
    return y


record.defvjp(lambda slot, x, y: (y, x), lambda x, dy: ((x, dy), None, dy))
