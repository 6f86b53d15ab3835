"""A language head's training loss a tile of tokens at a time, its gradients
formed where the logits are.

The plain form — ``logits = x @ W`` for every token, a log-sum-exp and a
gather over them, autodiff for the rest — holds a (tokens, rows) float32
array, so a step keeps or recomputes the step's largest array and runs the
head's product four times (logits, logits again, dX, dW). Here the tokens are
walked in tiles, and the pass that forms a tile's logits also forms its
log-sum-exp, its target logits, ``(p − onehot) · weight``, the tile's rows of
dX and its addend to dW: three products, and nothing a tile's height times
``rows`` outlives its tile. What crosses to the backward pass is dX (the
hidden state's shape and dtype) and dW (the kernel's shape and dtype, one
rounding of a float32 sum over the tiles); the backward rule scales them by
the scalar's cotangent (PERF.md §6, PR 41).

The precision is no lower than the plain form's: operands in the hidden
state's dtype, float32 accumulation; logits (not rounded to the operands'
dtype on their way, as the plain einsum's are), log-sum-exp, target logit,
``p − onehot`` and the loss in float32; ``p − onehot`` enters the two
gradient products in the hidden state's dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# one tile's float32 logits; the tile follows from it and the shapes
TILE_BYTES = 256 * 2**20
# a tile's height is whole lane tiles wherever the tokens are cut at all
TILE_ALIGN = 128


def head_tile(tokens: int, rows: int) -> int:
    """Tokens a tile, from the shapes: every token where their (tokens,
    ``rows``) float32 logits stay within ``TILE_BYTES``; else, of the whole
    ``TILE_ALIGN``s from all that the bytes allow down to half of it, the one
    that leaves the last tile the least padding (rows of weight 0; none where
    it divides the tokens), the largest such."""
    most = max(TILE_BYTES // (4 * rows) // TILE_ALIGN, 1) * TILE_ALIGN
    if tokens <= most:
        return tokens
    return min(range(most, most // 2, -TILE_ALIGN),
               key=lambda tile: (-tokens % tile, -tile))


def _walk(x, kernel, targets, weights, tile: int, grads: bool):
    """Every token's ``logsumexp − target logit`` (float32) and, with
    ``grads``, the gradients of ``Σ weights · nll`` in ``x`` and ``kernel``."""
    tokens, dim = x.shape
    rows = kernel.shape[1]
    w = kernel.astype(x.dtype)
    tiles = -(-tokens // tile)
    pad = tiles * tile - tokens  # rows of weight 0: they add nothing
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        tiles, tile, *a.shape[1:])

    def one(dw, xs):
        x_t, t_t, w_t = xs
        logits = jnp.dot(x_t, w, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, t_t[:, None], axis=-1)[:, 0]
        if not grads:
            return dw, (lse - hit, None)
        onehot = t_t[:, None] == jnp.arange(rows, dtype=t_t.dtype)
        g = ((jnp.exp(logits - lse[:, None]) - onehot) * w_t[:, None]).astype(x.dtype)
        # formed once a tile: fused into each of the two products that read
        # it, the exponentials are taken twice and the products run slower
        g = lax.optimization_barrier(g)
        dx_t = lax.dot_general(g, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32).astype(x.dtype)
        dw = dw + lax.dot_general(x_t, g, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dw, (lse - hit, dx_t)

    dw = jnp.zeros((dim, rows), jnp.float32) if grads else None
    dw, (nll, dx) = lax.scan(one, dw, (cut(x), cut(targets), cut(weights)))
    nll = nll.reshape(tiles * tile)[:tokens]
    if grads:
        dx = dx.reshape(tiles * tile, dim)[:tokens]
    return nll, dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _tiled(x, kernel, targets, weights, tile):
    nll, _, _ = _walk(x, kernel, targets, weights, tile, grads=False)
    return (weights * nll).sum(), nll


def _tiled_fwd(x, kernel, targets, weights, tile):
    nll, dx, dw = _walk(x, kernel, targets, weights, tile, grads=True)
    return ((weights * nll).sum(), nll), (dx, dw.astype(kernel.dtype), nll)


def _tiled_bwd(tile, kept, cotangent):
    dx, dw, nll = kept
    ct, _ = cotangent  # head_loss lets no gradient reach the per-token values
    return ct.astype(dx.dtype) * dx, ct.astype(dw.dtype) * dw, None, ct * nll


_tiled.defvjp(_tiled_fwd, _tiled_bwd)


def head_loss(x, kernel, targets, weights, *, tile: int | None = None):
    """``(Σ_t weights[t] · nll[t], nll)`` with ``nll[t] = logsumexp(x[t] @
    kernel) − (x[t] @ kernel)[targets[t]]``: ``x`` (tokens, dim) in the
    compute dtype, ``kernel`` (dim, rows), ``targets`` (tokens,) integer row
    indices, ``weights`` (tokens,) float32. The scalar is differentiable in
    ``x``, ``kernel`` and ``weights`` for any cotangent; ``nll`` (float32) is
    a value only — no gradient flows through it. ``tile`` (tokens a tile;
    :func:`head_tile` of the shapes by default) need not divide the tokens."""
    tokens, rows = x.shape[0], kernel.shape[1]
    tile = head_tile(tokens, rows) if tile is None else min(tile, tokens)
    loss, nll = _tiled(x, kernel, targets, weights.astype(jnp.float32), tile)
    return loss, lax.stop_gradient(nll)
