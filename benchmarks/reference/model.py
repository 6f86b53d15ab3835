"""The Jumbo-ViT encoder and the MAE pretraining loss in plain ``jax.numpy``.

Written from the model's description (Jumbo: k CLS tokens whose
concatenation passes through one wide MLP shared by all layers; MAE: encode
the visible quarter, decode the full grid, regress normalised pixels of the
masked patches). float32 throughout, every contraction at precision
"highest"; no kernels, no remat, no donation. ``rounding`` rounds the two
operands of every contraction to a narrower type first (the accumulation
stays float32): that is the lower-precision control, never the reference.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MEAN = np.array([0.485, 0.456, 0.406], np.float32)
STD = np.array([0.229, 0.224, 0.225], np.float32)
ROUNDINGS = {"float32": None, "bfloat16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def _rounder(rounding: str):
    dt = ROUNDINGS[rounding]
    if dt is None:
        return lambda x: x
    return lambda x: x.astype(dt).astype(jnp.float32)


class Ops:
    """Contractions at one rounding."""

    def __init__(self, rounding: str = "float32"):
        self.r = _rounder(rounding)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.r(a), self.r(b),
                          precision=jax.lax.Precision.HIGHEST)

    def dense(self, x, p):
        return self.einsum("...i,io->...o", x, p["kernel"]) + p["bias"]


def sincos2d(grid: int, dim: int) -> np.ndarray:
    """Fixed 2-D sin/cos table, (grid*grid, dim): four bands sin/cos of the
    column and row index over ``10000 ** -linspace(0, 1, dim/4)``."""
    freq = 10000.0 ** -np.linspace(0.0, 1.0, dim // 4, dtype=np.float64)
    ang = np.arange(grid, dtype=np.float64)[:, None] * freq[None, :]
    a = np.broadcast_to(ang[None, :, :], (grid, grid, dim // 4))
    b = np.broadcast_to(ang[:, None, :], (grid, grid, dim // 4))
    table = np.concatenate([np.sin(a), np.cos(a), np.sin(b), np.cos(b)], axis=2)
    return table.reshape(grid * grid, dim).astype(np.float32)


def layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def attention(ops: Ops, x, p):
    hd = p["q"]["kernel"].shape[-1]
    proj = lambda n: ops.einsum("bsd,dhe->bshe", x, p[n]["kernel"]) + p[n]["bias"]
    q, k, v = proj("q") * hd**-0.5, proj("k"), proj("v")
    probs = jax.nn.softmax(ops.einsum("bqhe,bkhe->bhqk", q, k), axis=-1)
    z = ops.einsum("bhqk,bkhe->bqhe", probs, v)
    return ops.einsum("bqhe,hed->bqd", z, p["out"]["kernel"]) + p["out"]["bias"]


def mlp(ops: Ops, x, p):
    return ops.dense(gelu(ops.dense(x, p["fc1"])), p["fc2"])


def jumbo_block(ops: Ops, x, p, jumbo, k: int):
    x = x + attention(ops, layer_norm(x, p["ln1"]), p["attn"])
    cls, patches = x[:, :k], x[:, k:]
    b, _, d = cls.shape
    # the CLS residual starts from the normalised vector, as published
    cc = layer_norm(cls.reshape(b, k * d), p["ln3"])
    cc = cc + mlp(ops, cc, jumbo)
    patches = patches + mlp(ops, layer_norm(patches, p["ln2"]), p["mlp"])
    return jnp.concatenate([cc.reshape(b, k, d), patches], axis=1)


def plain_block(ops: Ops, x, p):
    x = x + attention(ops, layer_norm(x, p["ln1"]), p["attn"])
    return x + mlp(ops, layer_norm(x, p["ln2"]), p["mlp"])


def _stack(tree: dict, n: int):
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[tree[f"block_{i}"] for i in range(n)]
    )


def _layers(step, t, stacked):
    """One layer after another. Under differentiation each layer keeps only
    its input and is computed again on the way back — the same arithmetic,
    and the only way the float32 backward pass of the wide shared MLP fits a
    16 GB chip beside its weights."""
    return jax.lax.scan(jax.checkpoint(step), t, stacked)


def normalize(images_u8):
    return (images_u8.astype(jnp.float32) / 255.0 - MEAN) / STD


def patches_of(x, p: int):
    b, h, w, c = x.shape
    x = x.reshape(b, h // p, p, w // p, p, c).swapaxes(2, 3)
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def encode(ops: Ops, params, x, m: dict, keep=None):
    """Normalised images (B, H, W, 3) -> final-norm tokens (B, k + n, dim).
    ``keep``: indices of the visible patches (MAE), or None for all."""
    p, d, k = m["patch_size"], m["enc_dim"], m["num_cls_tokens"]
    if m["posemb"] != "sincos2d":
        raise ValueError("the reference implements sincos2d positions only")
    kernel = params["embed"]["proj"]["kernel"].reshape(p * p * 3, d)
    t = ops.einsum("bnp,pd->bnd", patches_of(x, p), kernel)
    t = t + params["embed"]["proj"]["bias"] + sincos2d(m["image_size"] // p, d)
    if keep is not None:
        t = t[:, keep]
    cls = jnp.broadcast_to(params["cls_tokens"], (t.shape[0], k, d))
    t = jnp.concatenate([cls, t], axis=1)
    step = lambda t, blk: (jumbo_block(ops, t, blk, params["jumbo_mlp"], k), None)
    t, _ = _layers(step, t, _stack(params, m["enc_layers"]))
    return layer_norm(t, params["ln"])


def features(params, images_u8, m: dict, rounding: str = "float32"):
    """Pooled serving features: the k CLS tokens of the full-sequence
    encoder, concatenated — (B, k*dim)."""
    t = encode(Ops(rounding), params, normalize(images_u8), m)
    return t[:, : m["num_cls_tokens"]].reshape(t.shape[0], -1)


def mae_loss(params, images_u8, noise, m: dict, rounding: str = "float32"):
    """Mean over images of the masked-patch MSE. ``noise`` (n_patches,) is
    the uniform draw whose argsort orders the patches: the first
    ``int(n * (1 - mask_ratio))`` stay visible, one order for the batch."""
    ops = Ops(rounding)
    k, p = m["num_cls_tokens"], m["patch_size"]
    n = (m["image_size"] // p) ** 2
    keep_len = int(n * (1.0 - m["mask_ratio"]))
    shuffle = jnp.argsort(noise)
    restore = jnp.argsort(shuffle)
    masked = (jnp.arange(n) >= keep_len)[restore]

    x = normalize(images_u8)
    t = encode(ops, params["encoder"], x, m, keep=shuffle[:keep_len])
    t = ops.dense(t, params["decoder_proj"])
    cls, visible = t[:, :k], t[:, k:]
    fill = jnp.broadcast_to(params["mask_token"], (t.shape[0], n - keep_len, t.shape[-1]))
    full = jnp.concatenate([visible, fill], axis=1)[:, restore]
    full = full + sincos2d(m["image_size"] // p, m["dec_dim"])
    t = jnp.concatenate([cls, full], axis=1)
    dec = params["decoder"]
    step = lambda t, blk: (plain_block(ops, t, blk), None)
    t, _ = _layers(step, t, _stack(dec, m["dec_layers"]))
    pred = ops.dense(layer_norm(t, dec["ln"])[:, k:], params["pixel_proj"])

    target = patches_of(x, p)
    if m["norm_pix_loss"]:
        mu = target.mean(-1, keepdims=True)
        target = (target - mu) / jnp.sqrt(target.var(-1, keepdims=True) + 1e-6)
    per_patch = jnp.square(target - pred).mean(-1)
    per_image = jnp.where(masked, per_patch, 0.0).sum(-1) / masked.sum()
    return per_image.mean()
