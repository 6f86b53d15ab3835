"""Parameter shapes of the Jumbo-ViT MAE, written from the model description,
and seeded weights for them.

The tree uses the checkpoint layout's names (encoder/block_i/attn/q/kernel,
...) so that the harness can hand the same weights to the program; the
harness refuses to run when the program's own tree differs from this one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dense(d_in, d_out):
    return {"kernel": (d_in, d_out), "bias": (d_out,)}


def _ln(d):
    return {"scale": (d,), "bias": (d,)}


def _attn(dim, heads):
    hd = dim // heads
    qkv = {"kernel": (dim, heads, hd), "bias": (heads, hd)}
    return {"q": qkv, "k": dict(qkv), "v": dict(qkv),
            "out": {"kernel": (heads, hd, dim), "bias": (dim,)}}


def _mlp(dim, hidden):
    return {"fc1": _dense(dim, hidden), "fc2": _dense(hidden, dim)}


def encoder_shapes(m: dict) -> dict:
    """``m`` is the ``model`` section of a configuration file."""
    d, k, p = m["enc_dim"], m["num_cls_tokens"], m["patch_size"]
    tree = {
        "embed": {"proj": {"kernel": (p, p, 3, d), "bias": (d,)}},
        "cls_tokens": (1, k, d),
        "jumbo_mlp": _mlp(k * d, 4 * k * d),
        "ln": _ln(d),
    }
    for i in range(m["enc_layers"]):
        tree[f"block_{i}"] = {
            "ln1": _ln(d), "attn": _attn(d, m["enc_heads"]),
            "ln2": _ln(d), "mlp": _mlp(d, 4 * d), "ln3": _ln(k * d),
        }
    return tree


def mae_shapes(m: dict) -> dict:
    dd, p = m["dec_dim"], m["patch_size"]
    dec = {"ln": _ln(dd)}
    for i in range(m["dec_layers"]):
        dec[f"block_{i}"] = {
            "ln1": _ln(dd), "attn": _attn(dd, m["dec_heads"]),
            "ln2": _ln(dd), "mlp": _mlp(dd, 4 * dd),
        }
    return {
        "encoder": encoder_shapes(m),
        "mask_token": (1, 1, dd),
        "decoder_proj": _dense(m["enc_dim"], dd),
        "decoder": dec,
        "pixel_proj": _dense(dd, p * p * 3),
    }


def _is_shape(x):
    return isinstance(x, tuple)


def flat_shapes(tree: dict) -> dict[str, tuple]:
    """``{"encoder/embed/proj/kernel": (16, 16, 3, 1024), ...}``, sorted."""
    leaves = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]
    return dict(sorted(
        ("/".join(str(k.key) for k in path), shape) for path, shape in leaves
    ))


def make_params(seed, tree: dict) -> dict:
    """Float32 weights for ``tree`` from ``seed`` (a Python int or a traced
    uint32): LayerNorm scales 1 + 0.02 n, everything else 0.02 n with n a
    normal truncated at two deviations — the scale the model trains from, with
    no leaf left at zero so that every bias and token takes part in a
    comparison. Jit-compatible; each leaf has its own key (the seed folded
    with the leaf's rank among the sorted names), so one call makes the whole
    tree on the device with nothing larger than a leaf beside it."""
    base = jax.random.key(seed)
    rank = {name: i for i, name in enumerate(flat_shapes(tree))}

    def leaf(path, shape):
        name = "/".join(str(k.key) for k in path)
        key = jax.random.fold_in(base, rank[name])
        n = 0.02 * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
        return 1.0 + n if name.endswith("scale") else n

    return jax.tree_util.tree_map_with_path(leaf, tree, is_leaf=_is_shape)


def seeded(seed: int, tree: dict) -> dict:
    """:func:`make_params` as one compiled call with the seed traced, so
    that every seed runs the same program."""
    return jax.jit(lambda s: make_params(s, tree))(np.uint32(int(seed) % 2**32))
