"""Parameter shapes of the linear-attention / grouped-query sparse-expert
language model (``kda_gqa_lm_model.py``) on one chip's share, written from
the configuration file, its seeded weights and its seeded non-gradient router
biases.

``config`` is a configuration file's document: ``config.json``'s own keys at
the top level (``Solar-Open2-250B``'s names), with ``num_hidden_layers``,
``n_routed_experts``, ``vocab_size`` and the three head counts
(``num_attention_heads``, ``num_key_value_heads``,
``linear_attn_config.num_heads``) holding what this chip holds and
``published`` the model's own counts; ``gqa_layers`` is the published list,
whole, and this chip's layers are ``0 .. num_hidden_layers``. The tree uses
the program's checkpoint names so that the harness can hand the same weights
to the program. Weights come from ``params.make_params`` (0.02 x a normal
truncated at two deviations, norm scales about 1) but for three kinds of leaf
that a linear-attention layer needs at another scale (the file's
``assumed``): the convolution filters, ``A_log`` and ``dt_bias``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import params as ref_params
from benchmarks.reference.lm_params import _gated, _kernel, _norm  # the tree's leaf shapes
from benchmarks.reference.lm_params import bias_shapes, sparse_blocks  # noqa: F401 - this family's too

# the deviation of the seeded router biases: a tenth of the other families'
# 0.01, one step of the bias rule (the file's ``assumed``, ``router_bias_init``)
ROUTER_BIAS_INIT = 0.001


def is_linear(c: dict, layer: int) -> bool:
    """Layer ``layer`` is grouped-query attention where it is in
    ``gqa_layers`` (0, 4, 8, ...: the period starts with it), linear
    attention otherwise."""
    return layer not in c["gqa_layers"]


def _linear_attention(c: dict) -> dict:
    lin = c["linear_attn_config"]
    d, h, e, taps = c["hidden_size"], lin["num_heads"], lin["head_dim"], lin[
        "short_conv_kernel_size"]
    wide, through = (lambda: _kernel(d, h, e)), (lambda: (_kernel(d, e), _kernel(e, h, e)))
    (f_a, f_b), (gate_a, gate_b) = through(), through()  # kda_use_full_proj false: rank head_dim
    return {"q": wide(), "k": wide(), "v": wide(), "f_a": f_a, "f_b": f_b,
            "b": _kernel(d, h), "gate_a": gate_a, "gate_b": gate_b,
            "q_conv": _kernel(taps, h, e), "k_conv": _kernel(taps, h, e),
            "v_conv": _kernel(taps, h, e),
            "A_log": (h,), "dt_bias": (h, e), "o_norm": _norm(e), "out": _kernel(h, e, d)}


def _attention(c: dict) -> dict:
    d, e = c["hidden_size"], c["head_dim"]
    h, g = c["num_attention_heads"], c["num_key_value_heads"]
    return {"q": _kernel(d, h, e), "k": _kernel(d, g, e), "v": _kernel(d, g, e),
            "gate": _kernel(d, h), "out": _kernel(h, e, d)}


def _block(c: dict, layer: int) -> dict:
    d, w = c["hidden_size"], c["moe_intermediate_size"]
    blk = {"ln1": _norm(d), "ln2": _norm(d),
           "attn": _linear_attention(c) if is_linear(c, layer) else _attention(c)}
    if layer < c["first_k_dense_replace"]:
        return blk | {"mlp": _gated(d, c["intermediate_size"])}
    moe = _gated(d, w, lead=(c["n_routed_experts"],))
    moe |= {"router": _kernel(d, c["published"]["n_routed_experts"]),
            "shared": _gated(d, c["n_shared_experts"] * w)}
    return blk | {"moe": moe}


def shapes(c: dict) -> dict:
    d, rows = c["hidden_size"], c["vocab_size"]
    tree = {"embedding": (rows, d), "ln": _norm(d), "head": _kernel(d, rows)}
    for i in range(c["num_hidden_layers"]):
        tree[f"block_{i}"] = _block(c, i)
    return tree


def make_params(seed, c: dict) -> dict:
    """Float32 weights from ``seed`` (an int or a traced uint32). A linear-
    attention layer's filters are uniform over ±1/sqrt(taps); its ``A_log``
    is the log of a uniform draw over 1 .. 16 a head; its ``dt_bias`` is such
    that ``softplus(dt_bias)`` is log-uniform over 0.001 .. 0.1 a channel
    (flash-linear-attention's initialisers: assumed). Jit-compatible."""
    params = ref_params.make_params(seed, shapes(c))
    base = jax.random.fold_in(jax.random.key(seed), 0x6B6461)  # "kda"
    bound = c["linear_attn_config"]["short_conv_kernel_size"] ** -0.5
    for i in range(c["num_hidden_layers"]):
        if not is_linear(c, i):
            continue
        attn = params[f"block_{i}"]["attn"]
        keys = jax.random.split(jax.random.fold_in(base, i), 5)
        for key, name in zip(keys, ("q_conv", "k_conv", "v_conv")):
            shape = attn[name]["kernel"].shape
            attn[name] = {"kernel": jax.random.uniform(key, shape, jnp.float32, -bound, bound)}
        attn["A_log"] = jnp.log(jax.random.uniform(keys[3], attn["A_log"].shape, jnp.float32,
                                                   1.0, 16.0))
        dt = jnp.exp(jax.random.uniform(keys[4], attn["dt_bias"].shape, jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        attn["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse
    return params


def make_biases(seed, c: dict) -> dict:
    """The router biases from ``seed`` (an int or a traced uint32):
    ``ROUTER_BIAS_INIT`` x a normal, one draw a sparse block, so that none
    starts at zero. Jit-compatible."""
    base = jax.random.fold_in(jax.random.key(seed), 0x62696173)  # "bias"
    e = c["published"]["n_routed_experts"]
    return {name: {"moe": {"router_bias": ROUTER_BIAS_INIT * jax.random.normal(
        jax.random.fold_in(base, i), (e,), jnp.float32)}}
        for i, name in enumerate(sparse_blocks(c))}
