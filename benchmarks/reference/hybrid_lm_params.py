"""Parameter shapes of the hybrid linear-attention / latent-attention
sparse-expert language model (``hybrid_lm_model.py``) on one chip's share,
written from the configuration file, its seeded weights and its seeded
non-gradient router biases.

``config`` is a configuration file's document: ``config.json``'s own keys at
the top level (``Ling-3.0-flash``'s names), with ``num_experts``,
``vocab_size`` and ``num_hidden_layers`` holding what this chip holds and
``published`` the model's own counts. The tree uses the program's checkpoint
names so that the harness can hand the same weights to the program. Weights
come from ``params.make_params`` (0.02 x a normal truncated at two
deviations, norm scales about 1) but for three kinds of leaf that a linear-
attention layer needs at another scale (the file's ``assumed``): the
convolution filters, ``A_log`` and ``dt_bias``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import lm_params
from benchmarks.reference import params as ref_params
from benchmarks.reference.lm_params import _gated, _kernel, _norm  # the tree's leaf shapes


def is_linear(c: dict, layer: int) -> bool:
    """Layer ``layer`` is latent attention when ``layer + 1`` is a multiple
    of ``layer_group_size``, linear attention otherwise."""
    return (layer + 1) % c["layer_group_size"] != 0


def _linear_attention(c: dict) -> dict:
    d, h, e, taps = (c["hidden_size"], c["num_attention_heads"], c["head_dim"],
                     c["short_conv_kernel_size"])
    wide = lambda: _kernel(d, h, e)
    return {"q": wide(), "k": wide(), "v": wide(), "f": wide(),
            "b": _kernel(d, h), "gate": _kernel(d, h),
            "q_conv": _kernel(taps, h, e), "k_conv": _kernel(taps, h, e),
            "v_conv": _kernel(taps, h, e),
            "A_log": (h,), "dt_bias": (h, e), "o_norm": _norm(e), "out": _kernel(h, e, d)}


def _latent_attention(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    return {"q": _kernel(d, h, nope + rope), "kv_a": _kernel(d, c["kv_lora_rank"] + rope),
            "kv_norm": _norm(c["kv_lora_rank"]),
            "kv_b": _kernel(c["kv_lora_rank"], h, nope + c["v_head_dim"]),
            "gate": _kernel(d, h), "out": _kernel(h, c["v_head_dim"], d)}


def _block(c: dict, sparse: bool, linear: bool) -> dict:
    d = c["hidden_size"]
    blk = {"ln1": _norm(d), "ln2": _norm(d),
           "attn": _linear_attention(c) if linear else _latent_attention(c)}
    if not sparse:
        return blk | {"mlp": _gated(d, c["intermediate_size"])}
    moe = _gated(d, c["moe_intermediate_size"], lead=(c["num_experts"],))
    moe |= {"router": _kernel(d, c["published"]["num_experts"]),
            "shared": _gated(d, c["num_shared_experts"] * c["moe_shared_expert_intermediate_size"])}
    return blk | {"moe": moe}


def shapes(c: dict) -> dict:
    d, rows = c["hidden_size"], c["vocab_size"]
    tree = {"embedding": (rows, d), "ln": _norm(d), "head": _kernel(d, rows)}
    for i in range(c["num_hidden_layers"]):
        tree[f"block_{i}"] = _block(c, i >= c["first_k_dense_replace"], is_linear(c, i))
    if c["num_nextn_predict_layers"]:  # mtp_use_kda false: a block of the latent kind
        tree |= {"mtp_embed_norm": _norm(d), "mtp_hidden_norm": _norm(d),
                 "mtp_merge": _kernel(2 * d, d), "mtp_block": _block(c, True, False)}
    return tree


def make_params(seed, c: dict) -> dict:
    """Float32 weights from ``seed`` (an int or a traced uint32). A linear-
    attention layer's filters are uniform over ±1/sqrt(taps); its ``A_log``
    is the log of a uniform draw over 0.25 .. 1 a head; its ``dt_bias`` is
    such that the decay a step at a zero gate input, ``exp(lower_bound ·
    sigmoid(exp(A_log) · dt_bias))``, leaves ``1 − α`` log-uniform over
    0.001 .. 0.1. Jit-compatible."""
    params = ref_params.make_params(seed, shapes(c))
    base = jax.random.fold_in(jax.random.key(seed), 0x6B6461)  # "kda"
    bound = c["short_conv_kernel_size"] ** -0.5
    for i in range(c["num_hidden_layers"]):
        if not is_linear(c, i):
            continue
        attn = params[f"block_{i}"]["attn"]
        keys = jax.random.split(jax.random.fold_in(base, i), 5)
        for key, name in zip(keys, ("q_conv", "k_conv", "v_conv")):
            shape = attn[name]["kernel"].shape
            attn[name] = {"kernel": jax.random.uniform(key, shape, jnp.float32, -bound, bound)}
        rate = jax.random.uniform(keys[3], attn["A_log"].shape, jnp.float32, 0.25, 1.0)
        miss = jnp.exp(jax.random.uniform(keys[4], attn["dt_bias"].shape, jnp.float32,
                                          jnp.log(1e-3), jnp.log(1e-1)))
        share = jnp.log1p(-miss) / c["kda_lower_bound"]  # what the sigmoid has to give
        attn["A_log"] = jnp.log(rate)
        attn["dt_bias"] = jnp.log(share / (1.0 - share)) / rate[:, None]
    return params


def _with_lm_names(c: dict) -> dict:
    """The document with the published expert count under the name
    ``lm_params`` reads; the keys that place the expert layers are shared."""
    return c | {"published": c["published"]
                | {"n_routed_experts": c["published"]["num_experts"]}}


def bias_shapes(c: dict) -> dict:
    return lm_params.bias_shapes(_with_lm_names(c))


def make_biases(seed, c: dict) -> dict:
    """The router biases from ``seed``, as the all-MLA family's reference
    makes them (0.01 x a normal, one draw a sparse block)."""
    return lm_params.make_biases(seed, _with_lm_names(c))
