"""Parameter shapes of the short-convolution / grouped-query sparse-expert
language model (``conv_moe_lm_model.py``) on one chip's share, written from
the configuration file, its seeded weights and its seeded non-gradient router
biases.

``config`` is a configuration file's document: ``config.json``'s own keys at
the top level (``LFM2-24B-A2B``'s names), with ``num_hidden_layers``,
``num_dense_layers``, ``num_experts`` and ``vocab_size`` holding what this
chip holds and ``published`` the model's own counts; ``layer_types`` is the
published list, whole, and this chip's layers are its entries ``first_layer
.. first_layer + num_hidden_layers`` (``kinds``). The tree uses the program's
checkpoint names so that the harness can hand the same weights to the
program; the embedding is the head too (tied), so the tree has no ``head``.
Weights come from ``params.make_params`` (0.02 x a normal truncated at two
deviations — the filters' taps too — norm scales about 1)."""

from __future__ import annotations

from benchmarks.reference import lm_params
from benchmarks.reference import params as ref_params
from benchmarks.reference.lm_params import _gated, _kernel, _norm  # the tree's leaf shapes


def kinds(c: dict) -> list[str]:
    """The mixer kind of each layer held: ``conv`` or ``full_attention``."""
    first = c["first_layer"]
    held = c["layer_types"][first : first + c["num_hidden_layers"]]
    if set(held) - {"conv", "full_attention"} or len(held) != c["num_hidden_layers"]:
        raise ValueError(f"layer_types {held}: conv or full_attention, one a layer held")
    return held


def head_dim(c: dict) -> int:
    """The config has no ``head_dim`` key: hidden_size / num_attention_heads."""
    return c["hidden_size"] // c["num_attention_heads"]


def _short_conv(c: dict) -> dict:
    d = c["hidden_size"]
    return {"in_proj": _kernel(d, 3 * d), "conv": _kernel(c["conv_L_cache"], d),
            "out_proj": _kernel(d, d)}


def _attention(c: dict) -> dict:
    d, e = c["hidden_size"], head_dim(c)
    h, g = c["num_attention_heads"], c["num_key_value_heads"]
    return {"q": _kernel(d, h, e), "k": _kernel(d, g, e), "v": _kernel(d, g, e),
            "q_norm": _norm(e), "k_norm": _norm(e), "out": _kernel(h, e, d)}


def _block(c: dict, kind: str, sparse: bool) -> dict:
    d = c["hidden_size"]
    blk = {"ln1": _norm(d), "ln2": _norm(d)}
    blk |= {"conv": _short_conv(c)} if kind == "conv" else {"attn": _attention(c)}
    if not sparse:
        return blk | {"mlp": _gated(d, c["intermediate_size"])}
    moe = _gated(d, c["moe_intermediate_size"], lead=(c["num_experts"],))
    return blk | {"moe": moe | {"router": _kernel(d, c["published"]["num_experts"])}}


def shapes(c: dict) -> dict:
    d, rows = c["hidden_size"], c["vocab_size"]
    tree = {"embedding": (rows, d), "ln": _norm(d)}  # tied: the embedding is the head
    for i, kind in enumerate(kinds(c)):
        tree[f"block_{i}"] = _block(c, kind, i >= c["num_dense_layers"])
    return tree


def make_params(seed, c: dict) -> dict:
    """Float32 weights from ``seed`` (an int or a traced uint32).
    Jit-compatible."""
    return ref_params.make_params(seed, shapes(c))


def _with_lm_names(c: dict) -> dict:
    """The document under the names ``lm_params`` places the expert layers
    by: the count of leading dense layers, the published expert count, no
    MTP module."""
    return c | {"first_k_dense_replace": c["num_dense_layers"], "num_nextn_predict_layers": 0,
                "published": c["published"]
                | {"n_routed_experts": c["published"]["num_experts"]}}


def bias_shapes(c: dict) -> dict:
    return lm_params.bias_shapes(_with_lm_names(c))


def make_biases(seed, c: dict) -> dict:
    """The router biases from ``seed``, as the all-MLA family's reference
    makes them (0.01 x a normal, one draw a sparse block)."""
    return lm_params.make_biases(seed, _with_lm_names(c))
