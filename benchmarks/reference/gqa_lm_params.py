"""Parameter shapes of the grouped-query sparse-expert language model
(``gqa_lm_model.py``) on one chip's share, written from the configuration
file, its seeded weights and its seeded non-gradient router biases.

``config`` is a configuration file's document: ``config.json``'s own keys at
the top level (``Laguna-XS.2``'s names), with ``num_experts``, ``vocab_size``
and ``num_hidden_layers`` holding what this chip holds and ``published`` the
model's own counts; the three per-layer lists are the published ones, whole,
and this chip's layers are their first ``num_hidden_layers`` entries. The
tree uses the program's checkpoint names so that the harness can hand the
same weights to the program. Weights come from ``params.make_params`` (0.02 x
a normal truncated at two deviations, norm scales about 1)."""

from __future__ import annotations

from benchmarks.reference import lm_params
from benchmarks.reference import params as ref_params
from benchmarks.reference.lm_params import _gated, _kernel, _norm  # the tree's leaf shapes


def dense_layers(c: dict) -> int:
    """Leading layers whose ``mlp_layer_types`` entry is ``dense``; every
    layer after them has to be ``sparse``."""
    kinds = c["mlp_layer_types"][: c["num_hidden_layers"]]
    dense = next((i for i, kind in enumerate(kinds) if kind != "dense"), len(kinds))
    if set(kinds[dense:]) - {"sparse"}:
        raise ValueError(f"mlp_layer_types {kinds}: dense layers lead, sparse ones follow")
    return dense


def _attention(c: dict, layer: int) -> dict:
    d, e, g = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    h = c["num_attention_heads_per_layer"][layer]
    return {"q": _kernel(d, h, e), "k": _kernel(d, g, e), "v": _kernel(d, g, e),
            "gate": _kernel(d, h), "out": _kernel(h, e, d)}


def _block(c: dict, layer: int, sparse: bool) -> dict:
    d = c["hidden_size"]
    blk = {"ln1": _norm(d), "ln2": _norm(d), "attn": _attention(c, layer)}
    if not sparse:
        return blk | {"mlp": _gated(d, c["intermediate_size"])}
    moe = _gated(d, c["moe_intermediate_size"], lead=(c["num_experts"],))
    moe |= {"router": _kernel(d, c["published"]["num_experts"]),
            "shared": _gated(d, c["shared_expert_intermediate_size"])}
    return blk | {"moe": moe}


def shapes(c: dict) -> dict:
    d, rows = c["hidden_size"], c["vocab_size"]
    tree = {"embedding": (rows, d), "ln": _norm(d), "head": _kernel(d, rows)}
    dense = dense_layers(c)
    for i in range(c["num_hidden_layers"]):
        tree[f"block_{i}"] = _block(c, i, i >= dense)
    return tree


def make_params(seed, c: dict) -> dict:
    """Float32 weights from ``seed`` (an int or a traced uint32).
    Jit-compatible."""
    return ref_params.make_params(seed, shapes(c))


def _with_lm_names(c: dict) -> dict:
    """The document under the names ``lm_params`` places the expert layers
    by: the count of leading dense layers, the published expert count, no
    MTP module."""
    return c | {"first_k_dense_replace": dense_layers(c), "num_nextn_predict_layers": 0,
                "published": c["published"]
                | {"n_routed_experts": c["published"]["num_experts"]}}


def bias_shapes(c: dict) -> dict:
    return lm_params.bias_shapes(_with_lm_names(c))


def make_biases(seed, c: dict) -> dict:
    """The router biases from ``seed``, as the all-MLA family's reference
    makes them (0.01 x a normal, one draw a sparse block)."""
    return lm_params.make_biases(seed, _with_lm_names(c))
