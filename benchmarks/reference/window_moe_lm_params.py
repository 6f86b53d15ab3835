"""Parameter shapes of the window / rope-free-full grouped-query sparse-expert
language model (``window_moe_lm_model.py``) on one chip's share, written from
the configuration file, and its seeded weights.

``config`` is a configuration file's document: ``config.json``'s own keys at
the top level (``SmallThinker-21BA3B-Instruct``'s names), with
``num_hidden_layers``, ``moe_num_primary_experts`` and ``vocab_size`` holding
what this chip holds and ``published`` the model's own counts; the two
per-layer lists (``sliding_window_layout``, ``rope_layout``) are the
published ones, whole, and this chip's layers are their first
``num_hidden_layers`` entries. The tree uses the program's checkpoint names so
that the harness can hand the same weights to the program. Weights come from
``params.make_params`` (0.02 x a normal truncated at two deviations, norm
scales about 1), but for the token embedding, which is ``embedding_init_std``
x that normal (``make_params``). The router has no bias, so the family has no non-gradient
state: ``bias_shapes`` is empty and ``make_biases`` gives None, which is what
the trainer's state holds where a model has no ``batch_stats``."""

from __future__ import annotations

from benchmarks.reference import params as ref_params
from benchmarks.reference.lm_params import _gated, _kernel, _norm  # the tree's leaf shapes


def is_window(c: dict, layer: int) -> bool:
    """Layer ``layer`` sees ``sliding_window_size`` keys where its entry of
    ``sliding_window_layout`` is 1, every earlier key where it is 0."""
    return bool(c["sliding_window_layout"][layer])


def has_rope(c: dict, layer: int) -> bool:
    return bool(c["rope_layout"][layer])


def _attention(c: dict) -> dict:
    d, e = c["hidden_size"], c["head_dim"]
    h, g = c["num_attention_heads"], c["num_key_value_heads"]
    return {"q": _kernel(d, h, e), "k": _kernel(d, g, e), "v": _kernel(d, g, e),
            "out": _kernel(h, e, d)}


def _block(c: dict) -> dict:
    d = c["hidden_size"]
    moe = _gated(d, c["moe_ffn_hidden_size"], lead=(c["moe_num_primary_experts"],))
    moe["router"] = _kernel(d, c["published"]["moe_num_primary_experts"])
    return {"ln1": _norm(d), "ln2": _norm(d), "attn": _attention(c), "moe": moe}


def shapes(c: dict) -> dict:
    d, rows = c["hidden_size"], c["vocab_size"]
    tree = {"embedding": (rows, d), "ln": _norm(d), "head": _kernel(d, rows)}
    for i in range(c["num_hidden_layers"]):
        tree[f"block_{i}"] = _block(c)
    return tree


def make_params(seed, c: dict) -> dict:
    """Float32 weights from ``seed`` (an int or a traced uint32); the
    embedding's rows are ``embedding_init_std`` x the truncated normal where
    every other matrix is 0.02 x it. The router reads the un-normalised
    stream: with rows of 0.02 the attention layers' output on what the tokens
    share (a gain of about one a layer on a stream of 0.02-0.1) outgrows the
    rows within three layers, every token then routes alike, and which held
    experts that one direction favours is the seed's draw (the file's
    ``assumed``, ``init``). Jit-compatible."""
    params = ref_params.make_params(seed, shapes(c))
    return params | {"embedding": params["embedding"] * (c["embedding_init_std"] / 0.02)}


def bias_shapes(c: dict) -> dict:
    return {}


def make_biases(seed, c: dict) -> None:
    return None
