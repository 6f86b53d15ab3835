"""Parameter shapes of the block-diffusion grouped-query sparse-expert
language model (``blockdiff_lm_model.py``) on one chip's share, written from
the configuration file, and its seeded weights.

``config`` is a configuration file's document: ``config.json``'s own keys at
the top level (``SDAR-30B-A3B-Chat``'s names, ``model_type: sdar_moe``), with
``num_hidden_layers``, ``num_experts`` and ``vocab_size`` holding what this
chip holds and ``published`` the model's own counts. The tree uses the
program's checkpoint names so that the harness can hand the same weights to
the program. Weights come from ``params.make_params`` (0.02 x a normal
truncated at two deviations, norm scales about 1), but for the q/k norms'
scales, which are ``qk_norm_init_scale`` x that (``make_params``). The
router has no bias, so the family has no non-gradient state: ``bias_shapes``
is empty and ``make_biases`` gives None, which is what the trainer's state
holds where a model has no ``batch_stats``. The objective has no parameter of
its own: the mask id is a row of the embedding like any other."""

from __future__ import annotations

from benchmarks.reference import params as ref_params
from benchmarks.reference.lm_params import _gated, _kernel, _norm  # the tree's leaf shapes


def mask_id(c: dict) -> int:
    """The id a masked token takes: the last vocabulary row held (assumed)."""
    first, rows = c["vocab_rows"]
    return first + rows - 1


def _attention(c: dict) -> dict:
    d, e = c["hidden_size"], c["head_dim"]
    h, g = c["num_attention_heads"], c["num_key_value_heads"]
    return {"q": _kernel(d, h, e), "k": _kernel(d, g, e), "v": _kernel(d, g, e),
            "q_norm": _norm(e), "k_norm": _norm(e), "out": _kernel(h, e, d)}


def _block(c: dict) -> dict:
    d = c["hidden_size"]
    moe = _gated(d, c["moe_intermediate_size"], lead=(c["num_experts"],))
    moe["router"] = _kernel(d, c["published"]["num_experts"])
    return {"ln1": _norm(d), "ln2": _norm(d), "attn": _attention(c), "moe": moe}


def shapes(c: dict) -> dict:
    d, rows = c["hidden_size"], c["vocab_size"]
    tree = {"embedding": (rows, d), "ln": _norm(d), "head": _kernel(d, rows)}
    for i in range(c["num_hidden_layers"]):
        tree[f"block_{i}"] = _block(c)
    return tree


def make_params(seed, c: dict) -> dict:
    """Float32 weights from ``seed`` (an int or a traced uint32); the q/k
    norms' scales are ``qk_norm_init_scale`` x the about-1 every other norm's
    is. At 1 the seeded scores have a deviation of 1, a softmax over
    thousands of keys is an average of them all, every query's attention
    output is the same running mean, that mean grows a layer, and from the
    second layer on every token routes to the same experts: which of them this
    chip holds is then the seed's draw, and the masked rows, a quarter of all
    and identical by construction, flip between experts all at once at a near
    tie (the file's ``assumed``, ``init``: measured). At 2.5 a query sees a
    few keys, as a trained model's does. Jit-compatible."""
    params = ref_params.make_params(seed, shapes(c))
    for name in [n for n in params if n.startswith("block_")]:
        for norm in ("q_norm", "k_norm"):
            scale = params[name]["attn"][norm]["scale"]
            params[name]["attn"][norm] = {"scale": scale * c["qk_norm_init_scale"]}
    return params


def bias_shapes(c: dict) -> dict:
    return {}


def make_biases(seed, c: dict) -> None:
    return None
