"""Parameter shapes of the latent-attention sparse-expert language model
(``lm_model.py``) on one chip's share, written from the configuration file,
and the seeded non-gradient router biases.

``config`` is a configuration file's document: ``config.json``'s own keys at
the top level, with ``n_routed_experts``, ``vocab_size`` and
``num_hidden_layers`` holding what this chip holds and ``published`` the
model's own counts. The tree uses the program's checkpoint names
(block_i/attn/q_a/kernel, ...) so that the harness can hand the same weights
to the program. Weights come from ``params.make_params`` (0.02 x a normal
truncated at two deviations, norm scales about 1)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _kernel(*shape):
    return {"kernel": tuple(shape)}


def _norm(d):
    return {"scale": (d,)}


def _attention(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return {
        "q_a": _kernel(d, c["q_lora_rank"]), "q_norm": _norm(c["q_lora_rank"]),
        "q_b": _kernel(c["q_lora_rank"], h, qk),
        "kv_a": _kernel(d, c["kv_lora_rank"] + c["qk_rope_head_dim"]),
        "kv_norm": _norm(c["kv_lora_rank"]),
        "kv_b": _kernel(c["kv_lora_rank"], h, c["qk_nope_head_dim"] + c["v_head_dim"]),
        "out": _kernel(h, c["v_head_dim"], d),
    }


def _gated(d, hidden, lead=()):
    return {"gate": _kernel(*lead, d, hidden), "up": _kernel(*lead, d, hidden),
            "down": _kernel(*lead, hidden, d)}


def _block(c: dict, sparse: bool) -> dict:
    d = c["hidden_size"]
    blk = {"ln1": _norm(d), "attn": _attention(c), "ln2": _norm(d)}
    if not sparse:
        return blk | {"mlp": _gated(d, c["intermediate_size"])}
    w = c["moe_intermediate_size"]
    moe = _gated(d, w, lead=(c["n_routed_experts"],))
    moe |= {"router": _kernel(d, c["published"]["n_routed_experts"]),
            "shared": _gated(d, c["n_shared_experts"] * w)}
    return blk | {"moe": moe}


def sparse_blocks(c: dict) -> list[str]:
    """Names of the blocks that hold an expert layer, in model order."""
    names = [f"block_{i}" for i in range(c["first_k_dense_replace"], c["num_hidden_layers"])]
    return names + ["mtp_block"] * c["num_nextn_predict_layers"]


def lm_shapes(c: dict) -> dict:
    d, rows = c["hidden_size"], c["vocab_size"]
    tree = {"embedding": (rows, d), "ln": _norm(d), "head": _kernel(d, rows)}
    for i in range(c["num_hidden_layers"]):
        tree[f"block_{i}"] = _block(c, i >= c["first_k_dense_replace"])
    if c["num_nextn_predict_layers"]:
        tree |= {"mtp_embed_norm": _norm(d), "mtp_hidden_norm": _norm(d),
                 "mtp_merge": _kernel(2 * d, d), "mtp_block": _block(c, True)}
    return tree


def bias_shapes(c: dict) -> dict:
    e = c["published"]["n_routed_experts"]
    return {name: {"moe": {"router_bias": (e,)}} for name in sparse_blocks(c)}


def make_biases(seed, c: dict) -> dict:
    """The router biases from ``seed`` (an int or a traced uint32): 0.01 x a
    normal, so that none starts at zero. Jit-compatible."""
    base = jax.random.fold_in(jax.random.key(seed), 0x62696173)  # "bias"
    e = c["published"]["n_routed_experts"]
    return {name: {"moe": {"router_bias": 0.01 * jax.random.normal(
        jax.random.fold_in(base, i), (e,), jnp.float32)}}
        for i, name in enumerate(sparse_blocks(c))}
