"""AdamW with linear warm-up and cosine decay, as published: moments in
float32, decoupled weight decay on the matrices (leaves named ``kernel``)."""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def for_batch(o: dict, batch: int) -> dict:
    """The ``optim`` section of a configuration file with the quantities that
    follow from the global batch: epochs become steps, and the peak learning
    rate is the base rate x batch / 256 (the recipe's linear scaling)."""
    steps = lambda epochs: int(o["dataset_size"] * epochs / batch)
    return {**o, "peak_lr": o["base_lr"] * batch / 256,
            "warmup_steps": steps(o["warmup_epochs"]), "training_steps": steps(o["epochs"])}


def learning_rate(count: int, o: dict) -> float:
    """``o`` is an ``optim`` section completed by :func:`for_batch`."""
    peak = o["peak_lr"]
    if count < o["warmup_steps"]:
        return o["init_lr"] + (peak - o["init_lr"]) * count / o["warmup_steps"]
    span = max(o["training_steps"] - o["warmup_steps"], 1)
    frac = min((count - o["warmup_steps"]) / span, 1.0)
    return o["end_lr"] + (peak - o["end_lr"]) * 0.5 * (1.0 + math.cos(math.pi * frac))


def adamw_init(params):
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros(), "v": zeros(), "count": 0}


@partial(jax.jit, donate_argnums=(0, 2, 3))
def _update(params, grads, m, v, lr, c1, c2, b1, b2, eps, weight_decay):
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)

    def leaf(path, p, m, v):
        step = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if path[-1].key == "kernel":
            step = step + weight_decay * p
        return p - lr * step

    return jax.tree_util.tree_map_with_path(leaf, params, m, v), m, v


def adamw_step(params, grads, state: dict, o: dict):
    """One update; ``params`` and the moments are consumed."""
    t = state["count"] + 1
    params, m, v = _update(
        params, grads, state["m"], state["v"], learning_rate(state["count"], o),
        1 - o["b1"] ** t, 1 - o["b2"] ** t, o["b1"], o["b2"], o["eps"], o["weight_decay"],
    )
    return params, {"m": m, "v": v, "count": t}
