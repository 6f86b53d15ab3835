"""What the drivers share: building the program's configuration from a
configuration file, and comparing a tree of the program with the reference's."""

from __future__ import annotations

import copy

import jax
import numpy as np

from benchmarks.reference import params as ref_params


def program_config(config: dict, *, batch: int | None = None):
    """The program's ``TrainConfig`` for a configuration file: its
    ``program`` section is the recipe's document, as shipped. The program's
    own seeds stay 0: it bakes them into its init program as constants, and
    the benchmark's seed has to leave every compiled program the same."""
    from jumbo_mae_tpu_tpu.config import config_from_dict

    doc = copy.deepcopy(config["program"])
    run = doc.setdefault("run", {})
    run |= {"seed": 0, "init_seed": 0, "synthetic_data": True}
    if batch is not None:
        run |= {"train_batch_size": batch, "valid_batch_size": batch}
    doc.setdefault("data", {})["image_size"] = config["model"]["image_size"]
    return config_from_dict(doc)


def seed32(seed: int) -> np.uint32:
    return np.uint32(int(seed) % 2**32)


def require_same_tree(program_tree, shapes: dict, what: str) -> None:
    """The benchmark hands its own weights to the program by name: refuse to
    run when the program's tree is not the one the reference describes."""
    have = ref_params.flat_shapes(
        jax.tree_util.tree_map(lambda leaf: tuple(leaf.shape), program_tree)
    )
    want = ref_params.flat_shapes(shapes)
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise RuntimeError(f"{what}: parameter tree differs from the reference's: {odd}")
