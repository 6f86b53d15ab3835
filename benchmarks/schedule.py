"""The training cells' input, from a seed: distinct uint8 images in batches
whose rows all differ (the generator of ``data/synthetic.synthetic_batches``,
with the benchmark's seed)."""

from __future__ import annotations

import numpy as np


def image_pool(seed: int, count: int, size: int) -> np.ndarray:
    """``count`` distinct uint8 images (count, size, size, 3)."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (count, size, size, 3), dtype=np.uint8)


def image_batches(seed: int, batch: int, size: int, distinct: int):
    """Endless cycle over ``distinct`` seeded uint8 batches."""
    pool = image_pool(seed, distinct * batch, size).reshape(
        distinct, batch, size, size, 3
    )
    i = 0
    while True:
        yield {"images": pool[i % distinct]}
        i += 1
