"""Matmul FLOPs the Jumbo-ViT MAE requires per image, from the sizes in a
configuration file's ``model`` section. A copy of the arithmetic of
``jumbo_mae_tpu_tpu/obs/mfu.py`` (2·m·n·k per matmul, elementwise work not
counted, backward = 2 × forward, recomputation not counted), kept here so
that no later change to the program can move the yardstick.
"""

from __future__ import annotations

import json
from pathlib import Path


def _attention(seq: int, dim: int) -> float:
    return 4 * 2 * seq * dim * dim + 2 * 2 * seq * seq * dim


def _mlp(seq: int, dim: int, hidden: int) -> float:
    return 2 * 2 * seq * dim * hidden


def encoder_forward(m: dict, *, masked: bool) -> float:
    n = (m["image_size"] // m["patch_size"]) ** 2
    patches = int(n * (1.0 - m["mask_ratio"])) if masked else n
    d, k = m["enc_dim"], m["num_cls_tokens"]
    per_layer = (
        _attention(patches + k, d)
        + _mlp(patches, d, 4 * d)
        + _mlp(1, k * d, 4 * k * d)
    )
    embed = 2 * n * d * (m["patch_size"] ** 2 * 3)  # every patch is embedded
    return m["enc_layers"] * per_layer + embed


def decoder_forward(m: dict) -> float:
    n = (m["image_size"] // m["patch_size"]) ** 2
    seq, d = n + m["num_cls_tokens"], m["dec_dim"]
    per_layer = _attention(seq, d) + _mlp(seq, d, 4 * d)
    return (
        m["dec_layers"] * per_layer
        + 2 * seq * m["enc_dim"] * d
        + 2 * n * d * (m["patch_size"] ** 2 * 3)
    )


def pretrain_step(m: dict) -> float:
    """Forward + backward of one image through masked encoder and decoder."""
    return 3.0 * (encoder_forward(m, masked=True) + decoder_forward(m))


def peak(device_kind: str, key: str = "bf16_flops") -> float:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    try:
        return float(table["device_kinds"][device_kind][key])
    except KeyError:
        raise ValueError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json: a "
            "utilization against a guessed peak is not a measurement"
        ) from None
