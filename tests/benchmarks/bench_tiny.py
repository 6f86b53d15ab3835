"""Cut a cell to a size the CPU holds: the test-sized encoder (2x64x4h, a
2x32x4h decoder, 64 px images) and a few steps."""

import copy


def tiny_cell(cell: dict) -> dict:
    cell = copy.deepcopy(cell)
    config, t = cell["config"], cell["traffic"]
    config["model"] |= {"image_size": 64, "enc_layers": 2, "enc_dim": 64, "enc_heads": 4,
                        "dec_layers": 2, "dec_dim": 32, "dec_heads": 4}
    prog = config["program"]
    prog["model"] |= {"preset": "vit_t16", "dec_layers": 2, "dec_dim": 32, "dec_heads": 4}
    prog["model"]["overrides"] = {**prog["model"]["overrides"], "image_size": 64}
    small = {"batch_per_chip": 8, "distinct_batches": 2, "fetch_every": 2, "trace_seconds": 0.3}
    t |= {k: v for k, v in small.items() if k in t}
    return cell
