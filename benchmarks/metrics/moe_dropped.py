"""(token, expert) pairs on held experts that no row of the grouped product took, summed over the expert layers and the window's steps: the program's counter ``moe_dropped`` (0: nothing is dropped)."""


def read(record: dict):
    return record.get("moe", {}).get("dropped")
