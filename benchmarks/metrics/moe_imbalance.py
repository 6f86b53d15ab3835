"""Largest rows-of-one-held-expert over the mean of the held experts, the worst expert layer of a step, mean over the window's steps: the program's counter ``moe_imbalance``, fetched after the window."""


def read(record: dict):
    return record.get("moe", {}).get("imbalance")
