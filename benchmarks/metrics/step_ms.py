"""Median over the fetch windows of (fetch-to-fetch time / steps between fetches)."""

import numpy as np


def read(record: dict):
    if not record.get("step_s"):
        return None
    return float(np.median(record["step_s"]) * 1e3)
