"""Mean host milliseconds of one batch put in prefetch_to_device (the program's own span h2d), over every put of this process."""

from benchmarks import scope_reduce


def read(record: dict):
    stats = scope_reduce.span_stats("h2d")
    return None if stats is None else 1e3 * stats[1] / stats[0]
