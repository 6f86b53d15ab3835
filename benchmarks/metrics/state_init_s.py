"""Seconds in the program's own span state_init (create_sharded_state: trace, compile or load, run), summed over this process."""

from benchmarks import scope_reduce


def read(record: dict):
    stats = scope_reduce.span_stats("state_init")
    return None if stats is None else stats[1]
