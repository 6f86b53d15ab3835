"""Seconds in the program's own spans program_build:<step> (AOT lower + compile, or load from the cache), summed over this process."""

from benchmarks import scope_reduce


def read(record: dict):
    stats = scope_reduce.span_stats("program_build", prefix=True)
    return None if stats is None else stats[1]
