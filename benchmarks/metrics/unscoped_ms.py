"""Device self time per run of the step program in instructions that carry no scope of the program's: what the scopes do not cover, from the trace joined with the program's scopes (scope_reduce)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, "unscoped")
