"""Device self time per run of the step program in remat's recomputation of the forward inside the backward pass, from the trace joined with the program's scopes (scope_reduce)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.phase_ms(record, "recompute")
