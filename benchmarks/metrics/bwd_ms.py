"""Device self time per run of the step program in the backward pass (transposed jvp), recomputation apart, from the trace joined with the program's scopes (scope_reduce)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.phase_ms(record, "bwd")
