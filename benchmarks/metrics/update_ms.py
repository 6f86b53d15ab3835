"""Device self time per run of the step program in gradient scaling, gradient norm, the divergence guard and the optimizer inside it, from the trace joined with the program's scopes (scope_reduce)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.phase_ms(record, "update")
