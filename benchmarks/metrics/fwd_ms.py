"""Device self time per run of the step program in the forward pass (under jvp, not transposed, not rematerialised), from the trace joined with the program's scopes (scope_reduce)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.phase_ms(record, "fwd")
