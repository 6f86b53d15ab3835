"""Device self time per run of the step program in the sliding-window layers' causal core (the band kernels and what feeds them inside the scope swa_core), all phases, all such layers (scope_reduce, by the table the driver names). None where the step has no such part."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, "trunk_swa_core") or None
