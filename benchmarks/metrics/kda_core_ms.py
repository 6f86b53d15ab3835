"""Device self time per run of the step program in the linear-attention cores, everything from (q, k, v, g, beta) to o whatever implements it, all phases (scope_reduce, by the table the driver names). None where the step has no such part."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, 'trunk_kda_core') or None
