"""Device self time per run of the step program in the linear-attention layers' projections: the six of the block input (q, k, v, the decay gate, beta, the output gate) and W_o, all phases (scope_reduce, by the table the driver names). None where the step has no such part."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, 'trunk_kda_proj', 'trunk_kda_out') or None
