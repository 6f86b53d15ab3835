"""Device self time per run of the step program in what lies between the linear-attention layers' projections and their core: the causal convolutions and SiLU, the L2 norms, the decay and beta gates, the output norm and the head-wise gate, all phases (scope_reduce, by the table the driver names). None where the step has no such part."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, 'trunk_kda_conv', 'trunk_kda_gate') or None
