"""Device self time per run of the step program in the language model's expert dispatch: sort and gather in, permute and weighted combine out, all phases, trunk and MTP block (scope_reduce, by the table the driver names)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, 'trunk_moe_dispatch', 'mtp_moe_dispatch')
