"""Device self time per run of the step program in the language model's causal attention core (the flash kernels and what feeds them inside the scope), all phases, trunk and MTP block (scope_reduce, by the table the driver names)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, 'trunk_attn_core', 'mtp_attn_core')
