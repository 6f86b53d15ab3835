"""Device self time per run of the step program in the decoder's attention, core and projections, all phases, from the trace joined with the program's scopes (scope_reduce)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, "dec_attn_core", "dec_attn_proj")
