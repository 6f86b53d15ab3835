"""Device self time per run of the step program in the encoder's attention, core and projections, all phases, from the trace joined with the program's scopes (scope_reduce)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, "enc_attn_core", "enc_attn_proj")
