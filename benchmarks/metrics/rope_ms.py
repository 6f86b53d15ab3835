"""Device self time per run of the step program in the rotary embedding of q and k (scope rope: the tables and the turn itself), all phases, trunk and MTP block (scope_reduce, by the table the driver names). In the latent-attention cells the same part is also inside mla_latent_ms. None where the step has no such part."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, "trunk_rope", "mtp_rope") or None
