"""Device self time per run of the step program in the language model's latent q/kv projections, their norms, the rotary embedding and the attention output projection, all phases, trunk and MTP block (scope_reduce, by the table the driver names)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, 'trunk_mla_latent', 'mtp_mla_latent', 'trunk_rope', 'mtp_rope', 'trunk_attn_out', 'mtp_attn_out')
