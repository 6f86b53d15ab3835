"""Device self time per run of the step program in the language model's multi-token-prediction module: the merge and every part of its block, all phases (scope_reduce, by the table the driver names)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, 'mtp_merge', 'mtp_attn_core', 'mtp_rope', 'mtp_mla_latent', 'mtp_attn_out', 'mtp_experts', 'mtp_moe_dispatch', 'mtp_router', 'mtp_shared_expert', 'mtp_dense_mlp', 'mtp_other')
