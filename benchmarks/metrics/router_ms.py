"""Device self time per run of the step program in the language model's routers: float32 scores, top-k, weights, counts, the bias rule and the counters, all phases, trunk and MTP block (scope_reduce, by the table the driver names)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, 'trunk_router', 'mtp_router')
