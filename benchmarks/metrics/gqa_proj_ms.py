"""Device self time per run of the step program in the grouped-query layers' projections of the block input (q, k, v and the head-wise gate: scope gqa_proj), all phases (scope_reduce, by the table the driver names). None where the step has no such part."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, "trunk_gqa_proj") or None
