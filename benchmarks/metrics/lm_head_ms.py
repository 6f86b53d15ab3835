"""Device self time per run of the step program in the language model's two heads: final norm, logits over the held rows, cross-entropy, all phases (scope_reduce, by the table the driver names)."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, 'lm_head')
