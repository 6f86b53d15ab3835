"""Device self time per run of the step program in the gated short-convolution layers' elementwise part (scope sconv_mix: B * x, the depth-wise causal filter's taps, C *, and their gradients), all phases (scope_reduce, by the table the driver names). None where the step has no such part."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, "trunk_sconv_mix") or None
