"""Device self time per run of the step program in the gated short-convolution layers' two projections (W_in: the block input to the three gates' 3 x hidden columns, scope sconv_in; W_out, scope sconv_out), all phases (scope_reduce, by the table the driver names). None where the step has no such part."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, "trunk_sconv_proj") or None
