"""Device self time per run of the step program in the block-diffusion core (the causal kernels under the block-diffusion pattern over the clean and the noisy copy, and what feeds them inside the scope bd_core), all phases, all layers (scope_reduce, by the table the driver names). None where the step has no such part."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, "trunk_bd_core") or None
