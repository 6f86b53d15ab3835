"""Device self time per run of the step program in the block-diffusion noise (scope bd_noise: the draw of a level a block and a uniform a token, the noisy ids, the loss's 1/t weights, the masked share), all phases (scope_reduce, by the table the driver names). None where the step has no such part."""

from benchmarks import scope_reduce


def read(record: dict):
    return scope_reduce.part_ms(record, "bd_noise") or None
