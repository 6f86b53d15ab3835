"""Seconds of set-up in which JAX lowered a jaxpr to an MLIR module: the union of the span log's ``jit_lower:*`` records (``/jax/core/compile/jaxpr_to_mlir_module_duration``) that lie in this run's set-up, all threads. None where the program keeps no span log."""

from benchmarks import span_log


def read(record: dict):
    return span_log.kind_union_s(record, "jit_lower")
