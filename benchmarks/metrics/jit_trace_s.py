"""Seconds of set-up in which JAX traced Python to a jaxpr: the union of the span log's ``jit_trace:*`` records (``/jax/core/compile/jaxpr_trace_duration``, nested traces counted once) that lie in this run's set-up, all threads. None where the program keeps no span log."""

from benchmarks import span_log


def read(record: dict):
    return span_log.kind_union_s(record, "jit_trace")
