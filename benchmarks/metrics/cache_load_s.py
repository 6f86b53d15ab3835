"""Seconds of set-up spent reading executables back from the persistent compile cache: the sum of the span log's ``cache_load`` records (``/jax/compilation_cache/cache_retrieval_time_sec``) that lie in this run's set-up. JAX times a load inside the compile event that asked for it, so ``compile_s - cache_load_s`` is what really compiled. None where the program keeps no span log."""

from benchmarks import span_log


def read(record: dict):
    records = span_log.setup_records(record)
    if records is None:
        return None
    return sum(r["end"] - r["start"] for r in records if r["name"] == "cache_load")
