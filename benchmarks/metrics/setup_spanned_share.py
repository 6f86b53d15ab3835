"""Share of ``setup_s`` that lies under any record of the program's span log on the main thread (its own spans and JAX's trace / lower / compile events, as a union): how much of set-up the program's tracing sees at all. The rest is imports, the backend's start, the benchmark's own host work and programs that run without compiling. None where the program keeps no span log."""

from benchmarks import span_log


def read(record: dict):
    seen = span_log.main_thread_union_s(record)
    return None if seen is None else 100.0 * seen / record["setup_s"]
