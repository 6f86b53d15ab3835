"""Backend-compile seconds during set-up, summed over jax.monitoring events (they overlap when the ladder compiles on threads)."""


def read(record: dict):
    return record.get("compile_s")
