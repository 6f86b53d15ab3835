import pytest

from benchmarks import flops


@pytest.fixture
def cpu_has_no_peak(monkeypatch):
    """The CPU has no entry in ``peaks.json`` and must not get one; the
    rehearsals give ``mfu`` a test peak instead."""
    monkeypatch.setattr(flops, "peak", lambda kind, key="bf16_flops": 1e12)
