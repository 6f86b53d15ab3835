"""Test harness: run everything on a virtual 8-device CPU mesh.

The suite never needs a chip: ``JAX_PLATFORMS=cpu`` (the tier-1 command sets
it; the ``jax.config.update`` below covers a bare ``pytest``) plus eight
virtual host devices for the sharding paths. What only a chip can show —
Mosaic kernels executing, the trainer and the server at real widths — lives
in ``chip_smoke.py``; ``tests/test_chip_compile.py`` keeps the compile-only
part (AOT for a described v5e) and a CPU rehearsal of the script's phases.
"""

import contextlib
import json
import os
import signal
from pathlib import Path

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
# The CPU backend compiles every program under test without its optimisation
# passes: the suite's time is compiles of programs that run once at toy
# shapes, and a whole run of tier-1 took 0.6 x the wall and the sum of its
# cases with it, no tolerance, golden file or compile count moved (PR 48; in
# the environment, so that the children the tests start inherit it). The
# ahead-of-time compiles for a described v5e read the same bytes and kernel
# counts with it: it is the host backend's level, not the TPU compiler's.
if "xla_backend_optimization_level" not in _flags:
    _flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = _flags.strip()

# Both caches of compiled programs are off under test, here and in every
# child the tests start: compile-count contracts (compiles-exactly-once,
# warmup totals, the retrace sentinel) need every compile to happen, and no
# test may depend on what an earlier run left on disk. Tests of the caches
# themselves turn them on against a tmp dir.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.setdefault("JUMBO_WARMCACHE", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import sys  # noqa: E402

sys.path.insert(0, _repo_root)

import pytest  # noqa: E402

# ---------------------------------------------------------- the files' order
UNKNOWN_FILE_SECONDS = 60.0  # a file the table lacks: neither last nor first
WORKERS = 6  # the tier-1 command's ``-n 6``


def file_seconds() -> dict[str, float]:
    """Test file -> seconds of its cases in one whole run's junit: the table
    ``python tools/file_seconds.py <junit.xml>`` writes, and no run of the tests."""
    return json.loads((Path(__file__).parent / "file_seconds.json").read_text())


def order_files(files: list[str], seconds: dict[str, float]) -> list[str]:
    """The six longest files, then the six lightest, then the rest longest
    first: a pure function of the names and the table (every worker collects
    for itself, and xdist aborts if two disagree)."""
    ranked = sorted(files, key=lambda f: (-seconds.get(f, UNKNOWN_FILE_SECONDS), f))
    first, rest = ranked[:WORKERS], ranked[WORKERS:]
    return first + rest[-WORKERS:] + rest[:-WORKERS]


def order_items(ids: list[str], seconds: dict[str, float]) -> list[int]:
    """Positions of ``ids`` in the order they run: whole files as
    ``order_files`` says, a file's cases together and as collected."""
    by_file: dict[str, list[int]] = {}
    for i, nodeid in enumerate(ids):
        by_file.setdefault(nodeid.split("::", 1)[0], []).append(i)
    return [i for f in order_files(list(by_file), seconds) for i in by_file[f]]


# ``--dist loadfile`` hands a worker its next file when two cases of its
# current one are left, and xdist's own order is most cases first: here the
# heaviest files have the fewest, so they ran last and alone (PR 48's whole
# run's junit replayed, a worker taking the next file as it ends one: 940 s
# that way, 811 s longest first, 809 s evenly shared; 1305, 1179 and 1153 s
# at PR 42). The six light files are what a first file of one or two cases
# gets queued behind it before anything runs.
def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):  # absent under -p no:xdist
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    items[:] = [items[i] for i in order_items([item.nodeid for item in items], file_seconds())]


# ------------------------------------------------------- a case's own limit
# Under half the tier-1 command's clock, and about twice what the longest
# case has read under the six workers' load: a case that waits (a future, a
# lock, a child, a replica) fails alone and by name, where the command's own
# ``timeout`` would cut the run and count only how far it got. A case that
# needs more is ``slow``.
CASE_LIMIT_S = 600.0


@contextlib.contextmanager
def case_limit(nodeid: str):
    """Fail the case ``nodeid`` once it has run ``CASE_LIMIT_S`` seconds. The
    alarm's handler raises when the interpreter next runs on the main thread,
    where pytest and every xdist worker run their cases; the timer is off and
    the handler the former one when the block is left."""
    limit = CASE_LIMIT_S

    def fired(signum, frame):
        pytest.fail(f"{nodeid} ran past its limit of {limit:g} s", pytrace=False)

    former = signal.signal(signal.SIGALRM, fired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, former)


@pytest.fixture(autouse=True)
def _case_limit(request):
    with case_limit(request.node.nodeid):
        yield


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs
