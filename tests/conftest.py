"""Test harness: run everything on a virtual 8-device CPU mesh.

The suite never needs a chip: ``JAX_PLATFORMS=cpu`` (the tier-1 command sets
it; the ``jax.config.update`` below covers a bare ``pytest``) plus eight
virtual host devices for the sharding paths. What only a chip can show —
Mosaic kernels executing, the trainer and the server at real widths — lives
in ``chip_smoke.py``; ``tests/test_chip_compile.py`` keeps the compile-only
part (AOT for a described v5e) and a CPU rehearsal of the script's phases.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

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


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs
