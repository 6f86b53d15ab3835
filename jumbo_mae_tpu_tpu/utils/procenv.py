"""Process environment: where compiled programs are cached, and the
environment of child processes.

A chip belongs to one process at a time: a parent that has touched a JAX
backend holds it, and a child that needs it then fails or hangs. Every child
this package starts (data workers, the multichip dry run, test subprocesses)
therefore runs on the CPU backend — :func:`cpu_subprocess_env` sets
``JAX_PLATFORMS=cpu`` and nothing more is stripped. Anything that needs the
chip runs inside the one process that owns it.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]


def compile_cache_dir() -> str:
    """Directory of JAX's persistent compilation cache:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
    A cache that moves never hits, so the path carries no host hash, pid or
    time."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO_ROOT / ".jax_cache"
    )


def enable_compile_cache() -> str:
    """Point this process's persistent compilation cache at
    :func:`compile_cache_dir` — the one place the program sets that path.
    Entry points (``cli.train``, ``cli.predict``, ``cli.batch``,
    ``chip_smoke.py``) call it first thing. Entries other processes wrote
    there are never deleted: with the installed JAX an unreadable entry is a
    warning and a recompile, not a crash. Returns the directory."""
    import jax

    from jumbo_mae_tpu_tpu.obs.trace import SPAN_COMPILE_CACHE_SETUP, span

    with span(SPAN_COMPILE_CACHE_SETUP):
        path = compile_cache_dir()
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def default_warmcache_dir() -> str | None:
    """Default root of the serving warm-start executable cache
    (``infer/warmcache.py``; the engine's ``warm_cache=True`` resolves
    here): ``warmcache/`` under :func:`compile_cache_dir`.
    ``JUMBO_WARMCACHE=0`` disables the default (the test suite sets it:
    compile-count assertions need every compile to happen); an explicit
    ``warm_cache=<path>`` on the engine ignores the switch."""
    if os.environ.get("JUMBO_WARMCACHE", "1") == "0":
        return None
    return os.path.join(compile_cache_dir(), "warmcache")


def cpu_subprocess_env(
    n_devices: int | None = None, *, base: dict | None = None
) -> dict:
    """Env for a child process that must stay on the CPU backend:
    ``JAX_PLATFORMS=cpu`` and, with ``n_devices``, that many virtual devices
    (``--xla_force_host_platform_device_count``, replacing any inherited
    value)."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices:
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env
