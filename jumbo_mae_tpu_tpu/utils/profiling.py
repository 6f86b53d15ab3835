"""Compat shim: profiler capture moved to ``jumbo_mae_tpu_tpu.obs.trace``,
which adds the host-side spans alongside the XLA device-trace helper that
lived here."""

from jumbo_mae_tpu_tpu.obs.trace import trace

__all__ = ["trace"]
