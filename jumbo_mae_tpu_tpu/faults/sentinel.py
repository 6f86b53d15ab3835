"""Training divergence sentinel: skip bad steps on device, roll back on runs.

Two cooperating halves:

- **Device guard** (:func:`guarded_apply_gradients`, compiled into the train
  step by ``make_train_step(guard_nonfinite=True)``): an all-reduced
  ``isfinite(loss) & isfinite(grad_norm)`` flag — the mean over the
  globally-sharded batch IS the cross-replica value under GSPMD, so no
  explicit collective is needed — gates the optimizer update through a
  per-leaf select: the update is computed every step and
  ``where(finite, new, old)`` keeps or discards it inside the optimizer's
  own element-wise fusions. A conditional branch in its place cost the
  L/16 step 9.2 ms of copies and DMA waits inside the branches, every step
  (PERF.md §6, PR 25). A non-finite step
  passes the state through untouched (params, opt state, BatchNorm stats)
  except the step counter, which still advances so the data stream and LR
  schedule stay aligned. One program for both outcomes: **no recompile**,
  ever.

- **Host sentinel** (:class:`DivergenceSentinel`, driven by ``cli/train.py``
  at log boundaries — per-step host sync would serialize dispatch against
  device compute): counts consecutive bad steps (device-skipped or
  EMA-spike), and after ``patience`` of them in a row asks for a rollback to
  the last ``last/`` checkpoint (data cursor included). Skips, spikes and
  rollbacks are counted in the obs registry (``train_steps_skipped_total``,
  ``train_loss_spikes_total``, ``train_rollbacks_total``).

Why both: skipping protects the state from a *transient* bad batch; rollback
recovers from *persistent* badness (params already diverged, poisoned data
region) that skipping can't fix because the state itself is the problem.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import optax

from jumbo_mae_tpu_tpu.obs.metrics import get_registry
from jumbo_mae_tpu_tpu.obs.trace import (
    SCOPE_GRAD_NORM,
    SCOPE_GUARD,
    SCOPE_OPTIMIZER,
)


def _keep_if(finite, new, old):
    """Per leaf ``where(finite, new, old)``, the float32 leaves first and the
    narrower ones (a bf16 first moment, bf16 parameters beside their float32
    master) only once those are done.

    The order is for the compiler. XLA:TPU puts outputs of one element type
    into one loop fusion, so a leaf's update comes out as one fusion per
    dtype, and they share inputs: the float32 fusion (p', nu') reads the old
    bf16 mu that the bf16 fusion overwrites in place. Left unordered, the
    L/16 step ran the writer first for 454 of 577 leaves and kept a copy of
    every old mu from the start of the step (0.8 GB, PERF.md PR 25). Behind
    the barrier ``finite`` is the same flag; it only exists later.
    """
    leaves_new, treedef = jax.tree_util.tree_flatten(new)
    leaves_old = treedef.flatten_up_to(old)
    wide = [n.dtype.itemsize >= 4 for n in leaves_new]
    first = [
        jnp.where(finite, n, o)
        for n, o, w in zip(leaves_new, leaves_old, wide)
        if w
    ]
    if not all(wide):
        finite, first = jax.lax.optimization_barrier((finite, first))
    first = iter(first)
    return treedef.unflatten(
        next(first) if w else jnp.where(finite, n, o)
        for n, o, w in zip(leaves_new, leaves_old, wide)
    )


def guarded_apply_gradients(state, grads, loss):
    """Optimizer update gated on finiteness, inside the jitted step.

    Returns ``(new_state, grad_norm, finite)``. The update is always
    computed; on a non-finite ``loss`` or ``grad_norm`` a per-leaf
    ``where(finite, new, old)`` over ``params`` and ``opt_state`` throws it
    away, so the state comes back bit-unchanged except ``step + 1`` (the
    caller gates its BatchNorm-stats replace on ``finite`` the same way).
    The gradients themselves are never masked: a zeroed gradient would
    still apply weight decay and decay the moments.
    """
    with jax.named_scope(SCOPE_GRAD_NORM):
        grad_norm = optax.global_norm(grads)
    with jax.named_scope(SCOPE_GUARD):
        finite = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
    with jax.named_scope(SCOPE_OPTIMIZER):
        updated = state.apply_gradients(grads=grads)
    # apply_gradients touches step, params and opt_state only: the typed
    # ``rng`` key (no ``where`` on it) and ``batch_stats`` ride along
    with jax.named_scope(SCOPE_GUARD):
        params, opt_state = _keep_if(
            finite,
            (updated.params, updated.opt_state),
            (state.params, state.opt_state),
        )
    new_state = updated.replace(params=params, opt_state=opt_state)
    return new_state, grad_norm, finite


@dataclass(frozen=True)
class SentinelConfig:
    """Host-side divergence policy (RunConfig's ``sentinel_*`` knobs)."""

    patience: int = 3           # consecutive bad steps before rollback
    spike_factor: float = 10.0  # loss > factor x EMA counts as a bad step
    ema_beta: float = 0.98      # loss EMA decay
    max_rollbacks: int = 3      # give up (raise) after this many rollbacks


class DivergenceError(RuntimeError):
    """Raised when training diverges beyond what the sentinel can repair
    (no checkpoint to roll back to, or ``max_rollbacks`` exhausted)."""


class DivergenceSentinel:
    """Streaming bad-step detector fed with per-step host metrics.

    ``observe(step, metrics)`` is called once per fetched train step, in step
    order; it returns ``True`` when the consecutive-bad streak has reached
    ``patience`` and the caller should roll back. The EMA and streak reset
    after a rollback (``record_rollback``) — the restored stream re-earns its
    baseline.

    ``on_event`` (settable anytime) is the diagnostics tap: a callable
    ``(kind, payload_dict)`` invoked on every ``bad_step`` / ``loss_spike``
    / ``rollback`` verdict with the exact step index — the run journal and
    flight recorder subscribe here, so a rollback is *explainable* offline,
    not just counted. A raising callback is swallowed: diagnostics must
    never take down the recovery path they observe.
    """

    def __init__(self, cfg: SentinelConfig, registry=None, on_event=None):
        self.cfg = cfg
        self.on_event = on_event
        reg = registry if registry is not None else get_registry()
        self._m_skipped = reg.counter(
            "train_steps_skipped_total",
            "optimizer updates skipped on a non-finite loss/grad",
        )
        self._m_spikes = reg.counter(
            "train_loss_spikes_total",
            "steps whose loss exceeded spike_factor x EMA",
        )
        self._m_rollbacks = reg.counter(
            "train_rollbacks_total",
            "automatic rollbacks to the last checkpoint",
        )
        self.bad_streak = 0
        self.rollbacks = 0
        self.ema: float | None = None

    def _notify(self, kind: str, **payload) -> None:
        cb = self.on_event
        if cb is None:
            return
        try:
            cb(kind, payload)
        except Exception:  # noqa: BLE001 - diagnostics never break recovery
            pass

    def observe(self, step: int, metrics: dict) -> bool:
        """Digest one step's host-fetched metrics; True → roll back now."""
        skipped = float(metrics.get("skipped", 0.0)) >= 0.5
        loss = float(metrics.get("loss", math.nan))
        if skipped or not math.isfinite(loss):
            self._m_skipped.inc()
            self.bad_streak += 1
            self._notify(
                "bad_step",
                step=step,
                loss=loss,
                reason="device_skip" if skipped else "nonfinite_loss",
                streak=self.bad_streak,
            )
            return self.bad_streak >= self.cfg.patience
        if (
            self.ema is not None
            and self.cfg.spike_factor > 0
            and loss > self.cfg.spike_factor * max(self.ema, 1e-12)
        ):
            self._m_spikes.inc()
            self.bad_streak += 1
            self._notify(
                "loss_spike",
                step=step,
                loss=loss,
                ema=self.ema,
                streak=self.bad_streak,
            )
            # a spike still carries signal — let the EMA drift toward it so
            # a legitimate regime change stops counting as bad eventually
            self._update_ema(loss)
            return self.bad_streak >= self.cfg.patience
        self.bad_streak = 0
        self._update_ema(loss)
        return False

    def _update_ema(self, loss: float) -> None:
        b = self.cfg.ema_beta
        self.ema = loss if self.ema is None else b * self.ema + (1 - b) * loss

    def record_rollback(self) -> None:
        """Count a performed rollback and reset the streak/EMA baselines;
        raises :class:`DivergenceError` once the budget is exhausted."""
        self.rollbacks += 1
        self._m_rollbacks.inc()
        self.bad_streak = 0
        self.ema = None
        self._notify(
            "rollback",
            rollbacks=self.rollbacks,
            max_rollbacks=self.cfg.max_rollbacks,
        )
        if self.rollbacks > self.cfg.max_rollbacks:
            raise DivergenceError(
                f"training diverged {self.rollbacks} times "
                f"(sentinel_max_rollbacks={self.cfg.max_rollbacks}) — "
                "rollback is not converging; inspect the data/LR schedule"
            )
