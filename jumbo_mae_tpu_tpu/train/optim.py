"""Optimizers, schedules, layer-wise LR decay.

Parity targets:

- optimizer set {adamw, lamb(modified), lars, sgd} with the reference's
  hyperparameter wiring (``/root/reference/src/pretraining.py:223-259``,
  ``/root/reference/src/finetuning.py:218-265``);
- modified LAMB: adam scaling → decoupled weight decay → trust ratio applied
  ONLY to weight-decayed (kernel) params (``/root/reference/src/utils.py:124-139``);
- weight-decay mask = parameters literally named "kernel";
- layer-wise LR decay via ``optax.multi_transform`` keyed by encoder depth
  (``/root/reference/src/utils.py:142-147``);
- warmup+cosine schedule (init 1e-6 → peak → end), MAE linear LR scaling
  peak = lr · global_batch/256;
- live LR exposed through ``optax.inject_hyperparams`` for logging.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from functools import partial
from typing import Any, Literal, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.tree_util import tree_map_with_path

from jumbo_mae_tpu_tpu.obs.trace import SPAN_OPTIMIZER_BUILD, spanned

OptimizerName = Literal["adamw", "lamb", "lars", "sgd"]
LrScaling = Literal["batch", "none"]


@dataclass(frozen=True)
class OptimConfig:
    name: OptimizerName = "adamw"
    learning_rate: float = 1.5e-4  # base LR (pre-scaling)
    lr_scaling: LrScaling = "batch"
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.05
    momentum: float = 0.9
    clip_grad: float = 0.0
    layer_decay: float = 1.0  # <1 enables layer-wise decay
    warmup_steps: int = 0
    training_steps: int = 1
    init_lr: float = 1e-6
    end_lr: float = 1e-5
    # dtype for the Adam first moment (optax mu_dtype). "bfloat16" halves the
    # first-moment HBM traffic in the (bandwidth-bound) optimizer update; the
    # second moment and params stay float32.
    mu_dtype: str | None = None
    # dtype for the Adam second moment. The EMA itself always computes in
    # float32 (only the *stored* moment is cast), but bf16's 8-bit mantissa
    # quantizes the stored EMA between steps — an explicit opt-in perf knob
    # for bandwidth-bound large models (PERF_ARCHIVE.md §ViT-H/14), never a silent
    # default.
    nu_dtype: str | None = None
    # Storage dtype for the *parameters* (forward/backward weight reads).
    # "bfloat16" halves weight HBM traffic — the lever that matters when the
    # same weights are re-read many times per step (the shared jumbo MLP, the
    # constant-size decoder). The optimizer keeps a float32 master copy in
    # its state and computes the update in float32; the bf16 params are an
    # exact cast of the master after every step, so optimizer numerics are
    # full-precision and only the forward sees rounded weights. Opt-in.
    param_dtype: str | None = None

    def peak_lr(self, global_batch_size: int) -> float:
        if self.lr_scaling == "batch":
            return self.learning_rate * global_batch_size / 256
        return self.learning_rate


def kernel_mask(params):
    """True for every param whose final path key is "kernel"."""
    return tree_map_with_path(lambda kp, _: kp[-1].key == "kernel", params)


def layer_index(path, _unused=None, *, num_layers: int) -> int:
    """Param path → encoder depth for layer-wise LR decay.

    Layout-specific to this framework's trees: the encoder lives under a
    top-level "model" (finetune) with blocks named ``block_i``. embed → 0,
    block_i → i+1, everything else (head, final norm, cls_tokens,
    jumbo_mlp) → num_layers.
    """
    keys = [getattr(k, "key", str(k)) for k in path]
    if keys and keys[0] == "model":
        if len(keys) > 1 and keys[1] == "embed":
            return 0
        if len(keys) > 1 and (m := re.fullmatch(r"block_(\d+)", keys[1])):
            return int(m.group(1)) + 1
    return num_layers


def make_schedule(cfg: OptimConfig, global_batch_size: int) -> optax.Schedule:
    return optax.warmup_cosine_decay_schedule(
        init_value=cfg.init_lr,
        peak_value=cfg.peak_lr(global_batch_size),
        warmup_steps=cfg.warmup_steps,
        decay_steps=cfg.training_steps,
        end_value=cfg.end_lr,
    )


def scale_by_adam_dtyped(
    b1, b2, eps, mu_dtype=None, nu_dtype=None
) -> optax.GradientTransformation:
    """``optax.scale_by_adam`` with independently castable stored moments.

    optax only exposes ``mu_dtype``; this adds ``nu_dtype`` with the same
    contract: the EMAs and the update are computed in float32 (cast up from
    whatever is stored), and only the moment written back to the optimizer
    state is cast down. With both dtypes ``None`` the math is identical to
    ``optax.scale_by_adam`` (covered by a bit-parity test)."""
    mu_dtype = jnp.dtype(mu_dtype) if mu_dtype else None
    nu_dtype = jnp.dtype(nu_dtype) if nu_dtype else None

    def init_fn(params):
        mu = jax.tree.map(
            lambda p: jnp.zeros_like(p, dtype=mu_dtype or p.dtype), params
        )
        nu = jax.tree.map(
            lambda p: jnp.zeros_like(p, dtype=nu_dtype or p.dtype), params
        )
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32), mu=mu, nu=nu
        )

    def update_fn(updates, state, params=None):
        del params
        count = optax.safe_increment(state.count)
        f32 = jnp.float32
        mu_f = jax.tree.map(
            lambda g, m: b1 * m.astype(f32) + (1 - b1) * g.astype(f32),
            updates,
            state.mu,
        )
        nu_f = jax.tree.map(
            lambda g, n: b2 * n.astype(f32)
            + (1 - b2) * jnp.square(g.astype(f32)),
            updates,
            state.nu,
        )
        c1 = 1 - jnp.asarray(b1, f32) ** count.astype(f32)
        c2 = 1 - jnp.asarray(b2, f32) ** count.astype(f32)
        out = jax.tree.map(
            lambda g, m, n: ((m / c1) / (jnp.sqrt(n / c2) + eps)).astype(
                g.dtype
            ),
            updates,
            mu_f,
            nu_f,
        )
        mu_s = jax.tree.map(
            lambda m: m.astype(mu_dtype) if mu_dtype else m, mu_f
        )
        nu_s = jax.tree.map(
            lambda n: n.astype(nu_dtype) if nu_dtype else n, nu_f
        )
        return out, optax.ScaleByAdamState(count=count, mu=mu_s, nu=nu_s)

    return optax.GradientTransformation(init_fn, update_fn)


class MasterWeightsState(NamedTuple):
    """float32 master copy of the params + the wrapped optimizer's state."""

    master: Any
    inner: Any


def with_master_weights(
    inner: optax.GradientTransformation, master_dtype=jnp.float32
) -> optax.GradientTransformation:
    """Run ``inner`` against a float32 master copy of low-precision params.

    The returned transformation's update is ``new_master - params`` computed
    in ``master_dtype``; ``optax.apply_updates`` promotes ``params`` to the
    update dtype before adding, so the stored low-precision params are an
    EXACT downcast of the master after every step (covered by a test). The
    sharding rules in ``parallel/sharding.py`` match on trailing path names,
    so the master tree inherits the params' FSDP/TP layout automatically.
    """
    master_dtype = jnp.dtype(master_dtype)

    def init_fn(params):
        master = jax.tree.map(lambda p: p.astype(master_dtype), params)
        return MasterWeightsState(master=master, inner=inner.init(master))

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("with_master_weights requires params")
        grads = jax.tree.map(lambda g: g.astype(master_dtype), updates)
        inner_updates, inner_state = inner.update(
            grads, state.inner, state.master
        )
        new_master = optax.apply_updates(state.master, inner_updates)
        out = jax.tree.map(
            lambda m, p: m - p.astype(master_dtype), new_master, params
        )
        return out, MasterWeightsState(master=new_master, inner=inner_state)

    return optax.GradientTransformation(init_fn, update_fn)


def _scale_by_adam(b1, b2, eps, mu_dtype=None, nu_dtype=None):
    """Stock optax unless ``nu_dtype`` forces the dtyped variant."""
    if nu_dtype:
        return scale_by_adam_dtyped(
            b1, b2, eps, mu_dtype=mu_dtype, nu_dtype=nu_dtype
        )
    return optax.scale_by_adam(b1=b1, b2=b2, eps=eps, mu_dtype=mu_dtype)


def modified_lamb(
    learning_rate, b1, b2, eps, weight_decay, mask, mu_dtype=None, nu_dtype=None
) -> optax.GradientTransformation:
    """LAMB with the trust ratio restricted to weight-decayed params."""
    return optax.chain(
        _scale_by_adam(b1, b2, eps, mu_dtype=mu_dtype, nu_dtype=nu_dtype),
        optax.add_decayed_weights(weight_decay=weight_decay, mask=mask),
        optax.masked(optax.scale_by_trust_ratio(), mask=mask),
        optax.scale_by_learning_rate(learning_rate),
    )


@spanned(SPAN_OPTIMIZER_BUILD)
def make_optimizer(
    cfg: OptimConfig,
    global_batch_size: int,
    *,
    num_layers: int | None = None,
) -> optax.GradientTransformation:
    """Build the full transformation chain, LR exposed in
    ``opt_state.hyperparams["learning_rate"]``."""

    # float32 whatever the params' dtype: left to optax, the injected LR
    # takes the params' dtype at init and the updates' dtype afterwards, and
    # with low-precision params the state's type then changes after the
    # first step — which the AOT-compiled step program (rightly) refuses
    @partial(optax.inject_hyperparams, hyperparam_dtype=jnp.float32)
    def build(learning_rate):
        wd_mask = kernel_mask
        if cfg.name == "adamw":
            # optax.adamw's own chain, with the dtyped core swapped in when
            # nu_dtype asks for it (optax exposes no nu_dtype).
            tx = optax.chain(
                _scale_by_adam(
                    cfg.b1,
                    cfg.b2,
                    cfg.eps,
                    mu_dtype=cfg.mu_dtype,
                    nu_dtype=cfg.nu_dtype,
                ),
                optax.add_decayed_weights(
                    weight_decay=cfg.weight_decay, mask=wd_mask
                ),
                optax.scale_by_learning_rate(learning_rate),
            )
        elif cfg.name == "lamb":
            tx = modified_lamb(
                learning_rate,
                cfg.b1,
                cfg.b2,
                cfg.eps,
                cfg.weight_decay,
                wd_mask,
                mu_dtype=cfg.mu_dtype,
                nu_dtype=cfg.nu_dtype,
            )
        elif cfg.name == "lars":
            tx = optax.lars(learning_rate, momentum=cfg.momentum)
        elif cfg.name == "sgd":
            tx = optax.sgd(learning_rate, momentum=cfg.momentum)
        else:
            raise ValueError(f"unknown optimizer {cfg.name!r}")

        if cfg.layer_decay < 1.0:
            if num_layers is None:
                raise ValueError("layer_decay requires num_layers")
            scales = {
                i: optax.scale(cfg.layer_decay ** (num_layers - i))
                for i in range(num_layers + 1)
            }
            label_fn = partial(
                tree_map_with_path, partial(layer_index, num_layers=num_layers)
            )
            tx = optax.chain(tx, optax.multi_transform(scales, label_fn))
        if cfg.clip_grad > 0:
            tx = optax.chain(optax.clip_by_global_norm(cfg.clip_grad), tx)
        if cfg.param_dtype and jnp.dtype(cfg.param_dtype) != jnp.float32:
            tx = with_master_weights(tx)
        return tx

    return build(make_schedule(cfg, global_batch_size))
