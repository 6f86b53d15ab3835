"""jit + GSPMD train/eval steps and sharded state creation.

This is the runtime the reference delegated to ``jax.pmap``
(``/root/reference/src/pretraining.py:125-167``,
``/root/reference/src/finetuning.py:109-165``), rebuilt mesh-native:

- ONE ``jax.jit`` program per step over an explicit mesh; the batch is
  sharded over (data, fsdp), parameters/optimizer state over fsdp (ZeRO-3
  rule in ``parallel/sharding.py``). GSPMD inserts the gradient
  reduce-scatter/all-gather the reference expressed as ``lax.pmean``.
- Gradient accumulation is a ``lax.scan`` over a leading micro-batch axis
  *inside* the step — one device dispatch per optimizer update — instead of
  the reference's host-visible micro-step counter + ``lax.cond`` state
  machine.
- Metrics come back as global scalars (the mean over a globally-sharded
  batch IS the cross-replica mean; no explicit collective needed).
- Eval aggregates per-sample metrics against an explicit ``valid`` mask,
  fixing the reference's mis-normalized pretrain val loss
  (``/root/reference/src/main_pretrain.py:43-45``, SURVEY defect #2) and its
  count-the-padding ``num_samples`` quirk.

State creation initializes parameters *already sharded* via
``jax.jit(init, out_shardings=...)`` — no host-resident full copy, which is
what makes ViT-H-scale FSDP init feasible on small hosts.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from jumbo_mae_tpu_tpu.faults.sentinel import guarded_apply_gradients
from jumbo_mae_tpu_tpu.obs.modelstats import group_stats
from jumbo_mae_tpu_tpu.obs.trace import (
    SCOPE_GRAD_ACCUM,
    SCOPE_GRAD_SCALE,
    SCOPE_GUARD,
    SCOPE_METRICS,
    SCOPE_OPTIMIZER,
    SCOPE_RNG,
    SPAN_PROGRAM_BUILD,
    SPAN_STATE_INIT,
    SPAN_STATE_SHAPES,
    note_program,
    span,
)
from jumbo_mae_tpu_tpu.parallel.sharding import (
    batch_sharding,
    infer_state_sharding,
)
from jumbo_mae_tpu_tpu.train.modes import MODES, StepMode, model_inputs
from jumbo_mae_tpu_tpu.train.state import (
    EVAL_DOMAIN,
    STREAMS,
    TrainState,
    make_base_rng,
)

# Folded into the "dropout" stream before it enters the gpipe key
# derivation ("pipe" in ASCII) — keeps pipeline keys out of any integer
# range flax's path-folding could produce for the sequential blocks.
PIPE_RNG_DOMAIN = 0x70697065


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _tree_scale(a, s):
    return jax.tree_util.tree_map(lambda x: x * s, a)


def _batch_key(batch: dict) -> tuple:
    """What an AOT-compiled step is keyed by: the batch's leaf shapes."""
    return tuple(
        (k, tuple(v.shape), str(getattr(v, "dtype", type(v))))
        for k, v in sorted(batch.items())
    )


def create_sharded_state(
    module,
    tx: optax.GradientTransformation,
    example_batch: dict,
    mesh: Mesh,
    *,
    mode: StepMode,
    init_seed: int = 0,
    rng_seed: int = 0,
    min_shard_size: int = 2**16,
    param_dtype: str | None = None,
) -> tuple[TrainState, Any]:
    """Initialize a TrainState directly into its mesh sharding.

    Returns ``(state, state_sharding)``; the sharding tree is reused by the
    step factories and the checkpoint manager.

    ``param_dtype`` casts the stored params after init (e.g. "bfloat16" for
    half weight-read HBM traffic); pair it with ``optim.param_dtype`` so the
    optimizer keeps a float32 master copy (``with_master_weights``).
    """
    inputs = model_inputs(mode, example_batch)
    init_rngs = {
        "params": jax.random.key(init_seed),
        **{
            name: jax.random.fold_in(jax.random.key(init_seed), sid + 1)
            for name, sid in STREAMS.items()
        },
    }

    def init_fn():
        variables = module.init(init_rngs, *inputs)
        params = variables["params"]
        if param_dtype is not None:
            dt = jnp.dtype(param_dtype)
            params = jax.tree_util.tree_map(lambda p: p.astype(dt), params)
        state = TrainState.create(
            apply_fn=module.apply,
            params=params,
            tx=tx,
            batch_stats=variables.get("batch_stats"),
            rng=make_base_rng(rng_seed),
        )
        # flax creates ``step=0``, a weakly typed int; a restored checkpoint
        # carries a strong int32. The two lower to different step programs,
        # so a resumed run would recompile what the first run had cached.
        return state.replace(step=jnp.zeros((), jnp.int32))

    with span(SPAN_STATE_SHAPES):
        shapes = jax.eval_shape(init_fn)
        sharding = infer_state_sharding(shapes, mesh, min_shard_size=min_shard_size)
    with span(SPAN_STATE_INIT):
        state = jax.block_until_ready(jax.jit(init_fn, out_shardings=sharding)())
    return state, sharding


def make_train_step(
    mesh: Mesh,
    state_sharding: Any,
    *,
    mode: StepMode,
    grad_accum: int = 1,
    pipe_microbatches: int = 0,
    encoder_cfg: Any = None,
    decoder_cfg: Any = None,
    guard_nonfinite: bool = False,
    diag: bool = False,
) -> Callable[[TrainState, dict], tuple[TrainState, dict]]:
    """Build the jitted train step.

    ``grad_accum == 1``: batch leaves are (batch, ...).
    ``grad_accum > 1``: batch leaves are (accum, micro, ...) and a
    ``lax.scan`` accumulates gradients before the single optimizer update.

    ``guard_nonfinite=True`` compiles the divergence guard into the step
    (``faults/sentinel.py``): on a non-finite loss or grad norm the
    optimizer update is computed and thrown away by a per-leaf select (no
    branch: a conditional cost the L/16 step 9 ms of copies and waits) —
    state passes through untouched except ``step + 1`` — and the metrics gain
    ``grad_norm`` and ``skipped``. Same program either way batch-to-batch:
    no recompile.

    The returned callable accepts an optional third argument ``inject`` —
    a ``(2,)`` float32 host array ``[loss_mult, grad_mult]`` (defaults to
    ones) multiplied into the differentiated loss and the gradients. It is
    a *traced* input, so the fault-injection harness can turn a chosen
    step's loss/grads NaN (``train.loss`` / ``train.grad`` sites) without
    triggering a compile; a multiply by exactly 1.0 is bit-exact in every
    float dtype, so un-injected runs are numerically identical.

    ``diag=True`` (a STATIC flag — the ``diag=False`` program is untouched)
    additionally compiles per-layer-group diagnostics into the step
    (``obs/modelstats.py``): the metrics gain ``diag``, a ``(groups, 3)``
    float32 array of (grad_norm, param_norm, update_ratio) per layer group
    in :func:`~jumbo_mae_tpu_tpu.obs.modelstats.group_layout` order, and
    ``finite_frac``, the finite fraction of the per-sample loss batch. The
    host decides the fetch cadence (``run.diag_every``).

    ``pipe_microbatches > 0`` (requires ``encoder_cfg`` and a mesh with a
    ``pipe`` axis): the encoder's block chain runs through the GPipe
    schedule (``parallel/pipeline.py``) via the model's ``blocks_override``
    seam — same parameters, pipelined execution. Works for BOTH modes
    (pretrain and classify/finetune — the classifier shares the JumboViT
    encoder). With ``decoder_cfg`` additionally set (pretrain only), the
    MAE decoder stack is depth-sharded through the same schedule via its
    own seam (``dec_blocks_override``).
    """
    if pipe_microbatches:
        if encoder_cfg is None:
            raise ValueError("pipe_microbatches requires encoder_cfg")
        if "pipe" not in mesh.shape:
            raise ValueError("pipe_microbatches requires a mesh with a 'pipe' axis")
        if decoder_cfg is not None and mode != "pretrain":
            raise ValueError("decoder pipelining applies to pretrain only")
        from jumbo_mae_tpu_tpu.parallel.pipeline import (
            make_jumbo_pipeline_apply,
            make_plain_pipeline_apply,
        )

        pipeline_apply = make_jumbo_pipeline_apply(
            encoder_cfg, mesh=mesh, microbatches=pipe_microbatches
        )
        # the encoder subtree lives under "encoder" in MAEPretrainModel
        # trees and "model" in ClassificationModel trees
        enc_key = "encoder" if mode == "pretrain" else "model"
        # dropout/droppath ride gpipe's per-(shard, block, microbatch)
        # key derivation (parallel/pipeline.py); deterministic configs
        # skip the rng plumbing entirely
        pipe_stochastic = (encoder_cfg.dropout or 0) > 0 or (
            encoder_cfg.droppath or 0
        ) > 0
        dec_pipeline_apply = None
        if decoder_cfg is not None:
            dec_pipeline_apply = make_plain_pipeline_apply(
                decoder_cfg, mesh=mesh, microbatches=pipe_microbatches
            )
            dec_stochastic = (decoder_cfg.dropout or 0) > 0 or (
                decoder_cfg.droppath or 0
            ) > 0

    def loss_fn(params, batch_stats, micro_idx, batch, state, loss_mult):
        with jax.named_scope(SCOPE_RNG):
            rngs = state.step_rngs(micro=micro_idx)
        variables = {"params": params}
        extra = {}
        if pipe_microbatches:
            enc_params = params[enc_key]
            # domain-separated from flax's own path-folded "dropout" use so
            # the pipeline's integer folds can't collide with module
            # streams; encoder and decoder pipelines get disjoint folds
            pipe_base = jax.random.fold_in(rngs["dropout"], PIPE_RNG_DOMAIN)
            pipe_rng = (
                jax.random.fold_in(pipe_base, 0) if pipe_stochastic else None
            )
            extra["blocks_override"] = lambda x: pipeline_apply(
                enc_params, x, pipe_rng
            )
            if dec_pipeline_apply is not None:
                dec_params = params["decoder"]
                dec_rng = (
                    jax.random.fold_in(pipe_base, 1)
                    if dec_stochastic
                    else None
                )
                extra["dec_blocks_override"] = lambda x: dec_pipeline_apply(
                    dec_params, x, dec_rng
                )
        new_stats = None
        if batch_stats is not None:
            variables["batch_stats"] = batch_stats
            out, updated = state.apply_fn(
                variables,
                *model_inputs(mode, batch),
                deterministic=False,
                rngs=rngs,
                mutable=["batch_stats"],
                **extra,
            )
            new_stats = updated["batch_stats"]
        else:
            out = state.apply_fn(
                variables,
                *model_inputs(mode, batch),
                deterministic=False,
                rngs=rngs,
                **extra,
            )
        with jax.named_scope(SCOPE_METRICS):
            metrics = {
                k: v.mean() if v.ndim else v
                for k, v in out.items()
                if not k.endswith("_per_sample")
            }
            if diag:
                # finite fraction of the loss batch: per-sample where the
                # model exposes it (pretrain loss_per_sample, classify
                # per-sample loss), else the scalar's own finiteness
                ps = out.get("loss_per_sample", out["loss"])
                fin = jnp.isfinite(ps).astype(jnp.float32)
                metrics["finite_frac"] = fin.mean() if fin.ndim else fin
            return metrics["loss"] * loss_mult, (metrics, new_stats)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    @partial(
        jax.jit,
        donate_argnums=(0,),
        in_shardings=(
            state_sharding,
            batch_sharding(mesh, accum=grad_accum > 1),
            None,
        ),
        out_shardings=(state_sharding, None),
    )
    def _train_step(state: TrainState, batch: dict, inject):
        with jax.named_scope(SCOPE_GRAD_SCALE):
            loss_mult, grad_mult = inject[0], inject[1]
        if grad_accum == 1:
            (_, (metrics, new_stats)), grads = grad_fn(
                state.params, state.batch_stats, 0, batch, state, loss_mult
            )
        else:
            metrics_shape = jax.eval_shape(
                lambda: loss_fn(
                    state.params,
                    state.batch_stats,
                    0,
                    jax.tree_util.tree_map(lambda x: x[0], batch),
                    state,
                    loss_mult,
                )[1][0]
            )
            # Accumulate in float32 even when params (and so grads) are
            # bf16-stored: micro-grad sums lose mantissa fast in bf16.
            init = (
                jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state.params
                ),
                jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), metrics_shape
                ),
                state.batch_stats,
            )

            def micro(carry, xs):
                grads_acc, metrics_acc, stats = carry
                idx, micro_batch = xs
                (_, (metrics, new_stats)), grads = grad_fn(
                    state.params, stats, idx, micro_batch, state, loss_mult
                )
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads
                )
                return (
                    _tree_add(grads_acc, grads),
                    _tree_add(metrics_acc, metrics),
                    new_stats if new_stats is not None else stats,
                ), None

            with jax.named_scope(SCOPE_GRAD_ACCUM):
                (grads, metrics, new_stats), _ = jax.lax.scan(
                    micro, init, (jnp.arange(grad_accum), batch)
                )
                grads = _tree_scale(grads, 1.0 / grad_accum)
                metrics = _tree_scale(metrics, 1.0 / grad_accum)

        with jax.named_scope(SCOPE_GRAD_SCALE):
            grads = jax.tree_util.tree_map(
                lambda g: g * grad_mult.astype(g.dtype), grads
            )
        prev_params = state.params if diag else None
        if guard_nonfinite:
            # the guard must see the INJECTED loss (metrics keep the raw
            # one): raw_loss x loss_mult is exactly the differentiated value
            with jax.named_scope(SCOPE_GUARD):
                loss_val = metrics["loss"] * loss_mult
            state, grad_norm, finite = guarded_apply_gradients(
                state, grads, loss_val
            )
            if new_stats is not None:
                # BatchNorm stats from a non-finite forward are tainted too
                with jax.named_scope(SCOPE_GUARD):
                    state = state.replace(
                        batch_stats=jax.tree_util.tree_map(
                            lambda new, old: jnp.where(finite, new, old),
                            new_stats,
                            state.batch_stats,
                        )
                    )
            with jax.named_scope(SCOPE_METRICS):
                metrics = metrics | {
                    "grad_norm": grad_norm,
                    "skipped": 1.0 - finite.astype(jnp.float32),
                }
        else:
            with jax.named_scope(SCOPE_OPTIMIZER):
                state = state.apply_gradients(grads=grads)
            if new_stats is not None:
                state = state.replace(batch_stats=new_stats)
        if diag:
            # one stacked (groups, 3) array — a single small host fetch per
            # diagnostic step instead of a tree of scalars
            metrics = metrics | {
                "diag": group_stats(prev_params, grads, state.params)
            }
        hyper = getattr(state.opt_state, "hyperparams", None)
        if hyper is not None:
            metrics = metrics | {"learning_rate": hyper["learning_rate"]}
        return state, metrics

    no_inject = np.ones(2, np.float32)

    # Dispatch through an AOT-compiled executable (lower().compile(), keyed
    # by batch shapes) instead of the tracing jit wrapper. Two reasons:
    # (1) cost observability — ``Compiled.cost_analysis()`` needs the
    # executable in hand, and jax's AOT path is NOT deduped against the C++
    # jit cache, so a post-hoc ``lower().compile()`` on an already-traced
    # jit function would compile the whole program a second time;
    # (2) it makes the train loop's compile point explicit, matching the
    # serving engine's idiom. A compile or execution failure raises: there
    # is no second dispatch route that could hide an HBM-limit or Mosaic
    # error behind another multi-minute compile of the same program.
    aot: dict[tuple, Any] = {}

    def train_step(state: TrainState, batch: dict, inject=None):
        inj = no_inject if inject is None else np.asarray(inject, np.float32)
        key = _batch_key(batch)
        compiled = aot.get(key)
        if compiled is None:
            with span(f"{SPAN_PROGRAM_BUILD}:train_step"):
                compiled = aot[key] = _train_step.lower(state, batch, inj).compile()
            note_program("train_step", compiled)
        return compiled(state, batch, inj)

    train_step.executables = aot  # read by cli/train's cost extraction
    # the lowering without a run: given shapes that carry a described
    # device's shardings it compiles for a chip that is not attached
    train_step.lower = lambda state, batch: _train_step.lower(state, batch, no_inject)
    return train_step


def make_eval_step(
    mesh: Mesh, state_sharding: Any, *, mode: StepMode
) -> Callable[[TrainState, dict], dict]:
    """Jitted eval step returning SUMS over valid samples + the valid count;
    the host-side loop divides at the end (exact weighted mean even with
    ragged final batches). ``batch_idx`` varies the eval RNG (MAE masking)
    across the eval loop's batches; derivation is domain-separated from
    training so no (step, micro) coordinate can collide."""

    @partial(
        jax.jit,
        in_shardings=(state_sharding, batch_sharding(mesh, accum=False), None),
        out_shardings=None,
    )
    def _eval_step(state: TrainState, batch: dict, batch_idx):
        rngs = state.step_rngs(micro=batch_idx, domain=EVAL_DOMAIN)
        variables = {"params": state.params}
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
        inputs = {name: batch[name] for name in MODES[mode].inputs}
        valid = batch.get("valid")
        if valid is None:
            valid = jnp.ones(next(iter(inputs.values())).shape[0], jnp.float32)
        else:
            valid = valid.astype(jnp.float32)
        if "labels" in inputs:  # padding rows carry label -1
            inputs["labels"] = jnp.where(inputs["labels"] >= 0, inputs["labels"], 0)
        out = state.apply_fn(variables, *inputs.values(), deterministic=True, rngs=rngs)
        per_sample = {k: out[name] for k, name in MODES[mode].eval_outputs.items()}

        sums = {k: jnp.sum(v * valid) for k, v in per_sample.items()}
        sums["num_samples"] = valid.sum()
        return sums

    # AOT dispatch like the train step's: the compile point is explicit, so
    # it can be timed (``program_build:eval_step``) and its executable noted
    aot: dict[tuple, Any] = {}

    def eval_step(state: TrainState, batch: dict, batch_idx: int = 0):
        idx = jnp.asarray(batch_idx, jnp.int32)
        key = _batch_key(batch)
        compiled = aot.get(key)
        if compiled is None:
            with span(f"{SPAN_PROGRAM_BUILD}:eval_step"):
                compiled = aot[key] = _eval_step.lower(state, batch, idx).compile()
            note_program("eval_step", compiled)
        return compiled(state, batch, idx)

    eval_step.executables = aot
    return eval_step
