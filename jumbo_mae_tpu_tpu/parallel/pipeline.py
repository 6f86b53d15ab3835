"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

The reference has no pipeline parallelism (SURVEY §2.10: PP — NO); its
ViT sizes fit one chip. This module adds it as a first-class runtime
capability for depth-sharding larger stacks: transformer blocks are
stacked along a leading "stage" axis and sharded over the ``pipe`` mesh
axis — each device owns ``layers / n_stages`` consecutive blocks — and
microbatches stream through the classic GPipe schedule:

- tick t: stage 0 feeds microbatch t (clamped past the last one), every
  stage applies its local blocks, activations hop to the next stage with
  ``lax.ppermute`` (one ICI neighbor hop per tick — the mesh should place
  ``pipe`` on ICI);
- after ``microbatches + n_stages − 1`` ticks the last stage has collected
  every microbatch; a masked ``psum`` replicates the output.

Everything is ``lax.scan``/``ppermute`` inside one ``shard_map`` — a
single XLA program, fully differentiable (``ppermute`` transposes to the
reverse hop, so ``jax.grad`` yields the backward pipeline schedule
automatically). Composes with data parallelism by sharding the microbatch
batch dim over ``data`` in the same ``shard_map``.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def create_pipeline_mesh(
    data: int, pipe: int, devices: list | None = None
) -> Mesh:
    """(data, pipe) mesh: consecutive devices form a pipeline (ppermute
    hops ride neighbor ICI links), replicated ``data`` ways."""
    devices = devices if devices is not None else jax.devices()
    if data * pipe > len(devices):
        raise ValueError(
            f"mesh (data={data}, pipe={pipe}) needs {data * pipe} devices, "
            f"have {len(devices)}"
        )
    dev = np.array(devices[: data * pipe]).reshape(data, pipe)
    return Mesh(dev, ("data", "pipe"))


def stack_block_params(params: dict, prefix: str = "block_") -> tuple[dict, int]:
    """Stack homogeneous per-block subtrees (``block_0`` … ``block_{L-1}``,
    the JumboViT/MAE-decoder layout) into one tree with a leading block
    axis — the form :func:`gpipe` shards over ``pipe``."""
    names = sorted(
        (k for k in params if k.startswith(prefix)),
        key=lambda k: int(k[len(prefix) :]),
    )
    if not names:
        raise ValueError(f"no {prefix}* subtrees in params")
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *[params[n] for n in names]
    )
    return stacked, len(names)


def unstack_block_params(stacked: dict, prefix: str = "block_") -> dict:
    """Inverse of :func:`stack_block_params`."""
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return {
        f"{prefix}{i}": jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
        for i in range(n)
    }


def gpipe(
    block_fn: Callable[..., jax.Array],
    stacked_params: dict,
    x: jax.Array,
    *,
    mesh: Mesh,
    microbatches: int,
    axis: str = "pipe",
    data_axis: str | None = "data",
    shared_params: dict | None = None,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Run ``x`` through all stacked blocks under the GPipe schedule.

    ``block_fn(one_block_params, h) -> h`` must be pure (e.g. a flax
    ``apply`` with ``deterministic=True``). ``stacked_params`` carries the
    leading block axis (from :func:`stack_block_params`); the block count
    must divide by the mesh's ``pipe`` size. ``x`` is the global batch;
    ``microbatches`` must divide it. Returns the full-batch output,
    replicated over ``pipe``.

    ``shared_params`` (optional) is a param tree used by EVERY block — the
    jumbo architecture's shared CLS MLP is exactly this shape. It is
    replicated across stages, ``block_fn`` is then called as
    ``block_fn(one_block_params, h, shared_params)``, and its gradient
    comes back correctly summed over stages (the replicated-input
    transpose is a ``psum`` over ``pipe``).

    ``rng`` (optional) enables stochastic blocks (dropout / droppath):
    ``block_fn`` is then called with a trailing PRNG key derived per
    (data-shard, global block index, microbatch) — every block application
    anywhere in the schedule draws an independent stream, exactly the
    independence structure the sequential path gets from flax folding the
    "dropout" stream per module path (masks differ from sequential
    execution, the distribution matches). Without it the schedule is
    deterministic and ``block_fn`` keeps its short signature.
    """
    n_stages = mesh.shape[axis]
    n_blocks = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_blocks % n_stages:
        raise ValueError(
            f"{n_blocks} blocks do not divide over {n_stages} pipeline stages"
        )
    batch = x.shape[0]
    if batch % microbatches:
        raise ValueError(
            f"batch {batch} not divisible into {microbatches} microbatches"
        )
    mb = batch // microbatches
    xm = x.reshape(microbatches, mb, *x.shape[1:])

    data_spec = data_axis if (data_axis and data_axis in mesh.shape) else None
    if data_spec and mb % mesh.shape[data_axis]:
        raise ValueError(
            f"microbatch size {mb} (batch {batch} / {microbatches} "
            f"microbatches) does not divide over the "
            f"{data_axis}={mesh.shape[data_axis]} mesh axis"
        )

    shared = {} if shared_params is None else shared_params
    bps = n_blocks // n_stages  # blocks per stage
    # a dummy key keeps the shard_map arity static when rng is unused
    rng_in = rng if rng is not None else jax.random.key(0)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            jax.tree_util.tree_map(lambda _: P(axis), stacked_params),
            P(None, data_spec),
            jax.tree_util.tree_map(lambda _: P(), shared),  # replicated
            P(),  # rng: replicated; decorrelated below by axis_index folds
        ),
        out_specs=P(None, data_spec),
        check_vma=False,
    )
    def run(local_params, x_local, shared_local, rng_local):
        stage = jax.lax.axis_index(axis)
        m = x_local.shape[0]
        if rng is not None and data_spec:
            # distinct dropout masks per data shard (the GSPMD sequential
            # path gets this for free from sharding the global mask)
            rng_local = jax.random.fold_in(
                rng_local, jax.lax.axis_index(data_axis)
            )

        def apply_stage(h, mb_idx):
            # each stage applies its contiguous slice of blocks in order
            def one(h, xs):
                p, local_idx = xs
                args = (p, h) if shared_params is None else (p, h, shared_local)
                if rng is None:
                    return block_fn(*args), None
                key = jax.random.fold_in(
                    jax.random.fold_in(rng_local, stage * bps + local_idx),
                    mb_idx,
                )
                return block_fn(*args, key), None

            h, _ = jax.lax.scan(one, h, (local_params, jnp.arange(bps)))
            return h

        def tick(carry, t):
            act, buf = carry
            inp = jnp.where(stage == 0, x_local[jnp.clip(t, 0, m - 1)], act)
            # stage s processes microbatch t - s at tick t (clamped ticks
            # compute garbage that is never collected)
            out = apply_stage(inp, jnp.clip(t - stage, 0, m - 1))
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            out_idx = t - (n_stages - 1)
            collect = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            slot = jnp.clip(out_idx, 0, m - 1)
            buf = buf.at[slot].set(jnp.where(collect, out, buf[slot]))
            return (nxt, buf), None

        buf0 = jnp.zeros_like(x_local)
        act0 = jnp.zeros_like(x_local[0])
        (_, buf), _ = jax.lax.scan(
            tick, (act0, buf0), jnp.arange(microbatches + n_stages - 1)
        )
        # only the last stage holds real outputs; masked psum replicates
        mine = jnp.where(stage == n_stages - 1, buf, jnp.zeros_like(buf))
        return jax.lax.psum(mine, axis)

    out = run(stacked_params, xm, shared, rng_in)
    return out.reshape(batch, *x.shape[1:])


def make_jumbo_pipeline_apply(
    cfg, *, mesh: Mesh, microbatches: int
) -> Callable[[dict, jax.Array], jax.Array]:
    """Build ``apply(encoder_params, x) -> x`` that pipelines a JumboViT
    encoder's ``block_*`` chain with the shared jumbo CLS MLP replicated
    across stages.

    The standalone block module is constructed HERE, at factory time —
    constructing flax modules inside another module's apply (e.g. from the
    ``blocks_override`` seam) is an ``AssignSubModuleError``.

    ``encoder_params`` is the encoder subtree of a real model
    (``block_0…block_{L-1}`` + ``jumbo_mlp`` + embed/ln/… — only the
    blocks and ``jumbo_mlp`` are read). ``x`` is the token sequence after
    embedding/CLS concat, i.e. the input to ``block_0``.
    """
    from jumbo_mae_tpu_tpu.models.config import maybe_remat
    from jumbo_mae_tpu_tpu.models.layers import JumboBlock, make_jumbo_mlp

    # name=None: a standalone block scopes the shared MLP under itself
    # via its attribute name, and we graft the shared params in per call.
    # maybe_remat: the pipeline must honor cfg.grad_ckpt like the
    # sequential encoder does — GPipe holds every in-flight microbatch's
    # activations, so dropping remat here would silently change the memory
    # profile of exactly the configs pipeline parallelism targets.
    block = maybe_remat(JumboBlock, cfg)(cfg, make_jumbo_mlp(cfg, name=None))

    def apply(
        encoder_params: dict, x: jax.Array, rng: jax.Array | None = None
    ) -> jax.Array:
        stacked, _ = stack_block_params(encoder_params)

        if rng is None:

            def block_fn(p, h, shared):
                # a standalone JumboBlock scopes the shared MLP under
                # itself; the encoder scopes it at the parent — graft it in
                return block.apply(
                    {"params": {**p, "jumbo_mlp": shared}}, h, True
                )

        else:

            def block_fn(p, h, shared, key):
                return block.apply(
                    {"params": {**p, "jumbo_mlp": shared}},
                    h,
                    False,
                    rngs={"dropout": key},
                )

        return gpipe(
            block_fn,
            stacked,
            x,
            mesh=mesh,
            microbatches=microbatches,
            shared_params=encoder_params["jumbo_mlp"],
            rng=rng,
        )

    return apply


def make_plain_pipeline_apply(
    cfg, *, mesh: Mesh, microbatches: int
) -> Callable[[dict, jax.Array], jax.Array]:
    """Build ``apply(params, x) -> x`` that pipelines a plain pre-norm
    block chain (``block_0…block_{L-1}`` of :class:`PlainBlock` — the MAE
    decoder's stack) over the mesh's ``pipe`` axis.

    Same factory pattern as :func:`make_jumbo_pipeline_apply` (module
    constructed at factory time, honors ``cfg.grad_ckpt``); the optional
    ``rng`` third argument enables dropout/droppath via gpipe's
    per-(shard, block, microbatch) key derivation."""
    from jumbo_mae_tpu_tpu.models.config import maybe_remat
    from jumbo_mae_tpu_tpu.models.layers import PlainBlock

    block = maybe_remat(PlainBlock, cfg)(cfg)

    def apply(
        params: dict, x: jax.Array, rng: jax.Array | None = None
    ) -> jax.Array:
        stacked, _ = stack_block_params(params)

        if rng is None:

            def block_fn(p, h):
                return block.apply({"params": p}, h, True)

        else:

            def block_fn(p, h, key):
                return block.apply(
                    {"params": p}, h, False, rngs={"dropout": key}
                )

        return gpipe(
            block_fn, stacked, x, mesh=mesh, microbatches=microbatches, rng=rng
        )

    return apply


def pipelined_jumbo_blocks_apply(
    cfg,
    encoder_params: dict,
    x: jax.Array,
    *,
    mesh: Mesh,
    microbatches: int,
) -> jax.Array:
    """One-shot convenience over :func:`make_jumbo_pipeline_apply` (module
    construction happens per call — use the factory from inside train
    steps)."""
    return make_jumbo_pipeline_apply(cfg, mesh=mesh, microbatches=microbatches)(
        encoder_params, x
    )


def pipelined_blocks_apply(
    block_module,
    params: dict,
    x: jax.Array,
    *,
    mesh: Mesh,
    microbatches: int,
    prefix: str = "block_",
) -> jax.Array:
    """Convenience wrapper: run a model's ``block_*`` chain (e.g. the MAE
    decoder's :class:`~jumbo_mae_tpu_tpu.models.layers.PlainBlock` stack)
    through :func:`gpipe`, taking the ordinary (unstacked) param layout."""
    stacked, _ = stack_block_params(params, prefix)

    def block_fn(p, h):
        return block_module.apply({"params": p}, h, True)

    return gpipe(
        block_fn, stacked, x, mesh=mesh, microbatches=microbatches
    )
