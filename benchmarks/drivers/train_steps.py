"""Pretraining steps, back to back, through the trainer's own factories.

State, optimizer, step program and device prefetch are the trainer's
(``cli.train.build_model``, ``train.make_optimizer``, ``create_sharded_state``,
``make_train_step``, ``data.loader.prefetch_to_device``); the images and the
weights are the benchmark's, from the seed. The loop dispatches without
waiting and fetches the metrics every ``fetch_every`` steps, the trainer's
pattern at a log boundary.

``correct``: set-up drives the step through its first three steps, by the
same call and feed as the window, and keeps each loss, the per-leaf norm of
the first gradient (from Adam's first moment after one step) and the per-leaf
norm of the parameters' change after three. After the window the state is
freed and the float32 reference follows the same three steps.
"""

from __future__ import annotations

import itertools
import json
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops, schedule
from benchmarks.drivers import common
from benchmarks.harness import span
from benchmarks.reference import model as ref_model
from benchmarks.reference import optim as ref_optim
from benchmarks.reference import params as ref_params

LIMITS = json.loads((Path(__file__).parent / "train_steps.limits.json").read_text())
CHECK_STEPS = 3
SPANS = ("data_wait", "dispatch", "fetch")  # this driver's spans: they name a trace's idle gaps
REFERENCE_ROWS = 32  # rows a reference call takes at once


def _leaf_sq(tree):
    return jnp.stack([jnp.sum(jnp.square(x.astype(jnp.float32)))
                      for x in jax.tree_util.tree_leaves(tree)])


def _find_mu(opt_state):
    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer, found {len(found)}")
    return found[0].mu


@partial(jax.jit, static_argnums=2)
def mask_noise(seed, step, length: int):
    """The uniform draw behind step ``step``'s mask, as the program derives
    it: base key of the seed folded with (process 0, step, train domain 0,
    micro-batch 0, stream "noise" = 1), then flax's ``make_rng("noise")`` in
    the module at path ``encoder`` — reproduced through flax itself, with a
    probe at that path."""
    import flax.linen as nn

    class Probe(nn.Module):
        @nn.compact
        def __call__(self):
            return jax.random.uniform(self.make_rng("noise"), (length,), jnp.float32)

    class Parent(nn.Module):
        def setup(self):
            self.encoder = Probe(name="encoder")

        def __call__(self):
            return self.encoder()

    key = jax.random.key(seed)
    for fold in (0, step, 0, 0, 1):
        key = jax.random.fold_in(key, fold)
    return Parent().apply({}, rngs={"noise": key})


def worst_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Largest gap between two lists of per-leaf norms, each against the
    reference's norm of that leaf or of the median leaf, whichever is larger
    (some gradients are all but zero)."""
    floor = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / floor))


def reference_run(config: dict, seed: int, batches, rounding: str = "float32") -> dict:
    """The reference's losses, first-gradient norms and change norms over
    ``len(batches)`` steps from the seed's weights, in blocks of rows with the
    gradient summed in place, so that float32 L/16 fits the chip."""
    m = config["model"]
    o = ref_optim.for_batch(config["optim"], batches[0].shape[0])
    shapes = ref_params.mae_shapes(m)
    n_patches = (m["image_size"] // m["patch_size"]) ** 2
    seed = common.seed32(seed)
    with jax.default_matmul_precision("highest"):
        loss_grad = jax.value_and_grad(partial(ref_model.mae_loss, m=m, rounding=rounding))

        @partial(jax.jit, donate_argnums=(1,))
        def add_block(params, acc, images, noise):
            loss, g = loss_grad(params, images, noise)
            return loss, jax.tree_util.tree_map(jnp.add, acc, g)

        scale = jax.jit(lambda g, k: jax.tree_util.tree_map(lambda x: x / k, g),
                        donate_argnums=0)
        change_sq = jax.jit(lambda p, s: _leaf_sq(jax.tree_util.tree_map(
            jnp.subtract, p, ref_params.make_params(s, shapes))))
        params = ref_params.seeded(seed, shapes)
        state = ref_optim.adamw_init(params)
        losses, grad_sq = [], None
        for i, images in enumerate(batches):
            noise = mask_noise(seed, i, n_patches)
            starts = range(0, images.shape[0], REFERENCE_ROWS)
            total, acc = 0.0, jax.tree_util.tree_map(jnp.zeros_like, params)
            for r in starts:
                loss, acc = add_block(params, acc, images[r : r + REFERENCE_ROWS], noise)
                total += float(loss)
            g = scale(acc, float(len(starts)))
            losses.append(total / len(starts))
            if grad_sq is None:
                grad_sq = np.asarray(jax.jit(_leaf_sq)(g))
            params, state = ref_optim.adamw_step(params, g, state, o)
        return {"loss": np.asarray(losses), "grad": np.sqrt(grad_sq),
                "delta": np.sqrt(np.asarray(change_sq(params, seed)))}


def compare(prog: dict, ref: dict, window_bad: int = 0):
    return [
        ("loss_gap", float(np.max(np.abs(prog["loss"] - ref["loss"]) / np.abs(ref["loss"]))),
         LIMITS["loss_gap"]),
        ("first_grad_norm_gap", worst_gap(prog["grad"], ref["grad"]),
         LIMITS["first_grad_norm_gap"]),
        ("param_change_norm_gap", worst_gap(prog["delta"], ref["delta"]),
         LIMITS["param_change_norm_gap"]),
        ("nonfinite_losses_in_window", float(window_bad), 0.0),
    ]


class Driver:
    def __init__(self, cell: dict, *, devices, seed: int):
        from jumbo_mae_tpu_tpu.cli.train import build_model
        from jumbo_mae_tpu_tpu.data.loader import prefetch_to_device
        from jumbo_mae_tpu_tpu.parallel import create_mesh
        from jumbo_mae_tpu_tpu.parallel.sharding import batch_sharding
        from jumbo_mae_tpu_tpu.train import (
            create_sharded_state,
            make_optimizer,
            make_train_step,
        )

        self.config, t = cell["config"], cell["traffic"]
        m = self.config["model"]
        self.seed, self.chips = seed, len(devices)
        self.batch = t["batch_per_chip"] * self.chips
        self.fetch_every = t["fetch_every"]
        cfg = common.program_config(self.config, batch=self.batch)
        run = cfg.run
        mesh = create_mesh(cfg.mesh, devices=list(devices))
        model, enc_cfg, _ = build_model(cfg)
        tx = make_optimizer(cfg.optim, run.train_batch_size, num_layers=enc_cfg.layers)
        example = {"images": np.zeros((self.batch, m["image_size"], m["image_size"], 3), np.uint8)}
        state, sharding = create_sharded_state(
            model, tx, example, mesh, mode="pretrain", init_seed=run.init_seed,
            rng_seed=run.seed, param_dtype=cfg.optim.param_dtype,
        )
        self.shapes = ref_params.mae_shapes(m)
        common.require_same_tree(state.params, self.shapes, "pretraining state")

        # the state object is the trainer's; its weights are the benchmark's.
        # The trainer's own init is freed first, so that the peak the run
        # reports is the step's and not two states side by side.
        template = jax.eval_shape(lambda: state)
        jax.tree_util.tree_map(lambda x: x.delete(), state)

        def seeded(s):
            # the seed enters as a traced value only, so that every seed
            # finds the same programs in the compile cache
            params = ref_params.make_params(s, self.shapes)
            rng = jax.random.fold_in(jax.random.key(s), jax.process_index())
            return template.replace(step=jnp.zeros((), jnp.int32), params=params,
                                    opt_state=tx.init(params), rng=rng)

        self._delta_sq = jax.jit(lambda params, s: _leaf_sq(jax.tree_util.tree_map(
            jnp.subtract, params, ref_params.make_params(s, self.shapes))))
        self._leaf_sq = jax.jit(_leaf_sq)
        self.state = jax.jit(seeded, out_shardings=sharding)(common.seed32(seed))
        self.step = make_train_step(
            mesh, sharding, mode="pretrain", grad_accum=run.grad_accum,
            guard_nonfinite=run.sentinel,
        )
        host = schedule.image_batches(seed, self.batch, m["image_size"], t["distinct_batches"])
        first = [next(host) for _ in range(CHECK_STEPS)]
        self.first_batches = [b["images"] for b in first]  # the reference follows these
        self.it = prefetch_to_device(itertools.chain(first, host),
                                     batch_sharding(mesh, accum=False))
        self.b1 = float(self.config["optim"]["b1"])
        self.readings: dict | None = None
        self.window_bad = 0

    def _one_step(self):
        """One step by the window's own call and feed."""
        with span("data_wait"):
            t = time.perf_counter()
            batch = next(self.it)
            wait = time.perf_counter() - t
        with span("dispatch"):
            self.state, metrics = self.step(self.state, batch)
        return metrics, wait

    def warm(self):
        losses, grad = [], None
        for i in range(CHECK_STEPS):
            metrics, _ = self._one_step()
            losses.append(float(jax.device_get(metrics["loss"])))
            if i == 0:
                mu = _find_mu(self.state.opt_state)
                grad = np.sqrt(np.asarray(self._leaf_sq(mu))) / (1.0 - self.b1)
        delta = np.sqrt(np.asarray(self._delta_sq(self.state.params, common.seed32(self.seed))))
        self.readings = {"loss": np.asarray(losses), "grad": grad, "delta": delta}

    def window(self, seconds: float, seed: int) -> dict:
        del seed  # the feed goes on from where set-up left it
        marks, wait, steps, bad = [time.perf_counter()], 0.0, 0, 0
        while True:
            metrics, w = self._one_step()
            wait += w
            steps += 1
            if steps % self.fetch_every == 0:
                with span("fetch"):
                    host = jax.device_get(metrics)
                    jax.block_until_ready(self.state.step)
                marks.append(time.perf_counter())
                bad += int(not np.isfinite(host["loss"])) + int(host.get("skipped", 0) > 0)
                if marks[-1] - marks[0] >= seconds:
                    break
        self.window_bad += bad
        window_s = marks[-1] - marks[0]
        return {
            "window_s": window_s, "steps": steps, "images": steps * self.batch,
            "attempted": steps, "failed": bad, "samples": len(marks) - 1,
            "step_s": list(np.diff(marks) / self.fetch_every),
            "data_wait_s": wait,
            "flops_per_image": flops.pretrain_step(self.config["model"]),
        }

    def program_bytes(self) -> int:
        """What the compiled step holds while it runs, by the compiler's own
        accounting (``memory_analysis``): arguments + temporaries + outputs
        that alias no argument. Reported beside the runtime's counter, never
        in its place."""
        def held(compiled):
            m = compiled.memory_analysis()
            return (m.argument_size_in_bytes + m.temp_size_in_bytes
                    + m.output_size_in_bytes - m.alias_size_in_bytes)

        return max(map(held, self.step.executables.values()), default=0)

    def check(self):
        self.close()  # the reference runs with the program's state freed
        ref = reference_run(self.config, self.seed, self.first_batches)
        return compare(self.readings, ref, self.window_bad)

    def close(self):
        self.state = self.it = None


def build(cell, *, devices, seed):
    return Driver(cell, devices=devices, seed=seed)


def limit_readings(cell, *, devices, seeds, control_seeds):
    """For ``check.py``: the numbers compared, for sound runs of the program
    and for the control — the reference in the program's place, computed in
    fp8 (e4m3), the nearest precision below the configuration's bfloat16.
    Training's readings need no measured window."""
    for seed in seeds:
        driver = Driver(cell, devices=devices, seed=seed)
        driver.warm()
        readings, batches = driver.readings, driver.first_batches
        driver.close()
        del driver
        ref = reference_run(cell["config"], seed, batches)
        yield {"seed": seed, "kind": "sound", "checks": compare(readings, ref)}
        if seed in control_seeds:
            low = reference_run(cell["config"], seed, batches, rounding="fp8")
            yield {"seed": seed, "kind": "control", "checks": compare(low, ref)}
