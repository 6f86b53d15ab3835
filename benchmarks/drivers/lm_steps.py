"""Language-model pretraining steps, back to back, through the trainer's own
factories: the latent-attention sparse-expert family behind ``train_loop``'s
loop and check.

State, optimizer, step program and device prefetch are the trainer's
(``cli.train.build_model``, ``train.make_optimizer``, ``create_sharded_state``,
``make_train_step`` in mode ``lm``, ``data.loader.prefetch_to_device``); the
tokens, the weights and the router biases are the benchmark's, from the seed.
The float32 reference (``benchmarks/reference/lm_model.py``) follows the same
first steps from the same weights, biases and tokens, its biases moved by the
same rule. The program's ``model.lm`` fields are translated here from the
configuration file's ``config.json`` keys, the one place the sizes are stated.
"""

from __future__ import annotations

import copy
import itertools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_lm
from benchmarks.drivers import common, train_loop
from benchmarks.drivers.train_loop import CHECK_STEPS, _leaf_sq
from benchmarks.reference import lm_model, lm_params
from benchmarks.reference import optim as ref_optim
from benchmarks.reference import params as ref_params

LIMITS = json.loads((Path(__file__).parent / "lm_steps.limits.json").read_text())
# the committed limits are set from the chip's readings at the published
# widths; a 32-wide model's few-element leaves read noisier
TINY_LIMITS = {"loss_gap": 1e-3, "first_grad_norm_gap": 0.1, "param_change_norm_gap": 0.1}
SPANS = train_loop.SPANS
SCOPES = "mla_moe_lm"  # benchmarks/scopes/<name>.json: this family's parts
CONTROL = "fp8"  # e4m3, the nearest precision below the configuration's bfloat16
FLOPS_SEQ = 8192  # the sequence length flops_pair compares the two counts at

# config.json's keys -> the program's models/lm.MlaMoeConfig fields
_FIELDS = {
    "hidden_size": "dim", "num_hidden_layers": "layers",
    "first_k_dense_replace": "first_k_dense", "num_attention_heads": "heads",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "intermediate_size": "dense_hidden",
    "moe_intermediate_size": "expert_hidden", "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "experts_per_token",
    "routed_scaling_factor": "routed_scaling_factor",
    "num_nextn_predict_layers": "mtp_layers", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps", "mtp_loss_weight": "mtp_loss_weight",
    "router_bias_rate": "router_bias_rate", "experts_held": "experts_held",
    "vocab_rows": "vocab_rows", "compute_dtype": "dtype", "grad_ckpt": "grad_ckpt",
}


def lm_fields(config: dict) -> dict:
    fields = {field: config[key] for key, field in _FIELDS.items()}
    published = config["published"]
    return fields | {"n_routed_experts": published["n_routed_experts"],
                     "vocab_size": published["vocab_size"]}


def program_config(config: dict, *, batch: int, seq: int):
    """The program's ``TrainConfig``: the file's ``program`` section (the
    recipe's run, optimizer and mesh), the model from the file's sizes. The
    program's own seeds stay 0, as for every family (``common``)."""
    from jumbo_mae_tpu_tpu.config import config_from_dict

    doc = copy.deepcopy(config["program"])
    doc.setdefault("run", {}).update(seed=0, init_seed=0, synthetic_data=True,
                                     train_batch_size=batch, valid_batch_size=batch)
    doc["model"] = {"lm": lm_fields(config)}
    doc.setdefault("data", {})["seq_len"] = seq
    return config_from_dict(doc)


def token_batches(seed: int, config: dict, batch: int, seq: int, distinct: int):
    """Endless cycle over ``distinct`` seeded batches (batch, seq + 1 + mtp)
    int32, ids uniform over the vocabulary rows held."""
    first, rows = config["vocab_rows"]
    length = seq + 1 + config["num_nextn_predict_layers"]
    pool = np.random.default_rng(seed).integers(first, first + rows, (distinct, batch, length),
                                                dtype=np.int32)
    return ({"tokens": pool[i % distinct]} for i in itertools.count())


def reference_run(config: dict, seed: int, batches, rounding: str = "float32") -> dict:
    """The reference's losses, first-gradient norms and change norms over
    ``len(batches)`` steps from the seed's weights and biases."""
    shapes = lm_params.lm_shapes(config)
    seed = common.seed32(seed)
    with jax.default_matmul_precision("highest"):
        loss_grad = jax.jit(jax.value_and_grad(
            lambda p, b, t: lm_model.batch_loss(p, b, t, config, rounding), has_aux=True))
        move = jax.jit(lambda b, n: lm_model.next_biases(b, n, config["router_bias_rate"]))
        change_sq = jax.jit(lambda p, s: _leaf_sq(jax.tree_util.tree_map(
            jnp.subtract, p, ref_params.make_params(s, shapes))))
        in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
        print(f"reference ({rounding}): {in_use / 1e9:.2f} GB in use on the device "
              "before it starts", flush=True)
        params = ref_params.seeded(seed, shapes)
        biases = jax.jit(lambda s: lm_params.make_biases(s, config))(seed)
        # Adam's moments are on the device only while they are updated: beside
        # them (8 B a parameter) a float32 backward pass at 8192 tokens does
        # not fit, so between steps they wait on the host
        moments = lambda st, move: st | {k: move(st[k]) for k in ("m", "v")}
        state, losses, grad_sq = None, [], None
        for tokens in batches:
            (loss, counts), g = loss_grad(params, biases, tokens)
            losses.append(float(loss))
            if grad_sq is None:
                grad_sq = np.asarray(jax.jit(_leaf_sq)(g))
            state = (ref_optim.adamw_init(params) if state is None
                     else moments(state, jax.device_put))
            params, state = ref_optim.adamw_step(params, g, state, config["optim"])
            biases = move(biases, counts)
            del g
            state = moments(state, jax.device_get)
        del state
        return {"loss": np.asarray(losses), "grad": np.sqrt(grad_sq),
                "delta": np.sqrt(np.asarray(change_sq(params, seed)))}


class Driver(train_loop.Loop):
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
        config = self.config
        self.seed, self.chips = seed, len(devices)
        self.batch, self.seq = t["sequences_per_chip"] * self.chips, t["seq"]
        self.fetch_every = t["fetch_every"]
        cfg = program_config(config, batch=self.batch, seq=self.seq)
        run = cfg.run
        mesh = create_mesh(cfg.mesh, devices=list(devices))
        model, lm, _ = build_model(cfg)
        tx = make_optimizer(cfg.optim, run.train_batch_size, num_layers=lm.layers)
        length = self.seq + 1 + config["num_nextn_predict_layers"]
        example = {"tokens": np.zeros((self.batch, length), np.int32)}
        state, sharding = create_sharded_state(
            model, tx, example, mesh, mode="lm", init_seed=run.init_seed,
            rng_seed=run.seed, param_dtype=cfg.optim.param_dtype,
        )
        self.shapes = lm_params.lm_shapes(config)
        common.require_same_tree(state.params, self.shapes, "language-model state")
        common.require_same_tree(state.batch_stats, lm_params.bias_shapes(config),
                                 "router biases")

        # the state object is the trainer's; its weights and biases are the
        # benchmark's. The trainer's own init is freed first, so that the
        # peak the run reports is the step's and not two states side by side.
        template = jax.eval_shape(lambda: state)
        jax.tree_util.tree_map(lambda x: x.delete(), state)

        def seeded(s):
            # the seed enters as a traced value only, so that every seed
            # finds the same programs in the compile cache
            params = ref_params.make_params(s, self.shapes)
            rng = jax.random.fold_in(jax.random.key(s), jax.process_index())
            return template.replace(step=jnp.zeros((), jnp.int32), params=params,
                                    opt_state=tx.init(params), rng=rng,
                                    batch_stats=lm_params.make_biases(s, config))

        self._delta_sq = jax.jit(lambda params, s: _leaf_sq(jax.tree_util.tree_map(
            jnp.subtract, params, ref_params.make_params(s, self.shapes))))
        self._leaf_sq = jax.jit(_leaf_sq)
        self.state = jax.jit(seeded, out_shardings=sharding)(common.seed32(seed))
        self.step = make_train_step(
            mesh, sharding, mode="lm", grad_accum=run.grad_accum,
            guard_nonfinite=run.sentinel,
        )
        host = token_batches(seed, config, self.batch, self.seq, t["distinct_batches"])
        first = [next(host) for _ in range(CHECK_STEPS)]
        self.first_batches = [b["tokens"] for b in first]  # the reference follows these
        self.it = prefetch_to_device(itertools.chain(first, host),
                                     batch_sharding(mesh, accum=False))
        self.b1 = float(config["optim"]["b1"])
        self.limits = LIMITS
        self._counters = []  # the window's steps' expert counters, on the device

    def _one_step(self):
        metrics, wait = super()._one_step()
        self._counters.append({k: metrics[f"moe_{k}"]
                               for k in ("imbalance", "held_share", "dropped")})
        return metrics, wait

    def window(self, seconds: float, seed: int) -> dict:
        self._counters = []
        record = super().window(seconds, seed)
        # the loop has fetched the loss already; the counters of its steps
        # are a few device scalars, read after the window has closed
        steps = jax.device_get(self._counters)
        moe = {"imbalance": float(np.mean([s["imbalance"] for s in steps])),
               "held_share": float(np.mean([s["held_share"] for s in steps])),
               "dropped": float(np.sum([s["dropped"] for s in steps]))}
        print(f"expert counters over {len(steps)} steps: {json.dumps(moe)}", flush=True)
        rows = moe["held_share"] * self.batch * self.seq * self.config["num_experts_per_tok"]
        core = flops_lm.causal_core_step(self.config, self.batch, self.seq)
        experts = flops_lm.experts_step(self.config, rows)
        work = {"attn_core": core, "experts": experts}
        return record | {"moe": moe, "kernel_work": {
            name: {"flops": f, "bytes": b} for name, (f, b) in work.items()}}

    def work(self, steps: int) -> dict:
        # the benchmark's one accepted training rate counts samples
        # (``train_img_per_s``): a sample here is one sequence, one document
        # of ``seq`` tokens; ``tokens`` is the same work in this family's unit
        tokens = steps * self.batch * self.seq
        return {"images": steps * self.batch, "tokens": tokens,
                "work_flops": tokens * flops_lm.token_step(self.config, self.seq)}

    def reference(self, rounding: str = "float32") -> dict:
        return reference_run(self.config, self.seed, self.first_batches, rounding)


def build(cell, *, devices, seed):
    return Driver(cell, devices=devices, seed=seed)


def limit_readings(cell, *, devices, seeds, control_seeds):
    return train_loop.limit_readings(build, cell, devices=devices, seeds=seeds,
                                     control_seeds=control_seeds, control=CONTROL)


def tiny(cell: dict) -> dict:
    """The cell cut to a size the CPU holds, its structure kept: latent q/kv
    ranks, nope ‖ rope split, 1 dense + 2 expert layers + MTP, 16 experts of
    which 4 are held, top-4, a slice of a 512-row vocabulary."""
    cell = copy.deepcopy(cell)
    cell["config"] |= {
        "hidden_size": 32, "num_hidden_layers": 3, "num_attention_heads": 2,
        "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 64,
        "moe_intermediate_size": 16, "n_routed_experts": 4, "num_experts_per_tok": 4,
        "vocab_size": 64, "experts_held": [4, 4], "vocab_rows": [64, 64],
        "published": {"num_hidden_layers": 40, "n_routed_experts": 16, "vocab_size": 512},
    }
    cell["traffic"] |= {"sequences_per_chip": 4, "seq": 24, "distinct_batches": 2,
                        "fetch_every": 2, "trace_seconds": 0.3}
    return cell


def flops_pair(config: dict) -> tuple[float, float]:
    """Forward + backward FLOPs of one token at ``FLOPS_SEQ``: the
    benchmark's own count and the program's for the same configuration."""
    from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig
    from jumbo_mae_tpu_tpu.obs.mfu import lm_flops_per_token

    program = lm_flops_per_token(MlaMoeConfig(**lm_fields(config)), FLOPS_SEQ)
    return flops_lm.token_step(config, FLOPS_SEQ), program
