"""Language-model pretraining steps, back to back, through the trainer's own
factories: the hybrid linear-attention / latent-attention sparse-expert
family (``Ling-3.0-flash``) behind ``train_loop``'s loop and check.

State, optimizer, step program and device prefetch are the trainer's
(``cli.train.build_model``, ``train.make_optimizer``, ``create_sharded_state``,
``make_train_step`` in mode ``lm``, ``data.loader.prefetch_to_device``); the
tokens (``lm_steps.token_batches``: this family's traffic is the all-MLA
family's), the weights and the router biases are the benchmark's, from the
seed. The float32 reference (``benchmarks/reference/hybrid_lm_model.py``: KDA
as the token-by-token recurrence) follows the same first steps from the same
weights, biases and tokens, its biases moved by the same rule.

Every key of the configuration file is accounted for here, the one place the
sizes are stated: ``_FIELDS`` and ``_PUBLISHED`` go to the program's
``model.lm`` fields, ``_DERIVED`` are translated by a rule, ``_REQUIRED`` name
the one value the program and the reference implement (a file that says
otherwise is refused, not ignored), ``_CONSISTENT`` restate another key,
``_INERT`` have no effect as published (the file's ``assumed`` says why) and
``_ABOUT`` describe the file or steer this driver.
"""

from __future__ import annotations

import copy
import itertools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_hybrid_lm as flops_family
from benchmarks.drivers import common, train_loop
from benchmarks.drivers.lm_steps import token_batches
from benchmarks.drivers.train_loop import CHECK_STEPS, _leaf_sq
from benchmarks.reference import hybrid_lm_model as ref_model
from benchmarks.reference import hybrid_lm_params as ref_shapes
from benchmarks.reference import optim as ref_optim

LIMITS = json.loads((Path(__file__).parent / "hybrid_lm_steps.limits.json").read_text())
# the committed limits are set from the chip's readings at the published
# widths; a 32-wide model's few-element leaves read noisier
TINY_LIMITS = {"loss_gap": 1e-3, "first_grad_norm_gap": 0.1, "param_change_norm_gap": 0.1}
SPANS = train_loop.SPANS
SCOPES = "hybrid_lm"  # benchmarks/scopes/<name>.json: this family's parts
CONTROL = "fp8"  # e4m3, the nearest precision below the configuration's bfloat16
FLOPS_SEQ = 8192  # the sequence length flops_pair compares the two counts at

# config.json's keys -> the program's models/lm.MlaMoeConfig fields
_FIELDS = {
    "hidden_size": "dim", "num_hidden_layers": "layers",
    "first_k_dense_replace": "first_k_dense", "layer_group_size": "layer_group_size",
    "num_attention_heads": "heads", "head_dim": "kda_head_dim",
    "short_conv_kernel_size": "kda_conv", "kda_lower_bound": "kda_lower_bound",
    "kda_chunk": "kda_chunk", "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "intermediate_size": "dense_hidden",
    "moe_intermediate_size": "expert_hidden",
    "moe_shared_expert_intermediate_size": "shared_expert_hidden",
    "num_shared_experts": "n_shared_experts", "num_experts_per_tok": "experts_per_token",
    "n_group": "n_group", "topk_group": "topk_group",
    "routed_scaling_factor": "routed_scaling_factor",
    "num_nextn_predict_layers": "mtp_layers", "mtp_loss_scaling_factor": "mtp_loss_weight",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "router_bias_rate": "router_bias_rate", "experts_held": "experts_held",
    "vocab_rows": "vocab_rows", "compute_dtype": "dtype", "grad_ckpt": "grad_ckpt",
}
# the model's own counts, beside what the chip holds of them
_PUBLISHED = {"num_experts": "n_routed_experts", "vocab_size": "vocab_size"}
# translated by a rule in ``lm_fields``
_DERIVED = {"gated_attention_proj_granularity_type", "expert_swiglu_limit_list",
            "share_expert_swiglu_limit_list"}
# the one value that is implemented
_REQUIRED = {
    "model_type": "bailing_hybrid", "hidden_act": "silu", "kda_safe_gate": True,
    "linear_silu": True, "no_kda_lora": True, "use_kda_lora": False, "use_qk_norm": True,
    "value_norm": False, "group_norm_size": 1, "num_kv_heads_for_linear_attn": 0,
    "mtp_use_kda": False, "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
    "score_function": "sigmoid", "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "scale_router_input": False, "rope_interleave": True, "rope_scaling": None,
    "use_mla_nope": False, "use_bias": False, "use_qkv_bias": False, "use_nGPT": False,
    "up_proj_norm": False, "tie_word_embeddings": False, "q_lora_rank": None,
    "param_dtype": "float32",
}
# key -> what it has to equal, from the other keys
_CONSISTENT = {
    "num_key_value_heads": lambda c: c["num_attention_heads"],
    "qk_head_dim": lambda c: c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
    "rotary_dim": lambda c: c["qk_rope_head_dim"],
    "partial_rotary_factor": lambda c: c["rotary_dim"] / c["head_dim"],
    "num_experts": lambda c: c["experts_held"][1],
    "vocab_size": lambda c: c["vocab_rows"][1],
}
_INERT = {"max_window_layers", "seq_aux"}
# max_position_embeddings bounds the traffic's sequence (``Driver``); optim,
# program and published are read below; the rest is the file's own account
_ABOUT = {"name", "source", "recipe", "deployment", "published", "parameters_here", "optim",
          "program", "reduced", "reduced_why", "assumed", "max_position_embeddings"}
KEYS = (set(_FIELDS) | set(_PUBLISHED) | _DERIVED | set(_REQUIRED) | set(_CONSISTENT) | _INERT
        | _ABOUT)


def lm_fields(config: dict) -> dict:
    """The program's ``model.lm`` section from the configuration file; a key
    this driver has no account of, a value that is not implemented or two
    keys that contradict each other refuse the run."""
    unknown = set(config) - KEYS
    if unknown:
        raise ValueError(f"configuration keys the driver has no account of: {sorted(unknown)}")
    for key, want in _REQUIRED.items():
        if config[key] != want:
            raise ValueError(f"{key} = {config[key]!r}: only {want!r} is implemented")
    for key, rule in _CONSISTENT.items():
        if config[key] != rule(config):
            raise ValueError(f"{key} = {config[key]!r} contradicts {rule(config)!r}")
    if config["gated_attention_proj_granularity_type"] != "head_wise":
        raise ValueError("only the head-wise output gate is implemented")
    layers = config["num_hidden_layers"]
    fields = {field: config[key] for key, field in _FIELDS.items()}
    fields |= {field: config["published"][key] for key, field in _PUBLISHED.items()}
    return fields | {
        "attn_gate": True,
        # a clamped layer among those held is refused by the program's config
        "expert_swiglu_limit": max(config["expert_swiglu_limit_list"][:layers]),
        "shared_expert_swiglu_limit": max(config["share_expert_swiglu_limit_list"][:layers]),
    }


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


def reference_run(config: dict, seed: int, batches, rounding: str = "float32") -> dict:
    """The reference's losses, first-gradient norms and change norms over
    ``len(batches)`` steps from the seed's weights and biases."""
    seed = common.seed32(seed)
    with jax.default_matmul_precision("highest"):
        loss_grad = jax.jit(jax.value_and_grad(
            lambda p, b, t: ref_model.batch_loss(p, b, t, config, rounding), has_aux=True))
        move = jax.jit(lambda b, n: ref_model.next_biases(b, n, config["router_bias_rate"]))
        change_sq = jax.jit(lambda p, s: _leaf_sq(jax.tree_util.tree_map(
            jnp.subtract, p, ref_shapes.make_params(s, config))))
        in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
        print(f"reference ({rounding}): {in_use / 1e9:.2f} GB in use on the device "
              "before it starts", flush=True)
        params = jax.jit(lambda s: ref_shapes.make_params(s, config))(seed)
        biases = jax.jit(lambda s: ref_shapes.make_biases(s, config))(seed)
        # Adam's moments wait on the host between steps (``lm_steps``): beside
        # them a float32 backward pass at 8192 tokens does not fit
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
        if self.seq > config["max_position_embeddings"]:
            raise ValueError(f"{self.seq} positions exceed max_position_embeddings")
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
        common.require_same_tree(state.params, ref_shapes.shapes(config), "language-model state")
        common.require_same_tree(state.batch_stats, ref_shapes.bias_shapes(config),
                                 "router biases")

        # the state object is the trainer's; its weights and biases are the
        # benchmark's. The trainer's own init is freed first, so that the
        # peak the run reports is the step's and not two states side by side.
        template = jax.eval_shape(lambda: state)
        jax.tree_util.tree_map(lambda x: x.delete(), state)

        def seeded(s):
            # the seed enters as a traced value only, so that every seed
            # finds the same programs in the compile cache
            params = ref_shapes.make_params(s, config)
            rng = jax.random.fold_in(jax.random.key(s), jax.process_index())
            return template.replace(step=jnp.zeros((), jnp.int32), params=params,
                                    opt_state=tx.init(params), rng=rng,
                                    batch_stats=ref_shapes.make_biases(s, config))

        self._delta_sq = jax.jit(lambda params, s: _leaf_sq(jax.tree_util.tree_map(
            jnp.subtract, params, ref_shapes.make_params(s, config))))
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
        self._counters = []  # the window's steps' counters, on the device

    def _one_step(self):
        metrics, wait = super()._one_step()
        self._counters.append({k: metrics[k] for k in (
            "moe_imbalance", "moe_held_share", "moe_dropped", "kda_state_absmax",
            "kda_decay_mean")})
        return metrics, wait

    def window(self, seconds: float, seed: int) -> dict:
        self._counters = []
        record = super().window(seconds, seed)
        # the loop has fetched the loss already; the counters of its steps
        # are a few device scalars, read after the window has closed
        steps = jax.device_get(self._counters)
        over = lambda how, key: float(how([s[key] for s in steps]))
        moe = {"imbalance": over(np.mean, "moe_imbalance"),
               "held_share": over(np.mean, "moe_held_share"),
               "dropped": over(np.sum, "moe_dropped")}
        kda = {"state_absmax": over(np.max, "kda_state_absmax"),
               "decay_mean": over(np.mean, "kda_decay_mean")}
        print(f"counters over {len(steps)} steps: {json.dumps({'moe': moe, 'kda': kda})}",
              flush=True)
        rows = moe["held_share"] * self.batch * self.seq * self.config["num_experts_per_tok"]
        work = {"attn_core": flops_family.causal_core_step(self.config, self.batch, self.seq),
                "experts": flops_family.experts_step(self.config, rows),
                "kda_core": flops_family.kda_core_step(self.config, self.batch, self.seq)}
        return record | {"moe": moe, "kda": kda, "kernel_work": {
            name: {"flops": f, "bytes": b} for name, (f, b) in work.items()}}

    def work(self, steps: int) -> dict:
        # a sample is one sequence, as in the all-MLA family's cell (``lm_steps``)
        tokens = steps * self.batch * self.seq
        return {"images": steps * self.batch, "tokens": tokens,
                "work_flops": tokens * flops_family.token_step(self.config, self.seq)}

    def reference(self, rounding: str = "float32") -> dict:
        return reference_run(self.config, self.seed, self.first_batches, rounding)


def build(cell, *, devices, seed):
    return Driver(cell, devices=devices, seed=seed)


def limit_readings(cell, *, devices, seeds, control_seeds):
    return train_loop.limit_readings(build, cell, devices=devices, seeds=seeds,
                                     control_seeds=control_seeds, control=CONTROL)


def tiny(cell: dict) -> dict:
    """The cell cut to a size the CPU holds, its structure kept: a dense KDA
    block, four KDA expert blocks and the MLA expert block of one period of
    6 — the real cut's pattern but for its last block — 16 experts in 4
    groups of which 2 stay, top-4, 4 held, a slice of a 512-row vocabulary;
    chunks of 8 positions, so that 24 tokens cross two chunk boundaries."""
    cell = copy.deepcopy(cell)
    cell["config"] |= {
        "hidden_size": 32, "num_hidden_layers": 6, "num_attention_heads": 2, "head_dim": 16,
        "num_key_value_heads": 2, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "qk_head_dim": 24, "rotary_dim": 8, "v_head_dim": 16,
        "intermediate_size": 64, "moe_intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 16, "num_experts": 4, "num_experts_per_tok": 4,
        "n_group": 4, "topk_group": 2, "vocab_size": 64, "experts_held": [4, 4],
        "vocab_rows": [64, 64], "kda_chunk": 8,
        "published": {"num_hidden_layers": 42, "num_experts": 16, "vocab_size": 512},
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
    return flops_family.token_step(config, FLOPS_SEQ), program
