"""Language-model pretraining steps, back to back, through the trainer's own
factories: the linear-attention / grouped-query sparse-expert family
(``Solar-Open2-250B``: Kimi delta attention with a softplus decay gate and
``beta`` up to 2 three layers in four, rope-free gated grouped-query
attention the fourth, and a share of each layer's heads) behind
``train_loop``'s loop and check.

State, optimizer, step program and device prefetch are the trainer's; the
tokens (``lm_steps.token_batches``: this family's traffic is the all-MLA
family's), the weights and the router biases are the benchmark's, from the
seed. The float32 reference (``benchmarks/reference/kda_gqa_lm_model.py``:
the delta rule position by position, the (seq, seq) scores without rotation)
follows the same first steps from the same weights, biases and tokens.

The loop's set-up, the program's configuration and the reference's three
steps are ``hybrid_lm_steps``' own functions, as in ``gqa_lm_steps``:
``_here`` runs their code over this module's four names (``ref_shapes``,
``ref_model``, ``lm_fields``, ``LIMITS``; PERF.md §7 (f)).

Every key of the configuration file is accounted for here, the one place the
sizes are stated: ``_FIELDS`` and ``_PUBLISHED`` go to the program's
``model.lm`` fields, ``_DERIVED`` are translated by a rule, ``_REQUIRED`` name
the one value the program's translation and the reference implement (a file
that says otherwise is refused, not ignored), ``_CONSISTENT`` restate another
key, ``_INERT`` have no effect as published (the file's ``assumed`` says why)
and ``_ABOUT`` describe the file or steer this driver.
"""

from __future__ import annotations

# every module-level name ``hybrid_lm_steps``' three functions read is
# imported here under the same name (``_here``), used below or not
import copy
import itertools  # noqa: F401
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp  # noqa: F401
import numpy as np

from benchmarks import flops_kda_gqa_lm as flops_family
from benchmarks.drivers import common, hybrid_lm_steps, train_loop  # noqa: F401
from benchmarks.drivers.lm_steps import token_batches  # noqa: F401
from benchmarks.drivers.train_loop import CHECK_STEPS, _leaf_sq  # noqa: F401
from benchmarks.reference import kda_gqa_lm_model as ref_model
from benchmarks.reference import kda_gqa_lm_params as ref_shapes
from benchmarks.reference import optim as ref_optim  # noqa: F401

LIMITS = json.loads((Path(__file__).parent / "kda_gqa_lm_steps.limits.json").read_text())
# the committed limits are set from the chip's readings at the published
# widths; a 32-wide model's few-element leaves read noisier
TINY_LIMITS = {"loss_gap": 1e-3, "first_grad_norm_gap": 0.1, "param_change_norm_gap": 0.1}
SPANS = train_loop.SPANS
SCOPES = "kda_gqa_lm"  # benchmarks/scopes/<name>.json: this family's parts
CONTROL = "fp8"  # e4m3, the nearest precision below the configuration's bfloat16
FLOPS_SEQ = 8192  # the sequence length flops_pair compares the two counts at
COUNTERS = ("moe_imbalance", "moe_held_share", "moe_dropped", "kda_state_absmax",
            "kda_decay_mean", "kda_beta_max", "kda_neg_eig_share")

# config.json's keys -> the program's models/lm.MlaMoeConfig fields
_FIELDS = {
    "hidden_size": "dim", "num_hidden_layers": "layers",
    "first_k_dense_replace": "first_k_dense", "num_attention_heads": "heads",
    "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
    "intermediate_size": "dense_hidden", "moe_intermediate_size": "expert_hidden",
    "n_shared_experts": "n_shared_experts", "num_experts_per_tok": "experts_per_token",
    "routed_scaling_factor": "routed_scaling_factor",
    "num_nextn_predict_layers": "mtp_layers", "rms_norm_eps": "rms_eps",
    "router_bias_rate": "router_bias_rate", "experts_held": "experts_held",
    "vocab_rows": "vocab_rows", "kda_chunk": "kda_chunk", "compute_dtype": "dtype",
    "grad_ckpt": "grad_ckpt",
}
# the model's own counts, beside what the chip holds of them
_PUBLISHED = {"n_routed_experts": "n_routed_experts", "vocab_size": "vocab_size"}
# translated by a rule in ``lm_fields``: which layers are grouped-query, the
# linear layers' group of sizes
_DERIVED = {"gqa_layers", "linear_attn_config"}
# the one value that is implemented
_REQUIRED = {
    "model_type": "solar_open2", "use_rope": False, "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "norm_topk_prob": True,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 0, "param_dtype": "float32",
}
# key -> what it has to equal, from the other keys
_CONSISTENT = {
    "gqa_interval": lambda c: c["gqa_layers"][1] - c["gqa_layers"][0] - 1,
    "n_routed_experts": lambda c: c["experts_held"][1],
    "vocab_size": lambda c: c["vocab_rows"][1],
    "heads_held": lambda c: c["heads_held"] | {
        "kda": [c["heads_held"]["kda"][0], c["linear_attn_config"]["num_heads"]],
        "query": [c["heads_held"]["query"][0], c["num_attention_heads"]],
        "key_value": [c["heads_held"]["key_value"][0], c["num_key_value_heads"]]},
}
_INERT = {"partial_rotary_factor", "rope_theta"}  # use_rope false: nothing is rotated
# max_position_embeddings bounds the traffic's sequence (``Driver``); optim,
# program and published are read below; the rest is the file's own account
_ABOUT = {"name", "source", "recipe", "deployment", "published", "parameters_here", "ladder",
          "optim", "program", "reduced", "reduced_why", "assumed", "max_position_embeddings"}
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
    linear, published = config["linear_attn_config"], config["published"]
    if linear["num_kv_heads"] is not None:
        raise ValueError("linear_attn_config.num_kv_heads: only null (as many key and value "
                         "heads as query heads) is implemented")
    fields = {field: config[key] for key, field in _FIELDS.items()}
    fields |= {field: published[key] for key, field in _PUBLISHED.items()}
    return fields | {
        "layer_types": ["kda" if ref_shapes.is_linear(config, i) else "full_attention"
                        for i in range(config["num_hidden_layers"])],
        "rope_parameters": {"full_attention": None},  # use_rope false
        "attn_gate": True,  # use_gqa_gate
        "heads_published": {"full_attention": published["num_attention_heads"],
                            "kda": published["linear_attn_config"]["num_heads"]},
        "kda_heads": linear["num_heads"], "kda_head_dim": linear["head_dim"],
        "kda_conv": linear["short_conv_kernel_size"],
        "kda_gate": "softplus",  # no safe-gate key: no floor
        "kda_beta_scale": 2.0,  # kda_allow_neg_eigval
        "kda_gate_rank": linear["head_dim"],  # kda_use_full_proj false
        "kda_out_gate": "element",
    }


def _here(fn):
    """``fn`` of ``hybrid_lm_steps`` with its module-level names looked up in
    this module (``gqa_lm_steps._here``, over this module's names)."""
    return types.FunctionType(fn.__code__, globals(), fn.__name__, fn.__defaults__,
                              fn.__closure__)


program_config = _here(hybrid_lm_steps.program_config)
reference_run = _here(hybrid_lm_steps.reference_run)


class Driver(train_loop.Loop):
    __init__ = _here(hybrid_lm_steps.Driver.__init__)

    def _one_step(self):
        metrics, wait = super()._one_step()
        self._counters.append({k: metrics[k] for k in COUNTERS})
        return metrics, wait

    def window(self, seconds: float, seed: int) -> dict:
        from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig

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
               "decay_mean": over(np.mean, "kda_decay_mean"),
               "beta_max": over(np.max, "kda_beta_max"),
               "neg_eig_share": over(np.mean, "kda_neg_eig_share")}
        # the program's static count of the heads it holds of each kind
        heads = {kind: {"held": held, "published": published} for kind, (held, published)
                 in MlaMoeConfig(**lm_fields(self.config)).attn_heads().items()}
        print(f"counters over {len(steps)} steps: "
              f"{json.dumps({'moe': moe, 'kda': kda, 'attn_heads': heads})}", flush=True)
        rows = moe["held_share"] * self.batch * self.seq * self.config["num_experts_per_tok"]
        work = {"attn_core": flops_family.causal_core_step(self.config, self.batch, self.seq),
                "experts": flops_family.experts_step(self.config, rows),
                "kda_core": flops_family.kda_core_step(self.config, self.batch, self.seq)}
        return record | {"moe": moe, "kda": kda, "attn_heads": heads, "kernel_work": {
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
    """The cell cut to a size the CPU holds, its structure kept: the first
    two layers (the grouped-query layer and a KDA layer, both with experts:
    one of each kind, since the CPU compiles every layer of the unrolled step
    and a further KDA layer is the second again), 4 query heads over 2
    key/value heads and 2 KDA heads of 16 (the gates through a rank of 16),
    16 experts top-4 of which 4 are held, a slice of a 512-row vocabulary;
    chunks of 8 positions, so that 24 tokens cross two chunk boundaries."""
    cell = copy.deepcopy(cell)
    config = cell["config"]
    linear = config["linear_attn_config"] | {"head_dim": 16, "num_heads": 2}
    config |= {
        "num_hidden_layers": 2, "hidden_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "linear_attn_config": linear,
        "heads_held": config["heads_held"] | {"kda": [0, 2], "query": [0, 4], "key_value": [0, 2]},
        "intermediate_size": 64, "moe_intermediate_size": 16, "n_routed_experts": 4,
        "num_experts_per_tok": 4, "vocab_size": 64, "experts_held": [4, 4],
        "vocab_rows": [64, 64], "kda_chunk": 8,
        "published": config["published"] | {
            "num_hidden_layers": 48, "n_routed_experts": 16, "vocab_size": 512,
            "num_attention_heads": 16, "num_key_value_heads": 8,
            "linear_attn_config": linear | {"num_heads": 8}},
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
