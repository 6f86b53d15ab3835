"""Language-model pretraining steps, back to back, through the trainer's own
factories: the grouped-query sparse-expert family (``Laguna-XS.2``: full and
sliding-window attention in a published pattern, two head counts over shared
key/value heads) behind ``train_loop``'s loop and check.

State, optimizer, step program and device prefetch are the trainer's; the
tokens (``lm_steps.token_batches``: this family's traffic is the all-MLA
family's), the weights and the router biases are the benchmark's, from the
seed. The float32 reference (``benchmarks/reference/gqa_lm_model.py``: the
(seq, seq) scores with both masks as comparisons of positions) follows the
same first steps from the same weights, biases and tokens.

The loop's set-up, the program's configuration and the reference's three
steps are ``hybrid_lm_steps``' own functions, not a third copy of them:
``_here`` runs their code over this module's four names (``ref_shapes``,
``ref_model``, ``lm_fields``, ``LIMITS``; PERF.md §7 (f) says what a
``benchmark`` issue would move so that no such step is needed).

Every key of the configuration file is accounted for here, the one place the
sizes are stated: ``_FIELDS`` and ``_PUBLISHED`` go to the program's
``model.lm`` fields, ``_DERIVED`` are translated by a rule, ``_REQUIRED`` name
the one value the program and the reference implement (a file that says
otherwise is refused, not ignored), ``_CONSISTENT`` restate another key and
``_ABOUT`` describe the file or steer this driver.
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

from benchmarks import flops_gqa_lm as flops_family
from benchmarks.drivers import common, hybrid_lm_steps, train_loop  # noqa: F401
from benchmarks.drivers.lm_steps import token_batches  # noqa: F401
from benchmarks.drivers.train_loop import CHECK_STEPS, _leaf_sq  # noqa: F401
from benchmarks.reference import gqa_lm_model as ref_model
from benchmarks.reference import gqa_lm_params as ref_shapes
from benchmarks.reference import optim as ref_optim  # noqa: F401

LIMITS = json.loads((Path(__file__).parent / "gqa_lm_steps.limits.json").read_text())
# the committed limits are set from the chip's readings at the published
# widths; a 32-wide model's few-element leaves read noisier
TINY_LIMITS = {"loss_gap": 1e-3, "first_grad_norm_gap": 0.1, "param_change_norm_gap": 0.1}
SPANS = train_loop.SPANS
SCOPES = "gqa_lm"  # benchmarks/scopes/<name>.json: this family's parts
CONTROL = "fp8"  # e4m3, the nearest precision below the configuration's bfloat16
FLOPS_SEQ = 8192  # the sequence length flops_pair compares the two counts at
KINDS = flops_family.KINDS

# config.json's keys -> the program's models/lm.MlaMoeConfig fields
_FIELDS = {
    "hidden_size": "dim", "num_hidden_layers": "layers",
    "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
    "sliding_window": "sliding_window", "intermediate_size": "dense_hidden",
    "moe_intermediate_size": "expert_hidden",
    "shared_expert_intermediate_size": "shared_expert_hidden",
    "num_experts_per_tok": "experts_per_token",
    "moe_routed_scaling_factor": "routed_scaling_factor",
    "num_nextn_predict_layers": "mtp_layers", "rms_norm_eps": "rms_eps",
    "router_bias_rate": "router_bias_rate", "experts_held": "experts_held",
    "vocab_rows": "vocab_rows", "compute_dtype": "dtype", "grad_ckpt": "grad_ckpt",
}
# the model's own counts, beside what the chip holds of them
_PUBLISHED = {"num_experts": "n_routed_experts", "vocab_size": "vocab_size"}
# translated by a rule in ``lm_fields``: the three per-layer lists (this
# chip's layers are their first ``num_hidden_layers`` entries), the two kinds'
# rotary embeddings, the output gate
_DERIVED = {"layer_types", "num_attention_heads_per_layer", "mlp_layer_types",
            "rope_parameters", "gating"}
# the one value that is implemented
_REQUIRED = {"model_type": "laguna", "attention_bias": False, "tie_word_embeddings": False,
             "moe_apply_router_weight_on_input": False, "param_dtype": "float32",
             "num_nextn_predict_layers": 0}
# key -> what it has to equal, from the other keys
_CONSISTENT = {
    "num_attention_heads": lambda c: c["num_attention_heads_per_layer"][
        c["layer_types"].index("full_attention")],
    "partial_rotary_factor": lambda c: c["rope_parameters"]["full_attention"][
        "partial_rotary_factor"],
    "num_experts": lambda c: c["experts_held"][1],
    "vocab_size": lambda c: c["vocab_rows"][1],
}
# max_position_embeddings bounds the traffic's sequence (``Driver``); optim,
# program and published are read below; the rest is the file's own account
_ABOUT = {"name", "source", "recipe", "deployment", "published", "parameters_here", "optim",
          "program", "reduced", "reduced_why", "assumed", "max_position_embeddings"}
KEYS = set(_FIELDS) | set(_PUBLISHED) | _DERIVED | set(_REQUIRED) | set(_CONSISTENT) | _ABOUT


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
    if config["gating"] is not True:
        raise ValueError("gating: only true (the head-wise output gate) is implemented")
    rope = config["rope_parameters"]
    origin = rope["full_attention"]["original_max_position_embeddings"]
    if (set(rope) - set(KINDS) - {"original_max_position_embeddings"}
            or rope.get("original_max_position_embeddings", origin) != origin):
        raise ValueError("rope_parameters: only the two kinds' groups, and the full kind's "
                         "original length restated beside them, are implemented")
    layers = config["num_hidden_layers"]
    fields = {field: config[key] for key, field in _FIELDS.items()}
    fields |= {field: config["published"][key] for key, field in _PUBLISHED.items()}
    return fields | {
        "layer_types": config["layer_types"][:layers],
        "heads_per_layer": config["num_attention_heads_per_layer"][:layers],
        "first_k_dense": ref_shapes.dense_layers(config),
        "rope_parameters": {kind: config["rope_parameters"][kind] for kind in KINDS},
        "attn_gate": True,
        "n_shared_experts": 1,
    }


def _here(fn):
    """``fn`` of ``hybrid_lm_steps`` with its module-level names looked up in
    this module: that family's code to the letter, over this family's shapes,
    reference, field translation and limits."""
    return types.FunctionType(fn.__code__, globals(), fn.__name__, fn.__defaults__,
                              fn.__closure__)


program_config = _here(hybrid_lm_steps.program_config)
reference_run = _here(hybrid_lm_steps.reference_run)


class Driver(train_loop.Loop):
    __init__ = _here(hybrid_lm_steps.Driver.__init__)

    def _one_step(self):
        metrics, wait = super()._one_step()
        self._counters.append({k: metrics[k] for k in (
            "moe_imbalance", "moe_held_share", "moe_dropped")})
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
        # the program's static count of what its kernels' block tables walk
        pairs = {kind: {"visited": visited, "needed": needed} for kind, (visited, needed)
                 in MlaMoeConfig(**lm_fields(self.config)).attn_pairs(self.seq).items()}
        print(f"counters over {len(steps)} steps: {json.dumps({'moe': moe, 'attn_pairs': pairs})}",
              flush=True)
        for kind in KINDS:  # the program's mask keeps what the yardstick counts
            assert pairs[kind]["needed"] == flops_family.needed_pairs(self.config, kind, self.seq)
        rows = moe["held_share"] * self.batch * self.seq * self.config["num_experts_per_tok"]
        work = {"attn_core": flops_family.causal_core_step(self.config, self.batch, self.seq),
                "swa_core": flops_family.swa_core_step(self.config, self.batch, self.seq),
                "experts": flops_family.experts_step(self.config, rows)}
        return record | {"moe": moe, "attn_pairs": pairs, "kernel_work": {
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
    two layers (the dense full-attention layer and a sliding-window layer
    with experts: one of each kind, since the CPU compiles every layer of the
    unrolled step and a further layer of a kind is the first again) of 6 and
    8 query heads over 2 key/value heads, a window of 11 tokens that is
    smaller than the 24 of a sequence, partial + YaRN rope on the full kind
    (theta cut to 100, its original length to 128 and beta_fast to 4, so that
    the blend, low 1 to high 3, falls among the 4 pair frequencies and turns
    the slower two by a radian less over 24 positions), 16 experts top-4 of
    which 4 are held, a slice of a 512-row vocabulary."""
    cell = copy.deepcopy(cell)
    rope = cell["config"]["rope_parameters"]
    cell["config"] |= {
        "num_hidden_layers": 2, "hidden_size": 32, "num_attention_heads": 6,
        "num_key_value_heads": 2, "head_dim": 16,
        "num_attention_heads_per_layer": [6, 8, 8, 8] * 10, "sliding_window": 11,
        "rope_parameters": rope | {"original_max_position_embeddings": 128, "full_attention": rope[
            "full_attention"] | {"rope_theta": 100, "original_max_position_embeddings": 128,
                                 "beta_fast": 4}},
        "intermediate_size": 64, "moe_intermediate_size": 16,
        "shared_expert_intermediate_size": 16, "num_experts": 4, "num_experts_per_tok": 4,
        "vocab_size": 64, "experts_held": [4, 4], "vocab_rows": [64, 64],
        "published": {"num_hidden_layers": 40, "num_experts": 16, "vocab_size": 512},
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
