"""Language-model pretraining steps, back to back, through the trainer's own
factories: the short-convolution / grouped-query sparse-expert family
(``LFM2-24B-A2B``: a gated short-convolution mixer three layers in four,
grouped-query attention with 64-wide heads and per-head q/k norms in the
fourth, a dense first layer, 64 sigmoid-routed experts top-4 with a balancing
bias and no shared expert, a head tied to the embedding) behind
``train_loop``'s loop and check.

State, optimizer, step program and device prefetch are the trainer's; the
tokens (``lm_steps.token_batches``: this family's traffic is the all-MLA
family's), the weights and the router biases are the benchmark's, from the
seed. The float32 reference (``benchmarks/reference/conv_moe_lm_model.py``:
the convolution as three shifted products, every visible score a group of
heads and a block of query rows at a time) follows the same first steps from
the same weights, biases and tokens.

The loop's set-up, the program's configuration and the reference's three
steps are ``hybrid_lm_steps``' own functions, as in ``gqa_lm_steps``:
``_here`` runs their code over this module's names (``ref_shapes``,
``ref_model``, ``lm_fields``, ``LIMITS``; PERF.md §7 (f)).

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

from benchmarks import flops_conv_moe_lm as flops_family
from benchmarks.drivers import common, hybrid_lm_steps, train_loop  # noqa: F401
from benchmarks.drivers.lm_steps import token_batches  # noqa: F401
from benchmarks.drivers.train_loop import CHECK_STEPS, _leaf_sq  # noqa: F401
from benchmarks.reference import conv_moe_lm_model as ref_model
from benchmarks.reference import conv_moe_lm_params as ref_shapes
from benchmarks.reference import optim as ref_optim  # noqa: F401

LIMITS = json.loads((Path(__file__).parent / "conv_moe_lm_steps.limits.json").read_text())
# the committed limits are set from the chip's readings at the published
# widths; a 32-wide model's few-element leaves read noisier
TINY_LIMITS = {"loss_gap": 1e-3, "first_grad_norm_gap": 0.1, "param_change_norm_gap": 0.1}
SPANS = train_loop.SPANS
SCOPES = "conv_moe_lm"  # benchmarks/scopes/<name>.json: this family's parts
CONTROL = "fp8"  # e4m3, the nearest precision below the configuration's bfloat16
# the control in one block alone, on the control seeds too: the first block of
# each kind (block 0: conv, dense; block 1: full attention, experts)
ONE_BLOCK_CONTROLS = ("fp8@0", "fp8@1")
FLOPS_SEQ = 8192  # the sequence length flops_pair compares the two counts at
KINDS = flops_family.KINDS
COUNTERS = ("moe_imbalance", "moe_held_share", "moe_dropped")

# config.json's keys -> the program's models/lm.MlaMoeConfig fields
_FIELDS = {
    "hidden_size": "dim", "num_hidden_layers": "layers", "num_dense_layers": "first_k_dense",
    "num_attention_heads": "heads", "num_key_value_heads": "kv_heads",
    "conv_L_cache": "conv_taps", "intermediate_size": "dense_hidden",
    "moe_intermediate_size": "expert_hidden", "num_experts_per_tok": "experts_per_token",
    "routed_scaling_factor": "routed_scaling_factor",
    "num_nextn_predict_layers": "mtp_layers", "norm_eps": "rms_eps",
    "router_bias_rate": "router_bias_rate", "experts_held": "experts_held",
    "vocab_rows": "vocab_rows", "compute_dtype": "dtype", "grad_ckpt": "grad_ckpt",
}
# the model's own counts, beside what the chip holds of them
_PUBLISHED = {"num_experts": "n_routed_experts", "vocab_size": "vocab_size"}
# translated by a rule in ``lm_fields``: the published list of kinds (this
# chip's layers are its entries from ``first_layer``), the rotary embedding,
# the head's width (the config has no head_dim key: hidden / heads)
_DERIVED = {"layer_types", "first_layer", "rope_parameters"}
# the one value that is implemented
_REQUIRED = {"model_type": "lfm2_moe", "conv_bias": False, "norm_topk_prob": True,
             "use_expert_bias": True, "tie_word_embeddings": True, "param_dtype": "float32",
             "num_nextn_predict_layers": 0}
# key -> what it has to equal, from the other keys
_CONSISTENT = {
    "num_experts": lambda c: c["experts_held"][1],
    "vocab_size": lambda c: c["vocab_rows"][1],
    "first_layer": lambda c: c["published"]["num_dense_layers"] - c["num_dense_layers"],
}
# max_position_embeddings bounds the traffic's sequence (``Driver``); optim,
# program and published are read below; the rest is the file's own account
_ABOUT = {"name", "source", "recipe", "deployment", "published", "parameters_here", "ladder",
          "optim", "program", "reduced", "reduced_why", "assumed", "max_position_embeddings"}
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
    rope = config["rope_parameters"]
    if set(rope) != {"rope_theta", "rope_type"} or rope["rope_type"] != "default":
        raise ValueError("rope_parameters: only rope_theta with rope_type default is implemented")
    fields = {field: config[key] for key, field in _FIELDS.items()}
    fields |= {field: config["published"][key] for key, field in _PUBLISHED.items()}
    return fields | {
        "layer_types": ref_shapes.kinds(config),
        "head_dim": ref_shapes.head_dim(config),
        # every dimension of a head turns, dimension j with j + head_dim / 2
        "rope_parameters": {"full_attention": rope | {"partial_rotary_factor": 1}},
        "qk_norm": True,
        "tie_embeddings": True,
        "attn_gate": False,
        "n_shared_experts": 0,
        "router_scoring": "sigmoid_bias",  # use_expert_bias, norm_topk_prob
        "router_input": "ffn_norm",
        "expert_act": "silu",
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
        # only held experts reach the loss, so the first steps may turn the
        # routers towards them (PERF.md §6, PR 38): the window's first and last
        drift = [float(steps[at]["moe_held_share"]) for at in (0, -1)]
        # the program's static records: what its kernels' block tables walk,
        # the heads a kind holds, the blocks of each mixer kind
        lm = MlaMoeConfig(**lm_fields(self.config))
        pairs = {kind: {"visited": visited, "needed": needed}
                 for kind, (visited, needed) in lm.attn_pairs(self.seq).items()}
        heads = {kind: {"held": held, "published": published}
                 for kind, (held, published) in lm.attn_heads().items()}
        static = {"attn_pairs": pairs, "attn_heads": heads,
                  "layers_by_kind": lm.layers_by_kind}
        print(f"counters over {len(steps)} steps: "
              f"{json.dumps({'moe': moe, 'held_share_first_last': drift} | static)}", flush=True)
        assert set(pairs) == {"full_attention"}  # a conv layer has no pairs
        assert pairs["full_attention"]["needed"] == flops_family.needed_pairs(
            self.config, "full_attention", self.seq)
        rows = moe["held_share"] * self.batch * self.seq * self.config["num_experts_per_tok"]
        work = {"attn_core": flops_family.causal_core_step(self.config, self.batch, self.seq),
                "sconv_mix": flops_family.sconv_mix_step(self.config, self.batch, self.seq),
                "experts": flops_family.experts_step(self.config, rows)}
        return record | {"moe": moe} | static | {"kernel_work": {
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
    """``train_loop.limit_readings`` and, on the control seeds, the control in
    one block alone (``ONE_BLOCK_CONTROLS``), kind ``control@<block>``."""
    for seed in seeds:
        driver = build(cell, devices=devices, seed=seed)
        driver.warm()
        readings = driver.readings
        driver.close()
        ref = driver.reference()
        yield {"seed": seed, "kind": "sound",
               "checks": train_loop.compare(readings, ref, driver.limits)}
        if seed in control_seeds:
            for rounding in (CONTROL, *ONE_BLOCK_CONTROLS):
                low = driver.reference(rounding)
                yield {"seed": seed, "kind": "control" + rounding[len(CONTROL):],
                       "checks": train_loop.compare(low, ref, driver.limits)}


def tiny(cell: dict) -> dict:
    """The cell cut to a size the CPU holds, its structure kept: published
    layers 1-2 (the dense conv layer and the attention layer with experts: one
    block of each mixer kind and of each feed-forward layer, since the CPU
    compiles every layer of the unrolled step and a further conv layer is the
    first again), 4 query heads over 2 key/value heads of 8 (the group of 2;
    q/k norms over 8), rope theta cut to 100 so that 24 positions turn the
    slow pairs too, 16 experts top-4 of which 4 are held, a slice of a 512-row
    vocabulary, tied."""
    cell = copy.deepcopy(cell)
    cell["config"] |= {
        "num_hidden_layers": 2, "hidden_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 64, "moe_intermediate_size": 16,
        "rope_parameters": {"rope_theta": 100, "rope_type": "default"},
        "num_experts": 4, "vocab_size": 64, "experts_held": [4, 4], "vocab_rows": [64, 64],
        "published": cell["config"]["published"] | {
            "num_hidden_layers": 40, "num_experts": 16, "vocab_size": 512},
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
