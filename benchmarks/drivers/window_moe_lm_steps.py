"""Language-model pretraining steps, back to back, through the trainer's own
factories: the window / rope-free-full grouped-query sparse-expert family
(``SmallThinker-21BA3B-Instruct``: one full-attention layer without rotary
embedding to three sliding-window layers with it, a group of 7 query heads a
key/value head, a router that reads the block's input before attention and
weighs its chosen experts by a softmax over their logits, ReGLU experts, no
shared expert) behind ``train_loop``'s loop and check.

State, optimizer, step program and device prefetch are the trainer's; the
tokens (``lm_steps.token_batches``: this family's generator is the all-MLA
family's, at one 16 384-token row a step) and the weights are the
benchmark's, from the seed. The family has no router bias and so no
non-gradient state: the trainer's ``batch_stats`` is None, and the float32
reference (``benchmarks/reference/window_moe_lm_model.py``: every visible
score, a group of heads and a block of query rows at a time) follows the same
first steps from the same weights and tokens with nothing to move between them.

The loop's set-up and the program's configuration are ``hybrid_lm_steps``'
own functions, as in ``gqa_lm_steps``: ``_here`` runs their code over this
module's names (``ref_shapes``, ``lm_fields``, ``LIMITS``; PERF.md §7 (f));
``reference_run`` is this module's, since it carries no biases.

Every key of the configuration file is accounted for here, the one place the
sizes are stated: ``_FIELDS`` and ``_PUBLISHED`` go to the program's
``model.lm`` fields, ``_DERIVED`` are translated by a rule, ``_REQUIRED`` name
the one value the program's translation and the reference implement (a file
that says otherwise is refused, not ignored), ``_CONSISTENT`` restate another
key and ``_ABOUT`` describe the file or steer this driver.
"""

from __future__ import annotations

# every module-level name ``hybrid_lm_steps``' two functions read is imported
# here under the same name (``_here``), used below or not
import copy
import itertools  # noqa: F401
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_window_moe_lm as flops_family
from benchmarks.drivers import common, hybrid_lm_steps, train_loop
from benchmarks.drivers.lm_steps import token_batches  # noqa: F401
from benchmarks.drivers.train_loop import CHECK_STEPS, _leaf_sq  # noqa: F401
from benchmarks.reference import optim as ref_optim
from benchmarks.reference import window_moe_lm_model as ref_model
from benchmarks.reference import window_moe_lm_params as ref_shapes

LIMITS = json.loads((Path(__file__).parent / "window_moe_lm_steps.limits.json").read_text())
# the committed limits are set from the chip's readings at the published
# widths; a 32-wide model's few-element leaves read noisier
TINY_LIMITS = {"loss_gap": 1e-3, "first_grad_norm_gap": 0.1, "param_change_norm_gap": 0.1}
SPANS = train_loop.SPANS
SCOPES = "window_moe_lm"  # benchmarks/scopes/<name>.json: this family's parts
CONTROL = "fp8"  # e4m3, the nearest precision below the configuration's bfloat16
FLOPS_SEQ = 16384  # the sequence length flops_pair compares the two counts at
KINDS = flops_family.KINDS
COUNTERS = ("moe_imbalance", "moe_held_share", "moe_dropped", "moe_act_zero_share")

# config.json's keys -> the program's models/lm.MlaMoeConfig fields
_FIELDS = {
    "hidden_size": "dim", "num_hidden_layers": "layers", "num_attention_heads": "heads",
    "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
    "sliding_window_size": "sliding_window", "moe_ffn_hidden_size": "expert_hidden",
    "moe_num_active_primary_experts": "experts_per_token",
    "num_nextn_predict_layers": "mtp_layers", "rms_norm_eps": "rms_eps",
    "experts_held": "experts_held", "vocab_rows": "vocab_rows", "compute_dtype": "dtype",
    "grad_ckpt": "grad_ckpt", "embedding_init_std": "embed_init_std",
}
# the model's own counts, beside what the chip holds of them
_PUBLISHED = {"moe_num_primary_experts": "n_routed_experts", "vocab_size": "vocab_size"}
# translated by a rule in ``lm_fields``: which layers see a window (this
# chip's layers are the list's first ``num_hidden_layers`` entries), which
# are rotated and by what
_DERIVED = {"sliding_window_layout", "rope_theta"}
# the one value that is implemented
_REQUIRED = {
    "model_name": "smallthinker_21b_instruct", "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "rope_scaling": None, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 0, "param_dtype": "float32",
}
# key -> what it has to equal, from the other keys
_CONSISTENT = {
    # the program gives a rotation to a kind of layer: the window layers are
    # the rotated ones, as published
    "rope_layout": lambda c: c["sliding_window_layout"],
    "moe_num_primary_experts": lambda c: c["experts_held"][1],
    "vocab_size": lambda c: c["vocab_rows"][1],
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
    fields = {field: config[key] for key, field in _FIELDS.items()}
    fields |= {field: config["published"][key] for key, field in _PUBLISHED.items()}
    return fields | {
        "layer_types": [KINDS[int(ref_shapes.is_window(config, i))]
                        for i in range(config["num_hidden_layers"])],
        # rope_layout: none on the full layers; rope_scaling null: the default type
        "rope_parameters": {"full_attention": None,
                            "sliding_attention": {"rope_theta": config["rope_theta"]}},
        "first_k_dense": 0,  # no dense width is published: every layer is sparse
        "n_shared_experts": 0,
        "attn_gate": False,
        "router_input": "block_input",
        "router_scoring": "softmax_topk",  # moe_primary_router_apply_softmax, norm_topk_prob
        "routed_scaling_factor": 1.0,  # the config has none
        "expert_act": "relu",
    }


def _here(fn):
    """``fn`` of ``hybrid_lm_steps`` with its module-level names looked up in
    this module (``gqa_lm_steps._here``, over this module's names)."""
    return types.FunctionType(fn.__code__, globals(), fn.__name__, fn.__defaults__,
                              fn.__closure__)


program_config = _here(hybrid_lm_steps.program_config)


def reference_run(config: dict, seed: int, batches, rounding: str = "float32") -> dict:
    """The reference's losses, first-gradient norms and change norms over
    ``len(batches)`` steps from the seed's weights (``hybrid_lm_steps``'
    without biases: this family's router has none)."""
    seed = common.seed32(seed)
    with jax.default_matmul_precision("highest"):
        loss_grad = jax.jit(jax.value_and_grad(
            lambda p, t: ref_model.batch_loss(p, t, config, rounding)))
        change_sq = jax.jit(lambda p, s: _leaf_sq(jax.tree_util.tree_map(
            jnp.subtract, p, ref_shapes.make_params(s, config))))
        in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
        print(f"reference ({rounding}): {in_use / 1e9:.2f} GB in use on the device "
              "before it starts", flush=True)
        params = jax.jit(lambda s: ref_shapes.make_params(s, config))(seed)
        # Adam's moments wait on the host between steps (``lm_steps``)
        moments = lambda st, move: st | {k: move(st[k]) for k in ("m", "v")}
        state, losses, grad_sq = None, [], None
        for tokens in batches:
            loss, g = loss_grad(params, tokens)
            losses.append(float(loss))
            if grad_sq is None:
                grad_sq = np.asarray(jax.jit(_leaf_sq)(g))
            state = (ref_optim.adamw_init(params) if state is None
                     else moments(state, jax.device_put))
            params, state = ref_optim.adamw_step(params, g, state, config["optim"])
            del g
            state = moments(state, jax.device_get)
        del state
        return {"loss": np.asarray(losses), "grad": np.sqrt(grad_sq),
                "delta": np.sqrt(np.asarray(change_sq(params, seed)))}


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
               "dropped": over(np.sum, "moe_dropped"),
               "act_zero_share": over(np.mean, "moe_act_zero_share")}
        # only held experts reach the loss, so the first steps turn the routers
        # towards them (PERF.md §6, PR 38): the window's first and last reading
        drift = [float(steps[at]["moe_held_share"]) for at in (0, -1)]
        # the program's static records: what its kernels' block tables walk,
        # and what its router reads
        lm = MlaMoeConfig(**lm_fields(self.config))
        pairs = {kind: {"visited": visited, "needed": needed}
                 for kind, (visited, needed) in lm.attn_pairs(self.seq).items()}
        print(f"counters over {len(steps)} steps: "
              f"{json.dumps({'moe': moe, 'held_share_first_last': drift, 'attn_pairs': pairs, 'router_input': lm.router_input})}",
              flush=True)
        for kind in KINDS:  # the program's mask keeps what the yardstick counts
            assert pairs[kind]["needed"] == flops_family.needed_pairs(self.config, kind, self.seq)
        rows = (moe["held_share"] * self.batch * self.seq
                * self.config["moe_num_active_primary_experts"])
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
    two layers (the rope-free full layer and a window layer with rope: one of
    each kind, since the CPU compiles every layer of the unrolled step and a
    further window layer is the second again), 7 query heads over 1 key/value
    head (the group of 7), a window of 11 tokens that is smaller than the 24
    of a sequence, rope theta cut to 100 so that 24 positions turn the slow
    pairs too, 8 experts top-3 of which 2 are held, a slice of a 256-row
    vocabulary."""
    cell = copy.deepcopy(cell)
    cell["config"] |= {
        "num_hidden_layers": 2, "hidden_size": 32, "num_attention_heads": 7,
        "num_key_value_heads": 1, "head_dim": 16, "sliding_window_size": 11, "rope_theta": 100,
        "moe_ffn_hidden_size": 16, "moe_num_primary_experts": 2,
        "moe_num_active_primary_experts": 3, "vocab_size": 64, "experts_held": [2, 2],
        "vocab_rows": [64, 64],
        "published": {"num_hidden_layers": 52, "moe_num_primary_experts": 8, "vocab_size": 256},
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
