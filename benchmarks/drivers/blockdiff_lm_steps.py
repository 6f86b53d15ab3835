"""Language-model pretraining steps, back to back, through the trainer's own
factories: the block-diffusion family (``SDAR-30B-A3B-Chat``: a grouped-query
trunk with q/k norms and softmax-routed experts, trained not on the next token
but on the masked tokens of a noisy copy of every sequence, which runs
through the trunk beside the clean copy under a block-causal / block-diagonal
mask) behind ``train_loop``'s loop and check.

State, optimizer, step program and device prefetch are the trainer's; the
tokens (``token_batches``: clean ids uniform over the vocabulary rows held but
the mask id, one ``seq``-token document a row and nothing after it: nothing
is shifted) and the weights are the benchmark's, from the seed. The noise is
the program's: it draws a step's levels and masks inside the step program
from the step's ``noise`` stream. The float32 reference
(``benchmarks/reference/blockdiff_lm_model.py``) follows the same first steps
from the same weights and tokens under the same noise: ``noise_key`` derives
each step's key as the program does (the run's key folded with the process,
the step, the train domain, micro-batch 0 and the stream, then flax's
``make_rng`` at the model's root, reproduced through flax itself), and the
reference draws from it with its own lines. The family has no router bias
and so no non-gradient state.

Every key of the configuration file is accounted for here, the one place the
sizes are stated: ``_FIELDS`` and ``_PUBLISHED`` go to the program's
``model.lm`` fields, ``_REQUIRED`` name the one value the program and the
reference implement and ``_PROGRAM_CONSTANT`` the value a constant of the
program has (a file that says otherwise is refused, not ignored),
``_CONSISTENT`` restate another key, ``_INERT`` are published keys no layer
held reads, and ``_ABOUT`` describe the file or steer this driver.
"""

from __future__ import annotations

import copy
import dataclasses
import functools
import itertools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops_blockdiff_lm as flops_family
from benchmarks.drivers import common, train_loop
from benchmarks.drivers.train_loop import CHECK_STEPS, _leaf_sq
from benchmarks.harness import BenchmarkError
from benchmarks.reference import blockdiff_lm_model as ref_model
from benchmarks.reference import blockdiff_lm_params as ref_shapes
from benchmarks.reference import optim as ref_optim

LIMITS = json.loads((Path(__file__).parent / "blockdiff_lm_steps.limits.json").read_text())
# the committed limits are set from the chip's readings at the published
# widths; a 32-wide model's few-element leaves read noisier
TINY_LIMITS = {"loss_gap": 1e-3, "first_grad_norm_gap": 0.1, "param_change_norm_gap": 0.1,
               "core_gap": LIMITS["core_gap"]}
SPANS = train_loop.SPANS
SCOPES = "blockdiff_lm"  # benchmarks/scopes/<name>.json: this family's parts
CONTROL = "fp8"  # e4m3, the nearest precision below the configuration's bfloat16
ONE_BLOCK_CONTROL = "fp8@0"  # the control in the first block alone, on the control seeds too
FLOPS_SEQ = 8192  # the clean tokens a sequence flops_pair compares the two counts at
KIND = "block_diffusion"  # the program's name for the core's pattern (``attn_pairs``)
# the probe of the core's cuts (``core_gap``): the query rows of a slab, of which it
# compares each copy's first and last, and its seeded scores' deviation
PROBE_SLAB = 512
PROBE_DEVIATION = 6.0
COUNTERS = ("moe_imbalance", "moe_held_share", "moe_dropped", "bd_masked_share")

# config.json's keys -> the program's models/lm.MlaMoeConfig fields
_FIELDS = {
    "hidden_size": "dim", "num_hidden_layers": "layers", "num_attention_heads": "heads",
    "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
    "moe_intermediate_size": "expert_hidden", "num_experts_per_tok": "experts_per_token",
    "num_nextn_predict_layers": "mtp_layers", "rms_norm_eps": "rms_eps",
    "diffusion_block_length": "diffusion_block",
    "experts_held": "experts_held", "vocab_rows": "vocab_rows", "compute_dtype": "dtype",
    "grad_ckpt": "grad_ckpt",
}
# the model's own counts, beside what the chip holds of them
_PUBLISHED = {"num_experts": "n_routed_experts", "vocab_size": "vocab_size"}
# translated by a rule in ``lm_fields``
_DERIVED = {"rope_theta"}
# the one value that is implemented
_REQUIRED = {
    "model_type": "sdar_moe", "attention_bias": False, "hidden_act": "silu",
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "rope_scaling": None, "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 0, "param_dtype": "float32",
}
# key -> what it has to equal, from the other keys
_CONSISTENT = {
    "num_experts": lambda c: c["experts_held"][1],
    "vocab_size": lambda c: c["vocab_rows"][1],
    "mask_token_id": ref_shapes.mask_id,
}
# published keys that no layer held reads: the dense width of a model whose
# every layer is sparse, and the window layers' count of a model without windows
_INERT = {"intermediate_size", "max_window_layers"}
# max_position_embeddings bounds the traffic's positions (``Driver``); optim,
# program and published are read below; qk_norm_init_scale is the seeded
# weights' alone (``blockdiff_lm_params.make_params``: the program has no such
# option, its state is seeded from here); the rest is the file's own account
_ABOUT = {"name", "source", "recipe", "deployment", "published", "parameters_here", "optim",
          "program", "reduced", "reduced_why", "assumed", "max_position_embeddings",
          "qk_norm_init_scale"}
# the objective's constant, which the program has as a constant too (ops/masking.BLOCK_NOISE_EPS)
_PROGRAM_CONSTANT = {"diffusion_noise_eps"}
KEYS = (set(_FIELDS) | set(_PUBLISHED) | _DERIVED | set(_REQUIRED) | set(_CONSISTENT) | _INERT
        | _ABOUT | _PROGRAM_CONSTANT)


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
    from jumbo_mae_tpu_tpu.ops.masking import BLOCK_NOISE_EPS

    if config["diffusion_noise_eps"] != BLOCK_NOISE_EPS:
        raise ValueError(f"diffusion_noise_eps = {config['diffusion_noise_eps']!r}: the "
                         f"program's BLOCK_NOISE_EPS is {BLOCK_NOISE_EPS!r}")
    fields = {field: config[key] for key, field in _FIELDS.items()}
    fields |= {field: config["published"][key] for key, field in _PUBLISHED.items()}
    return fields | {
        "layer_types": ["full_attention"] * config["num_hidden_layers"],
        # rope_scaling null: the default type, on every dimension of a head
        "rope_parameters": {"full_attention": {"rope_theta": config["rope_theta"]}},
        "qk_norm": True,
        "first_k_dense": 0,  # decoder_sparse_step 1, mlp_only_layers []: every layer sparse
        "n_shared_experts": 0,
        "attn_gate": False,
        "router_input": "ffn_norm",
        "router_scoring": "softmax_topk",  # softmax, top-k, norm_topk_prob: the same function
        "routed_scaling_factor": 1.0,  # the config has none
        "expert_act": "silu",
    }


def program_config(config: dict, *, batch: int, seq: int):
    """The program's ``TrainConfig``: the file's ``program`` section (the
    recipe's run, optimizer and mesh), the model from the file's sizes. The
    program's own seeds stay 0, as for every family (``common``). A program
    without the block-diffusion objective is refused here, by name."""
    from jumbo_mae_tpu_tpu.config import config_from_dict
    from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig

    if "diffusion_block" not in {f.name for f in dataclasses.fields(MlaMoeConfig)}:
        raise BenchmarkError("the program has no block-diffusion objective "
                             "(models/lm.MlaMoeConfig.diffusion_block)")
    doc = copy.deepcopy(config["program"])
    doc.setdefault("run", {}).update(seed=0, init_seed=0, synthetic_data=True,
                                     train_batch_size=batch, valid_batch_size=batch)
    doc["model"] = {"lm": lm_fields(config)}
    doc.setdefault("data", {})["seq_len"] = seq
    return config_from_dict(doc)


def token_batches(seed: int, config: dict, batch: int, seq: int, distinct: int):
    """Endless cycle over ``distinct`` seeded batches (batch, seq) int32 of
    clean ids, uniform over the vocabulary rows held but the last, the mask
    id, which no document holds."""
    first, rows = config["vocab_rows"]
    pool = np.random.default_rng(seed).integers(first, first + rows - 1, (distinct, batch, seq),
                                                dtype=np.int32)
    return ({"tokens": pool[i % distinct]} for i in itertools.count())


@jax.jit
def noise_key(seed, step):
    """The key behind step ``step``'s noise, as the program derives it: the
    key of the seed folded with (process 0, step, train domain 0, micro-batch
    0, stream "noise" = 1), then flax's ``make_rng("noise")`` in the model's
    root module — reproduced through flax itself, with a probe at that path."""
    import flax.linen as nn

    class Probe(nn.Module):
        @nn.compact
        def __call__(self):
            return self.make_rng("noise")

    key = jax.random.key(seed)
    for fold in (0, step, 0, 0, 1):
        key = jax.random.fold_in(key, fold)
    return Probe().apply({}, rngs={"noise": key})


def reference_run(config: dict, seed: int, batches, rounding: str = "float32") -> dict:
    """The reference's losses, first-gradient norms and change norms over
    ``len(batches)`` steps from the seed's weights, each step under the noise
    the program drew for it, and its side of the core probe."""
    seed = common.seed32(seed)
    with jax.default_matmul_precision("highest"):
        loss_grad = jax.jit(jax.value_and_grad(
            lambda p, t, k: ref_model.batch_loss(p, t, k, config, rounding)))
        change_sq = jax.jit(lambda p, s: _leaf_sq(jax.tree_util.tree_map(
            jnp.subtract, p, ref_shapes.make_params(s, config))))
        in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
        print(f"reference ({rounding}): {in_use / 1e9:.2f} GB in use on the device "
              "before it starts", flush=True)
        params = jax.jit(lambda s: ref_shapes.make_params(s, config))(seed)
        # Adam's moments wait on the host between steps (``lm_steps``)
        moments = lambda st, move: st | {k: move(st[k]) for k in ("m", "v")}
        state, losses, grad_sq = None, [], None
        for step, tokens in enumerate(batches):
            loss, g = loss_grad(params, tokens, noise_key(seed, step))
            losses.append(float(loss))
            if grad_sq is None:
                grad_sq = np.asarray(jax.jit(_leaf_sq)(g))
            state = (ref_optim.adamw_init(params) if state is None
                     else moments(state, jax.device_put))
            params, state = ref_optim.adamw_step(params, g, state, config["optim"])
            del g
            state = moments(state, jax.device_get)
        del state
        (q, k, v, w), rows = probe(config, seed, batches[0].shape[1])
        low, _, only = rounding.partition("@")  # the probe is in no block of the trunk
        core = jax.jit(ref_model.core_probe, static_argnames=("block", "rounding"))(
            *(x.astype(jnp.float32) for x in (q, k, v)), w, rows,
            block=config["diffusion_block_length"], rounding="float32" if only else low)
        return {"loss": np.asarray(losses), "grad": np.sqrt(grad_sq),
                "delta": np.sqrt(np.asarray(change_sq(params, seed))),
                "core": [np.asarray(x) for x in core]}


def probe_rows(seq: int) -> np.ndarray:
    """The query rows the core probe compares, of the ``2 seq`` of a pair:
    each copy's first ``PROBE_SLAB`` (the diffusion blocks that see least, the
    first block pairs of the kernels' tables) and its last (the longest walk:
    every whole pair, then the cut ones)."""
    slab = min(PROBE_SLAB, seq // 2)
    return np.concatenate([first + np.arange(slab)
                           for first in (0, seq - slab, seq, 2 * seq - slab)])


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "seq", "width", "rows", "dtype"))
def probe_operands(seed, *, heads: int, kv_heads: int, seq: int, width: int, rows: int, dtype):
    """The probe's operands from the seed: ``q`` (heads, 2 seq, e), the scale
    in it, ``k`` and ``v`` (key/value heads, 2 seq, e) in the compute dtype,
    and the float32 weights ``w`` (heads, rows, e) of the sum differentiated.
    Scores of deviation ``PROBE_DEVIATION``: a row's output is then a few
    keys' values however many it sees, so that a key wrongly seen or hidden
    moves some row's output by its own size even 8192 tokens in (under
    scores of deviation 1 a late row is a mean of thousands, which the four
    keys a wrong cut adds or hides do not move)."""
    keys = jax.random.split(jax.random.fold_in(jax.random.key(seed), 1), 4)
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    q = (draw(keys[0], heads, 2 * seq, width) * (PROBE_DEVIATION * width**-0.5)).astype(dtype)
    k, v = (draw(key, kv_heads, 2 * seq, width).astype(dtype) for key in keys[1:3])
    return q, k, v, draw(keys[3], heads, rows, width)


def program_core(q, k, v, w, rows, *, block: int):
    """The program's core on the probe's operands, forward and backward:
    ``(o, dq)`` at the query rows ``rows`` and ``(dk, dv)`` of ``Σ w ⊙ o`` over
    them, through the entry the model's blocks call (``models/lm``'s
    ``causal_attention``, by the op's own rule: at the cell's shape on the
    chip the Pallas kernels under the block-diffusion pattern, the tables and
    cuts the timed step's layers run under)."""
    from jumbo_mae_tpu_tpu.models import lm

    def weighed(q, k, v):
        o = lm.causal_attention(q[None], None, k[None], None, v[None], impl=None,
                                diffusion=block)[0][:, rows]
        return (o.astype(jnp.float32) * w).sum(), o

    (_, o), (dq, dk, dv) = jax.value_and_grad(weighed, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return o, dq[:, rows], dk, dv


def probe(config: dict, seed: int, seq: int):
    """``(operands, rows)`` of the core probe for the cell's sizes."""
    rows = probe_rows(seq)
    return probe_operands(
        common.seed32(seed), heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], seq=seq, width=config["head_dim"],
        rows=len(rows), dtype=jnp.dtype(config["compute_dtype"])), rows


def core_gap(prog, ref) -> float:
    """Largest gap between the two sides' arrays of the core probe (``o``,
    ``dq``, ``dk``, ``dv``), each against the reference's largest entry of
    that array."""
    return max(float(np.abs(p - r).max() / np.abs(r).max()) for p, r in zip(prog, ref))


def checks(prog: dict, ref: dict, limits: dict, window_bad: int = 0) -> list:
    """``train_loop.compare``'s numbers of the timed program's first steps
    and, of the core alone, ``core_gap``: the norms of a step's gradient over
    32 768 rows cannot see a cut that adds or hides four keys of thousands
    (PERF.md §2, PR 47: both mask mutations read as a sound run there), the
    probe's rows can."""
    return train_loop.compare(prog, ref, limits, window_bad) + [
        ("core_gap", core_gap(prog["core"], ref["core"]), limits["core_gap"])]


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
        model, self.lm, _ = build_model(cfg)
        tx = make_optimizer(cfg.optim, run.train_batch_size, num_layers=self.lm.layers)
        # a row is the clean tokens alone: nothing is shifted
        example = {"tokens": np.zeros((self.batch, self.seq), np.int32)}
        state, sharding = create_sharded_state(
            model, tx, example, mesh, mode="lm", init_seed=run.init_seed,
            rng_seed=run.seed, param_dtype=cfg.optim.param_dtype,
        )
        common.require_same_tree(state.params, ref_shapes.shapes(config), "language-model state")
        common.require_same_tree(state.batch_stats, ref_shapes.bias_shapes(config),
                                 "router biases")

        # the state object is the trainer's; its weights are the benchmark's.
        # The trainer's own init is freed first, so that the peak the run
        # reports is the step's and not two states side by side.
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
        self._counters.append({k: metrics[k] for k in COUNTERS})
        return metrics, wait

    def warm(self):
        super().warm()
        (*operands, w), rows = probe(self.config, self.seed, self.seq)
        core = jax.jit(functools.partial(  # a jit of its own: the call is traced as it stands now
            program_core, block=self.config["diffusion_block_length"]))
        self.readings["core"] = [np.asarray(x.astype(jnp.float32))
                                 for x in core(*operands, w, rows)]

    def check(self):
        self.close()  # the reference runs with the program's state freed
        return checks(self.readings, self.reference(), self.limits, self.window_bad)

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
        bd = {"masked_share": over(np.mean, "bd_masked_share")}
        # only held experts reach the loss, so the first steps may turn the
        # routers towards them (PERF.md §6, PR 38): the window's first and last
        drift = [float(steps[at]["moe_held_share"]) for at in (0, -1)]
        # the program's static record: what its kernels' block tables walk
        pairs = {kind: {"visited": visited, "needed": needed}
                 for kind, (visited, needed) in self.lm.attn_pairs(self.seq).items()}
        print(f"counters over {len(steps)} steps: "
              f"{json.dumps({'moe': moe, 'bd': bd, 'held_share_first_last': drift, 'attn_pairs': pairs})}",
              flush=True)
        # the program's mask keeps what the yardstick counts
        assert set(pairs) == {KIND}
        assert pairs[KIND]["needed"] == flops_family.needed_pairs(self.config, self.seq)
        # both copies' rows reach the router
        rows = moe["held_share"] * self.batch * 2 * self.seq * self.config["num_experts_per_tok"]
        work = {"bd_core": flops_family.core_step(self.config, self.batch, self.seq),
                "experts": flops_family.experts_step(self.config, rows)}
        return record | {"moe": moe, "bd": bd, "attn_pairs": pairs, "kernel_work": {
            name: {"flops": f, "bytes": b} for name, (f, b) in work.items()}}

    def work(self, steps: int) -> dict:
        # a sample is one sequence and a token a clean token: the noisy copy
        # is how a sequence is trained on, not more data
        tokens = steps * self.batch * self.seq
        return {"images": steps * self.batch, "tokens": tokens,
                "work_flops": tokens * flops_family.token_step(self.config, self.seq)}

    def reference(self, rounding: str = "float32") -> dict:
        return reference_run(self.config, self.seed, self.first_batches, rounding)


def build(cell, *, devices, seed):
    return Driver(cell, devices=devices, seed=seed)


def worst_leaves(prog: dict, ref: dict, names: list[str], most: int = 3) -> dict:
    """The leaves behind ``train_loop.worst_gap``'s two numbers: for the first
    gradient and for the change, the ``most`` largest gaps with their leaves."""
    out = {}
    for key in ("grad", "delta"):
        gaps = np.abs(prog[key] - ref[key]) / np.maximum(ref[key], np.median(ref[key]))
        out[key] = {names[i]: round(float(gaps[i]), 5) for i in np.argsort(-gaps)[:most]}
    return out


def limit_readings(cell, *, devices, seeds, control_seeds):
    """``train_loop.limit_readings`` with this family's ``checks``, the leaves
    the two norm gaps are read on (``worst``) and, on the control seeds, the
    control in the first block alone too (``ONE_BLOCK_CONTROL``), kind
    ``control@0``."""
    names = ["/".join(k.key for k in path) for path, _ in jax.tree_util.tree_flatten_with_path(
        ref_shapes.shapes(cell["config"]), is_leaf=lambda x: isinstance(x, tuple))[0]]
    for seed in seeds:
        driver = build(cell, devices=devices, seed=seed)
        driver.warm()
        readings = driver.readings
        driver.close()
        ref = driver.reference()
        yield {"seed": seed, "kind": "sound", "checks": checks(readings, ref, driver.limits),
               "worst": worst_leaves(readings, ref, names)}
        if seed in control_seeds:
            for rounding in (CONTROL, ONE_BLOCK_CONTROL):
                low = driver.reference(rounding)
                yield {"seed": seed, "kind": "control" + rounding[len(CONTROL):],
                       "checks": checks(low, ref, driver.limits),
                       "worst": worst_leaves(low, ref, names)}


def tiny(cell: dict) -> dict:
    """The cell cut to a size the CPU holds, its structure kept: two layers, 8
    query heads over 1 key/value head (the group of 8), rope theta cut to 100
    so that 24 positions turn the slow pairs too, 8 experts top-3 of which 2
    are held, a slice of a 256-row vocabulary whose last row is the mask id;
    24 clean tokens a sequence in 6 diffusion blocks of 4, 48 rows through
    the trunk."""
    cell = copy.deepcopy(cell)
    cell["config"] |= {
        "num_hidden_layers": 2, "hidden_size": 32, "num_attention_heads": 8,
        "num_key_value_heads": 1, "head_dim": 16, "rope_theta": 100,
        "moe_intermediate_size": 16, "num_experts": 2, "num_experts_per_tok": 3,
        "vocab_size": 64, "experts_held": [2, 2], "vocab_rows": [64, 64], "mask_token_id": 127,
        "published": {"num_hidden_layers": 48, "num_experts": 8, "vocab_size": 256},
    }
    cell["traffic"] |= {"sequences_per_chip": 4, "seq": 24, "distinct_batches": 2,
                        "fetch_every": 2, "trace_seconds": 0.3}
    return cell


def flops_pair(config: dict) -> tuple[float, float]:
    """Forward + backward FLOPs of one clean token at ``FLOPS_SEQ``: the
    benchmark's own count and the program's for the same configuration."""
    from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig
    from jumbo_mae_tpu_tpu.obs.mfu import lm_flops_per_token

    program = lm_flops_per_token(MlaMoeConfig(**lm_fields(config)), FLOPS_SEQ)
    return flops_family.token_step(config, FLOPS_SEQ), program
