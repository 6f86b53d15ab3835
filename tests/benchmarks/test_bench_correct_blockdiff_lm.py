"""``correct`` has to be able to come out false in the block-diffusion
family's cell: five mutations of the program (the clean copy under a
token-causal mask where it is block-causal, a noisy token that sees the clean
tokens of its own block — ``<=`` for ``<`` —, a loss without its 1/t, rope
positions that run on through the second copy, another row for the mask id:
each through the driver's own warm-up and check) fail the cell's check
at the test size, the lower-precision control fails it, the probe of the core
alone sees a wrong cut a thousand keys into a row, and the driver has an
account of every key of the configuration file. ``test_bench_rehearsal`` and
``test_bench_yardstick`` run the cell traced and untraced and hold its FLOP
count to the program's, as they do for every cell of ``BENCHMARK.json``.

What these tests say of ``BENCHMARK.json``'s lists is containment and
relative order only — no length, no last place — so that the next cell
breaks none of them."""

import contextlib
import io
import json
import re

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import driver_of, load_bench, tiny_cell
from benchmarks import harness
from benchmarks.drivers import train_loop
from jumbo_mae_tpu_tpu.models import lm

pytestmark = pytest.mark.usefixtures("cpu_has_no_peak")
CELL, CONFIG = "sdar_blockdiff_pretrain_2x8k", "sdar_30b_a3b_ep8"
SEED = 2_147_484_047


def load_cell(name: str = CELL) -> dict:
    return harness.load_cell(name)


def _under(visible):
    """``lm.causal_attention`` as the block calls it, under another mask:
    ``visible(clean query?, clean key?, b_q, b_k, q, k)`` over positions and
    their diffusion blocks within a copy."""
    def attention(q, q_b, k, k_b, v, *, impl=None, diffusion=None):
        assert q_b is None and k_b is None and impl is None and diffusion
        rows, group = q.shape[2], q.shape[1] // k.shape[1]
        at = jnp.arange(rows) % (rows // 2)
        clean = jnp.arange(rows) < rows // 2
        keep = visible(clean[:, None], clean[None, :], at[:, None] // diffusion,
                       at[None, :] // diffusion, at[:, None], at[None, :])
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

    return lm, "causal_attention", attention


def _pattern(clean_clean=lambda b_q, b_k, q, k: b_k <= b_q,
             noisy_clean=lambda b_q, b_k, q, k: b_k < b_q):
    """The block-diffusion pattern with one of its parts replaced."""
    return lambda cq, ck, b_q, b_k, q, k: jnp.where(
        cq, ck & clean_clean(b_q, b_k, q, k),
        jnp.where(ck, noisy_clean(b_q, b_k, q, k), b_k == b_q))


def _no_one_over_t():
    real = lm.block_noise

    def noise(*args):
        t, masked = real(*args)
        return jnp.ones_like(t), masked

    return lm, "block_noise", noise


def _rope_over_both_copies():
    """The second copy at positions ``L .. 2 L − 1``: the block hands the
    rope twice the heads of half the rows, and this undoes it."""
    real = lm.rope_half

    def rope(x, turn, **kw):
        b, h2, half, d = x.shape
        return real(x.reshape(b, h2 // 2, 2 * half, d), turn, **kw).reshape(x.shape)

    return lm, "rope_half", rope


# name -> () -> (owner, attribute, replacement)
MUTATIONS = {
    "pattern_itself": lambda: _under(_pattern()),  # the control: not a mutation, see below
    "clean_copy_token_causal": lambda: _under(_pattern(clean_clean=lambda b_q, b_k, q, k: k <= q)),
    "noisy_sees_its_own_clean_block": lambda: _under(
        _pattern(noisy_clean=lambda b_q, b_k, q, k: b_k <= b_q)),
    "no_one_over_t": _no_one_over_t,
    "rope_over_both_copies": _rope_over_both_copies,
    "mask_id_is_the_first_row": lambda: (lm.MlaMoeConfig, "mask_id",
                                         property(lambda self: self.rows[0])),
}


def _peaked(monkeypatch):
    """The seeded query, key, router and expert matrices scaled up, in the
    program and the reference alike (both take their weights from
    ``blockdiff_lm_params.make_params``): the scores, the routers' logits and
    the experts' outputs then spread as the real cut's do at its seeded
    weights (2048 inputs of 0.02 against the tiny cut's 32, which leave every
    softmax flat and a mutation of a mask without effect)."""
    from benchmarks.reference import blockdiff_lm_params

    real = blockdiff_lm_params.make_params

    def make_params(seed, c):
        params = real(seed, c)
        for name in [n for n in params if n.startswith("block_")]:
            blk = params[name]
            for leaf in [blk["attn"]["q"], blk["attn"]["k"],
                         *(blk["moe"][k] for k in ("router", "gate", "up", "down"))]:
                leaf["kernel"] = leaf["kernel"] * 8.0
        return params

    monkeypatch.setattr(blockdiff_lm_params, "make_params", make_params)


def _checks_in_float32() -> tuple[list, str]:
    """The tiny cell's first three steps by the driver's own warm-up and its
    check, computed in float32 (at 32 wide bfloat16's rounding alone reads
    more on a gradient leaf than some mutations move it): ``(checks, what the
    run printed)``. No window: ``correct`` is the check's."""
    cell = tiny_cell(load_cell())
    cell["config"]["compute_dtype"] = "float32"
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        driver = driver_of(cell).build(cell, devices=jax.devices()[:1], seed=SEED)
        try:
            driver.warm()
            checks = driver.check()
        finally:
            driver.close()
    return checks, printed.getvalue()


@pytest.fixture(scope="module")
def sound():
    """``(limits, reference)``: limits set as the cell's own are, three times
    the sound program's readings on this seed, and the float32 reference's
    three steps from this seed's weights, tokens and noise. Read once for the
    module's cases: no mutation touches the reference, so each case compares
    with the one copy and does not compute it again."""
    mod = driver_of(load_cell())
    kept = []
    with pytest.MonkeyPatch.context() as patch:
        _peaked(patch)
        for key, limit in mod.TINY_LIMITS.items():
            patch.setitem(mod.LIMITS, key, limit)
        real = mod.reference_run
        patch.setattr(mod, "reference_run", lambda *a, **k: kept.append(real(*a, **k)) or kept[-1])
        checks, printed = _checks_in_float32()
    assert all(value <= limit for _, value, limit in checks) and len(kept) == 1, checks
    sound = {name: value for name, value, _ in checks if name.endswith("_gap")}
    assert set(sound) == set(mod.TINY_LIMITS)
    assert all(3 * sound[key] < mod.TINY_LIMITS[key] for key in sound), sound
    return {key: 3 * reading for key, reading in sound.items()}, kept[0]


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_a_mutated_program_is_not_correct(mutation, monkeypatch, sound):
    """At limits the sound program passes (``sound`` has held it to them
    three times over) each mutation fails by at least one number, with every
    loss finite; and the stand-in the mask mutations are written with, under
    the pattern itself, passes: what fails them is their mask."""
    mod = driver_of(load_cell())
    limits, reference = sound
    _peaked(monkeypatch)
    for key, limit in limits.items():
        monkeypatch.setitem(mod.LIMITS, key, limit)
    monkeypatch.setattr(mod, "reference_run", lambda *a, **k: reference)
    monkeypatch.setattr(*MUTATIONS[mutation]())
    checks, printed = _checks_in_float32()
    passed = all(value <= limit for _, value, limit in checks)
    assert passed == (mutation == "pattern_itself"), checks
    assert all(value == value for _, value, _ in checks)  # the losses stay finite


def test_the_lower_precision_control_fails_the_limits_the_sound_run_passes(sound, monkeypatch):
    """The reference in the program's place at test size, on ``sound``'s seed,
    weights, tokens and noise: computed in fp8 (the control), whole or in the
    first block alone, it fails one of the limits."""
    cell = tiny_cell(load_cell())
    mod = driver_of(cell)
    _peaked(monkeypatch)
    config, t = cell["config"], cell["traffic"]
    gen = mod.token_batches(SEED, config, t["sequences_per_chip"], t["seq"], 2)
    batches = [next(gen)["tokens"] for _ in range(train_loop.CHECK_STEPS)]
    ref = sound[1]
    limits = mod.LIMITS | mod.TINY_LIMITS
    for rounding in (mod.CONTROL, mod.ONE_BLOCK_CONTROL):
        control = mod.reference_run(config, SEED, batches, rounding=rounding)
        assert not all(v <= limit for _, v, limit in train_loop.compare(control, ref, limits)), (
            rounding)


LONG_ROW = {"num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
            "compute_dtype": "float32", "diffusion_block_length": 4}


@pytest.mark.parametrize("mutation", ["pattern_itself", "clean_copy_token_causal",
                                      "noisy_sees_its_own_clean_block"])
def test_the_core_probe_sees_a_wrong_cut_in_the_last_rows_of_a_long_row(mutation, monkeypatch):
    """``core_gap`` on a row of 2 x 1024 tokens, read on the last 128 rows of
    each copy alone (a thousand keys in, where four keys more or fewer move
    no norm of a step's gradient): the pattern itself reads float32's
    rounding, either mask mutation several times the cell's limit."""
    import functools

    import numpy as np

    from benchmarks.reference import blockdiff_lm_model as ref_model

    mod, seq, slab = driver_of(load_cell()), 1024, 128
    monkeypatch.setattr(mod, "PROBE_SLAB", slab)
    (q, k, v, w), rows = mod.probe(LONG_ROW, SEED, seq)
    assert rows.tolist() == [*range(slab), *range(seq - slab, seq), *range(seq, seq + slab),
                             *range(2 * seq - slab, 2 * seq)]
    late = np.concatenate([rows[slab:2 * slab], rows[3 * slab:]])
    w = w[:, :2 * slab]
    monkeypatch.setattr(*MUTATIONS[mutation]())
    prog = jax.jit(functools.partial(mod.program_core, block=4))(q, k, v, w, late)
    with jax.default_matmul_precision("highest"):
        ref = ref_model.core_probe(q, k, v, w, late, block=4)
    gap = mod.core_gap([np.asarray(x) for x in prog], [np.asarray(x) for x in ref])
    limit = mod.LIMITS["core_gap"]
    assert gap < 1e-4 if mutation == "pattern_itself" else gap > 4 * limit, gap


def test_the_worst_leaves_are_named_in_the_order_the_norms_are_taken():
    """``limit_readings``' ``worst``: the leaves behind ``worst_gap``'s number,
    by the names of the tree the norms are stacked from."""
    import numpy as np

    mod = driver_of(load_cell())
    ref = {"grad": np.array([1.0, 4.0, 1e-9, 2.0]), "delta": np.array([1.0, 1.0, 1.0, 1.0])}
    prog = {"grad": np.array([1.1, 4.0, 0.3, 1.0]), "delta": np.array([1.0, 1.5, 1.0, 1.0])}
    worst = mod.worst_leaves(prog, ref, ["a", "b", "c", "d"], most=2)
    # against the reference's norm of the leaf or of the median leaf (1.5), whichever is larger
    assert worst == {"grad": {"d": 0.5, "c": 0.2}, "delta": {"b": 0.5, "a": 0.0}}
    assert max(worst["grad"].values()) == train_loop.worst_gap(prog["grad"], ref["grad"])


# the catalog's ``config`` of SDAR-30B-A3B-Chat, every key
# (/opt/skills/guides/model-configs/architectures.jsonl): a number the file
# changes is in ``reduced``
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def test_the_driver_has_an_account_of_every_key_of_the_configuration_file():
    """Every key is translated, required to hold the one value that is
    implemented, held to the keys it restates, inert, or about the file — and
    none of those accounts names a key the file lacks; every key of the
    catalog's ``config`` is in the file under its own name, as published or,
    where ``reduced`` names it, as this chip's share beside the published
    count."""
    from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig

    cell = load_cell()
    mod, config = driver_of(cell), cell["config"]
    assert set(config) == mod.KEYS
    kinds = [set(mod._FIELDS) | set(mod._PUBLISHED), mod._DERIVED,
             set(mod._REQUIRED) - {"num_nextn_predict_layers"},
             set(mod._CONSISTENT) - set(mod._PUBLISHED), mod._INERT, mod._ABOUT,
             mod._PROGRAM_CONSTANT]
    assert sum(map(len, kinds)) == len(mod.KEYS)  # one account a key
    named = {"block_length", "noise_schedule", "mask_id", "no_shift", "qk_norm", "rope_pairing",
             "init", "optim"}
    assert named <= set(config["assumed"])
    assert all("other reading" in config["assumed"][key] for key in named)
    entry = next(c for c in load_bench()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and entry["file"].endswith(f"{CONFIG}.json")
    assert 0 < len(entry["why"]) <= 200
    assert set(entry["reduced"]) == set(config["reduced"]) == set(config["reduced_why"])
    published = config["published"]
    for key, value in CATALOG.items():
        if key in entry["reduced"]:
            assert config[key] != value and published[key] == value, key
        else:
            assert config[key] == value, key
    assert {k for k in CATALOG if k in entry["reduced"]} == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    # the guide's floors: four layers, 8 experts a chip, an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] == 16
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert "8 chips share each layer" in config["deployment"]
    assert "8 pipeline stages" in config["deployment"] and "layers 0-5" in config["deployment"]
    cfg = MlaMoeConfig(**mod.lm_fields(config))
    assert (cfg.n_routed_experts, cfg.held, cfg.vocab_size, cfg.rows) == (
        128, (0, 16), 151936, (0, 18992))
    assert (cfg.layers, cfg.first_k_dense, cfg.mtp_layers, cfg.shared_hidden) == (6, 0, 0, 0)
    assert cfg.kinds == ("full_attention",) * 6
    assert (cfg.dim, cfg.heads, cfg.kv_heads, cfg.head_dim) == (2048, 32, 4, 128)
    assert (cfg.expert_hidden, cfg.experts_per_token, cfg.routed_scaling_factor,
            cfg.rms_eps) == (768, 8, 1.0, 1e-6)
    assert (cfg.diffusion_block, cfg.mask_id) == (4, 18991)
    assert (cfg.qk_norm, cfg.tie_embeddings, cfg.attn_gate) == (True, False, False)
    assert (cfg.router_input, cfg.router_scoring, cfg.expert_act) == (
        "ffn_norm", "softmax_topk", "silu")
    assert cfg.rope("full_attention") == lm.Rope(rope_theta=1000000)
    # a key it has no account of, a value that is not implemented and a
    # restated key that contradicts its source are each refused
    with pytest.raises(ValueError, match="no account of.*layer_types"):
        mod.lm_fields(config | {"layer_types": ["full_attention"] * 6})
    for key, other in [("model_type", "qwen3_moe"), ("attention_bias", True),
                       ("norm_topk_prob", False), ("decoder_sparse_step", 2),
                       ("mlp_only_layers", [0]), ("use_sliding_window", True),
                       ("tie_word_embeddings", True), ("num_nextn_predict_layers", 1)]:
        with pytest.raises(ValueError, match=f"{key} = .* is implemented"):
            mod.lm_fields(config | {key: other})
    for key, other in [("num_experts", 128), ("vocab_size", 151936), ("mask_token_id", 0)]:
        with pytest.raises(ValueError, match=f"{key} = .* contradicts"):
            mod.lm_fields(config | {key: other})
    # the least noise level is a constant of the program's, and the q/k norms'
    # seeded scale the benchmark's weights' alone: no field of the program takes either
    with pytest.raises(ValueError, match="diffusion_noise_eps = 0.01: the program's"):
        mod.lm_fields(config | {"diffusion_noise_eps": 0.01})
    assert not {"diffusion_eps", "qk_norm_init"} & set(mod.lm_fields(config))


def test_the_cell_is_the_other_language_cells_traffic_in_clean_tokens():
    """2 x 8192 clean tokens a step, 8 distinct batches, a fetch every 5th
    step, a 4 s traced window: the JoyAI cell's traffic to the number, through
    a generator of this family's own (a row is the clean ids alone, and never
    the mask id). A sample is one sequence and a token a clean token; the cell
    is on one chip, and its FLOP count is the program's."""
    cell, other = load_cell(), load_cell("joyai_flash_pretrain_2x8k")
    numbers = lambda t: {k: v for k, v in t.items() if k not in ("driver", "why")}
    assert numbers(cell["traffic"]) == numbers(other["traffic"]) == {
        "sequences_per_chip": 2, "seq": 8192, "distinct_batches": 8, "fetch_every": 5,
        "trace_seconds": 4}
    assert 2 * cell["traffic"]["seq"] <= cell["config"]["max_position_embeddings"]
    mod = driver_of(cell)
    assert mod.token_batches.__module__ == "benchmarks.drivers.blockdiff_lm_steps"
    assert {"train_img_per_s", "setup_s"} == {m["name"] for m in cell["end_to_end"]}
    assert cell["chips"] == 1
    batch = next(mod.token_batches(3_000_000_123, cell["config"], 2, 8192, 8))["tokens"]
    assert batch.shape == (2, 8192) and 0 <= batch.min() and batch.max() < 18991
    driver = object.__new__(mod.Driver)
    t = cell["traffic"]
    driver.config, driver.batch, driver.seq = cell["config"], t["sequences_per_chip"], t["seq"]
    record = driver.work(20) | {"window_s": 22.0, "chips": 1}
    img = harness.load_module("metrics", "train_img_per_s").read(record)
    tok = harness.load_module("metrics", "train_tok_per_s").read(record)
    assert record["images"] == 40 and tok == pytest.approx(img * 8192, rel=1e-12)
    assert record["work_flops"] == pytest.approx(20 * 71.57e12, rel=1e-3)
    ours, programs = mod.flops_pair(cell["config"])
    assert ours == pytest.approx(programs, rel=1e-12) and ours > 0


def test_the_cell_reports_the_grouped_query_familys_parts_and_five_new_ones():
    """The benchmark gained one configuration, one cell and five per-layer
    entries; the cell is in the lists of the readers whose parts it runs and
    in none whose reader would find nothing."""
    cell, bench = load_cell(), load_bench()
    names = {m["name"] for m in cell["per_layer"]}
    assert {"gqa_proj_ms.lm", "rope_ms.lm", "router_ms.lm", "moe_dispatch_ms.lm",
            "experts_ms.lm", "lm_head_ms.lm", "moe_imbalance.lm", "moe_dropped.lm",
            "train_tok_per_s.lm", "experts_roofline.lm", "bd_core_ms.lm", "bd_core_roofline.lm",
            "bd_overcompute.lm", "bd_noise_ms.lm", "bd_masked_share.lm", "mfu.train",
            "device_step_ms.train", "fwd_ms.train", "unscoped_ms.train", "jit_trace_s",
            "setup_spanned_share"} <= names
    assert not {n for n in names if n.startswith(
        ("attn_core", "mla_", "mtp_", "kda_", "swa_", "sconv_", "expert_zero", "enc_", "dec_",
         "jumbo_"))}
    for name, unit, better, source in (
            ("bd_core_ms.lm", "ms", "lower", "device_trace"),
            ("bd_core_roofline.lm", "%", "higher", "device_trace"),
            ("bd_overcompute.lm", "x", "lower", "program_counter"),
            ("bd_noise_ms.lm", "ms", "lower", "device_trace"),
            ("bd_masked_share.lm", "%", "higher", "program_counter")):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry == {"name": name, "unit": unit, "better": better, "source": source,
                         "layer": "step program", "moves": "train_img_per_s",
                         "workloads": [CELL]}
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert workload["config"] == CONFIG and len(workload["why"]) <= 200
    assert "1/8" in workload["why"] and "8x" in workload["why"]  # how near its deployment's load


def test_the_new_readers_find_nothing_where_the_program_has_no_such_part():
    """On a record of another family, or of a program without the scopes and
    counters (the parent of the PR that added them), each reader returns None
    and does not raise; on this family's it reads the part's time, its share
    of the roofline, the static count and the counter. The older readers find
    this family's parts under their names."""
    part = lambda name, record: harness.load_module("metrics", name).read(record)
    new = ("bd_core_ms", "bd_core_roofline", "bd_overcompute", "bd_noise_ms", "bd_masked_share")
    other = {"_scope_table": {("fwd", "trunk_attn_core"): 9.0, ("bwd", "trunk_gqa_proj"): 6.0},
             "_kernel_table": {("fwd", "trunk_attn_core"): 9e-3},
             "kernel_work": {"attn_core": {"flops": 1e12, "bytes": 1e9}},
             "attn_pairs": {"full_attention": {"visited": 9, "needed": 8}},
             "moe": {"dropped": 0.0}, "device_kind": "TPU v5 lite"}
    for record in ({}, {"_scope_table": None, "_kernel_table": None}, other):
        assert [part(name, record) for name in new] == [None] * 5
    record = {"_scope_table": {("fwd", "trunk_bd_core"): 100.0, ("bwd", "trunk_bd_core"): 300.0,
                               ("fwd", "bd_noise"): 0.25, ("fwd", "trunk_gqa_proj"): 7.0,
                               ("bwd", "trunk_rope"): 1.0, ("recompute", "trunk_router"): 2.0},
              "_kernel_table": {("fwd", "trunk_bd_core"): 0.1, ("bwd", "trunk_bd_core"): 0.3},
              "kernel_work": {"bd_core": {"flops": 39.4e12, "bytes": 9e9}},
              "attn_pairs": {"block_diffusion": {"visited": 68 * 2**20, "needed": 67_141_632}},
              "bd": {"masked_share": 0.4996}, "device_kind": "TPU v5 lite"}
    assert (part("bd_core_ms", record), part("bd_noise_ms", record)) == (400.0, 0.25)
    assert (part("gqa_proj_ms", record), part("rope_ms", record), part("router_ms", record)) == (
        7.0, 1.0, 2.0)
    assert part("bd_overcompute", record) == pytest.approx(1.062, abs=1e-3)
    assert part("bd_masked_share", record) == pytest.approx(49.96)
    flops = harness.load_module("metrics", "bd_core_roofline").kernel_roofline.flops
    with pytest.MonkeyPatch.context() as patch:  # the real table of peaks: the operations bind
        patch.setattr(flops, "peak", lambda kind, key="bf16_flops": {
            "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}[key])
        assert part("bd_core_roofline", record) == pytest.approx(50.0)  # 200 ms of 400


def test_the_scope_table_is_the_grouped_query_familys_over_six_blocks_and_two_new_parts():
    from benchmarks import scope_reduce

    root = harness.ROOT / "benchmarks" / "scopes"
    new, gqa = (scope_reduce.vocabulary(root / f"{n}.json") for n in ("blockdiff_lm", "gqa_lm"))
    assert set(new["parts"]) - set(gqa["parts"]) == {"trunk_bd_core", "mtp_bd_core", "bd_noise"}
    assert set(gqa["parts"]) <= set(new["parts"])
    path = "jit(_train_step)/jvp(MlaMoeLM)/{}"
    for where, want in [("block_0/attn/gqa_proj/q_norm/rsqrt", "trunk_gqa_proj"),
                        ("block_5/attn/bd_core/causal_attention_fwd/pallas_call", "trunk_bd_core"),
                        ("block_3/attn/rope/rope_half/pallas_call", "trunk_rope"),
                        ("block_3/attn/attn_out/out/dot_general", "trunk_attn_out"),
                        ("block_2/moe/router/top_k", "trunk_router"),
                        ("block_2/moe/moe_dispatch/while/body/experts/gmm/pallas_call",
                         "trunk_experts"),
                        ("bd_noise/threefry2x32", "bd_noise"), ("bd_noise/lt", "bd_noise"),
                        ("embed/gather", "embed"), ("lm_head/while/body/dot_general", "lm_head")]:
        assert scope_reduce.classify(path.format(where), new) == ("fwd", want), where
    table = lambda name: json.loads((root / f"{name}.json").read_text())
    assert [r for r in table("blockdiff_lm")["in_a_tower"] if r not in table("gqa_lm")[
        "in_a_tower"]] == [{"scope": "bd_core", "part": "{tower}_bd_core"}]
    assert [r for r in table("blockdiff_lm")["rules"] if r not in table("gqa_lm")["rules"]] == [
        {"scope": "bd_noise", "part": "bd_noise"}]
    assert [r for r in table("gqa_lm")["rules"] if r not in table("blockdiff_lm")["rules"]] == [
        {"scope": f"block_{i}", "tower": "trunk"} for i in (6, 7)]


def test_every_list_that_names_the_cell_is_in_the_benchmarks_own_order():
    """A PR appends: each list that names the cell names cells in the order
    ``workloads`` has them, with the cell after every cell that was there
    before it (containment and relative order: nothing here counts the
    benchmark or names a last place)."""
    bench = load_bench()
    order = [w["name"] for w in bench["workloads"]]
    before = order[: order.index(CELL)]
    assert {"l16_pretrain_b128", "joyai_flash_pretrain_2x8k", "ling3_flash_pretrain_8k",
            "laguna_xs2_pretrain_2x8k", "solar_open2_pretrain_2x8k",
            "smallthinker_pretrain_1x16k", "lfm2_24b_pretrain_2x8k"} <= set(before)
    listed = [m for key in ("end_to_end", "per_layer") for m in bench[key] if "workloads" in m]
    mine = [m for m in listed if CELL in m["workloads"]]
    assert {"train_img_per_s", "gqa_proj_ms.lm", "rope_ms.lm", "experts_roofline.lm",
            "bd_core_roofline.lm"} <= {m["name"] for m in mine}
    assert not {"attn_core_ms.lm", "attn_core_roofline.lm", "swa_core_ms.lm",
                "expert_zero_share.lm"} & {m["name"] for m in mine}
    for metric in listed:
        assert metric["workloads"] == [name for name in order if name in metric["workloads"]]
    for metric in mine:
        at = metric["workloads"].index(CELL)
        assert set(metric["workloads"][:at]) <= set(before)
    configs = [c["name"] for c in bench["configs"]]
    assert set(configs[: configs.index(CONFIG)]) >= {"smallthinker_21b_ep4", "lfm2_24b_a2b_ep8"}
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("bd_core_ms.lm") > names.index("sconv_mix_roofline.lm")
    assert re.fullmatch(r"[\w.\-]{1,64}", CELL)
