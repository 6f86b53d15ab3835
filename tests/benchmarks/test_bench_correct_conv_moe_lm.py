"""``correct`` has to be able to come out false in the short-convolution /
grouped-query family's cell: seven mutations of the program (the filter's
input gate or its output gate left out, a tap dropped, the filter looking one
token ahead, no q/k norm, a head that is not the embedding, scores scaled by
the wrong width: each through the driver's own warm-up and check) fail the
cell's check at the test size, the lower-precision control fails it, and the
driver has an account of every key of the configuration file.
``test_bench_rehearsal`` and ``test_bench_yardstick`` run the cell traced and
untraced and hold its FLOP count to the program's, as they do for every cell
of ``BENCHMARK.json``.

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
CELL, CONFIG = "lfm2_24b_pretrain_2x8k", "lfm2_24b_a2b_ep8"
SEED = 2_147_484_044


def load_cell(name: str = CELL) -> dict:
    return harness.load_cell(name)


def _gate_of_ones(third: int):
    """``W_in``'s output with one of its thirds set to 1: that gate (0: the
    filter's input gate ``B``; 1: its output gate ``C``) multiplies nothing."""
    real = lm.Proj.__call__

    def call(self, x):
        z = real(self, x)
        if self.name != "in_proj":
            return z
        d = z.shape[-1] // 3
        return z.at[..., third * d:(third + 1) * d].set(1.0)

    return lm.Proj, "__call__", call


def _filter(change):
    real = lm.causal_conv
    return lm, "causal_conv", lambda x, w, act: change(real, x, w, act)


def _no_qk_norm():
    real = lm.RMSNorm.__call__

    def call(self, x):
        y = real(self, x)  # the scale stays in the tree
        return x.astype(y.dtype) if self.name in ("q_norm", "k_norm") else y

    return lm.RMSNorm, "__call__", call


def _untied_head():
    """The head's kernel holds the embedding's values and is not the
    embedding: the lookup's gradient alone reaches it."""
    real = lm.MlaMoeLM._head_kernel
    return lm.MlaMoeLM, "_head_kernel", lambda self: jax.lax.stop_gradient(real(self))


def _scaled_for_twice_the_width():
    real = lm.causal_attention
    return lm, "causal_attention", lambda q, *rest, **kw: real(q * 2 ** -0.5, *rest, **kw)


# name -> () -> (owner, attribute, replacement)
MUTATIONS = {
    "no_input_gate": lambda: _gate_of_ones(0),
    "no_output_gate": lambda: _gate_of_ones(1),
    "a_tap_dropped": lambda: _filter(lambda real, x, w, act: real(x, w.at[0].set(0.0), act)),
    "one_token_ahead": lambda: _filter(
        lambda real, x, w, act: jnp.roll(real(x, w, act), -1, axis=-2)),
    "no_qk_norm": _no_qk_norm,
    "untied_head": _untied_head,
    "scale_of_twice_the_width": _scaled_for_twice_the_width,
}


def _peaked(monkeypatch):
    """The seeded query, key, filter, router and expert matrices scaled up, in
    the program and the reference alike (both take their weights from
    ``conv_moe_lm_params.make_params``): the scores, the filters' outputs, the
    routers' logits and the experts' outputs then spread as the real cut's do
    at its seeded weights (2048 inputs of 0.02 against the tiny cut's 32,
    which leave every softmax flat, the filter's output a thousandth of its
    input and a mutation of them without effect)."""
    from benchmarks.reference import conv_moe_lm_params

    real = conv_moe_lm_params.make_params

    def make_params(seed, c):
        params = real(seed, c)
        for name in [n for n in params if n.startswith("block_")]:
            blk = params[name]
            leaves = [blk["attn"]["q"], blk["attn"]["k"]] if "attn" in blk else [
                blk["conv"]["in_proj"], blk["conv"]["conv"], blk["conv"]["out_proj"]]
            if "moe" in blk:
                leaves += [blk["moe"][k] for k in ("router", "gate", "up", "down")]
            for leaf in leaves:
                leaf["kernel"] = leaf["kernel"] * 8.0
        return params

    monkeypatch.setattr(conv_moe_lm_params, "make_params", make_params)


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
    three steps from this seed's weights and tokens. Read once for the
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
    loss finite."""
    mod = driver_of(load_cell())
    limits, reference = sound
    _peaked(monkeypatch)
    for key, limit in limits.items():
        monkeypatch.setitem(mod.LIMITS, key, limit)
    monkeypatch.setattr(mod, "reference_run", lambda *a, **k: reference)
    monkeypatch.setattr(*MUTATIONS[mutation]())
    checks, printed = _checks_in_float32()
    assert not all(value <= limit for _, value, limit in checks), checks
    assert all(value == value for _, value, _ in checks)  # the losses stay finite


def test_the_lower_precision_control_fails_the_limits_the_sound_run_passes(sound, monkeypatch):
    """The reference in the program's place at test size, on ``sound``'s seed,
    weights and tokens: computed in fp8 (the control), whole or in the conv
    block alone, it fails one of the limits."""
    cell = tiny_cell(load_cell())
    mod = driver_of(cell)
    _peaked(monkeypatch)
    config, t = cell["config"], cell["traffic"]
    gen = mod.token_batches(SEED, config, t["sequences_per_chip"], t["seq"], 2)
    batches = [next(gen)["tokens"] for _ in range(train_loop.CHECK_STEPS)]
    ref = sound[1]
    limits = mod.LIMITS | mod.TINY_LIMITS
    for rounding in (mod.CONTROL, mod.ONE_BLOCK_CONTROLS[0]):
        control = mod.reference_run(config, SEED, batches, rounding=rounding)
        assert not all(v <= limit for _, v, limit in train_loop.compare(control, ref, limits)), (
            rounding)


# the catalog's ``config`` of LFM2-24B-A2B, every key
# (/opt/skills/guides/model-configs/architectures.jsonl): a number the file
# changes is in ``reduced``
PERIOD = ["full_attention", "conv", "conv", "conv"]
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + PERIOD * 9 + ["full_attention", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}


def test_the_driver_has_an_account_of_every_key_of_the_configuration_file():
    """Every key is translated, required to hold the one value that is
    implemented, held to the keys it restates, or about the file — and none
    of those accounts names a key the file lacks; every key of the catalog's
    ``config`` is in the file under its own name, as published or, where
    ``reduced`` names it, as this chip's share beside the published count."""
    from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig

    cell = load_cell()
    mod, config = driver_of(cell), cell["config"]
    assert set(config) == mod.KEYS
    kinds = [set(mod._FIELDS) | set(mod._PUBLISHED), mod._DERIVED - set(mod._CONSISTENT),
             set(mod._REQUIRED) - {"num_nextn_predict_layers"},
             set(mod._CONSISTENT) - set(mod._PUBLISHED), mod._ABOUT]
    assert sum(map(len, kinds)) == len(mod.KEYS)  # one account a key
    named = {"tie_word_embeddings", "head_dim", "router_bias", "router_denominator",
             "rope_pairing", "qk_norm", "conv", "init", "optim"}
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
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    # the guide's floors: whole periods and four layers after the dense ones,
    # 8 experts, an eighth of the vocabulary
    held = config["layer_types"][config["first_layer"]:][: config["num_hidden_layers"]]
    assert held == ["conv"] + PERIOD * 2 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    assert "8 chips share each layer" in config["deployment"]
    assert "4 pipeline stages" in config["deployment"] and "layers 1-9" in config["deployment"]
    cfg = MlaMoeConfig(**mod.lm_fields(config))
    assert (cfg.n_routed_experts, cfg.held, cfg.vocab_size, cfg.rows) == (
        64, (0, 8), 65536, (0, 8192))
    assert (cfg.layers, cfg.first_k_dense, cfg.mtp_layers, cfg.shared_hidden) == (9, 1, 0, 0)
    assert cfg.kinds == tuple(held) and cfg.layers_by_kind == {"conv": 7, "full_attention": 2}
    assert (cfg.dim, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.conv_taps) == (2048, 32, 8, 64, 3)
    assert (cfg.dense_hidden, cfg.expert_hidden, cfg.experts_per_token,
            cfg.routed_scaling_factor, cfg.rms_eps, cfg.router_bias_rate) == (
        11776, 1536, 4, 1, 1e-5, 0.001)
    assert (cfg.qk_norm, cfg.tie_embeddings, cfg.attn_gate) == (True, True, False)
    assert (cfg.router_input, cfg.router_scoring, cfg.expert_act) == (
        "ffn_norm", "sigmoid_bias", "silu")
    assert cfg.rope("full_attention") == lm.Rope(rope_theta=1000000)
    # a key it has no account of, a value that is not implemented and a
    # restated key that contradicts its source are each refused
    with pytest.raises(ValueError, match="no account of.*sliding_window"):
        mod.lm_fields(config | {"sliding_window": 512})
    for key, other in [("model_type", "lfm2"), ("conv_bias", True), ("norm_topk_prob", False),
                       ("use_expert_bias", False), ("tie_word_embeddings", False),
                       ("num_nextn_predict_layers", 1)]:
        with pytest.raises(ValueError, match=f"{key} = .* is implemented"):
            mod.lm_fields(config | {key: other})
    for key, other in [("first_layer", 0), ("num_experts", 64), ("vocab_size", 65536)]:
        with pytest.raises(ValueError, match=f"{key} = .* contradicts"):
            mod.lm_fields(config | {key: other})
    with pytest.raises(ValueError, match="rope_parameters"):
        mod.lm_fields(config | {"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}})


def test_the_cell_is_the_other_language_cells_traffic_to_the_number():
    """2 x 8192 tokens a step, 8 distinct batches, a fetch every 5th step, a
    4 s traced window, through the same generator: the JoyAI cell's traffic
    to the number. A sample is one sequence, and ``train_tok_per_s.lm`` the
    same in tokens. The cell is on one chip, and its FLOP count is the
    program's."""
    cell, other = load_cell(), load_cell("joyai_flash_pretrain_2x8k")
    numbers = lambda t: {k: v for k, v in t.items() if k not in ("driver", "why")}
    assert numbers(cell["traffic"]) == numbers(other["traffic"]) == {
        "sequences_per_chip": 2, "seq": 8192, "distinct_batches": 8, "fetch_every": 5,
        "trace_seconds": 4}
    assert cell["traffic"]["seq"] <= cell["config"]["max_position_embeddings"]
    mod = driver_of(cell)
    assert mod.token_batches.__module__ == "benchmarks.drivers.lm_steps"
    assert {"train_img_per_s", "setup_s"} == {m["name"] for m in cell["end_to_end"]}
    assert cell["chips"] == 1
    batch = next(mod.token_batches(3_000_000_123, cell["config"], 2, 8192, 8))["tokens"]
    assert batch.shape == (2, 8193) and 0 <= batch.min() and batch.max() < 8192
    driver = object.__new__(mod.Driver)
    t = cell["traffic"]
    driver.config, driver.batch, driver.seq = cell["config"], t["sequences_per_chip"], t["seq"]
    record = driver.work(20) | {"window_s": 22.0, "chips": 1}
    img = harness.load_module("metrics", "train_img_per_s").read(record)
    tok = harness.load_module("metrics", "train_tok_per_s").read(record)
    assert record["images"] == 40 and tok == pytest.approx(img * 8192, rel=1e-12)
    assert record["work_flops"] == pytest.approx(20 * 29.48e12, rel=1e-3)
    ours, programs = mod.flops_pair(cell["config"])
    assert ours == pytest.approx(programs, rel=1e-12) and ours > 0


def test_the_cell_reports_the_grouped_query_familys_parts_and_three_new_ones():
    """The benchmark gained one configuration, one cell and three per-layer
    entries; the cell is in the lists of the readers whose parts it runs and
    in none whose reader would find nothing."""
    cell, bench = load_cell(), load_bench()
    names = {m["name"] for m in cell["per_layer"]}
    assert {"gqa_proj_ms.lm", "attn_core_ms.lm", "rope_ms.lm", "router_ms.lm",
            "moe_dispatch_ms.lm", "experts_ms.lm", "lm_head_ms.lm", "moe_imbalance.lm",
            "moe_dropped.lm", "train_tok_per_s.lm", "attn_core_roofline.lm",
            "experts_roofline.lm", "sconv_proj_ms.lm", "sconv_mix_ms.lm",
            "sconv_mix_roofline.lm", "mfu.train", "device_step_ms.train", "fwd_ms.train",
            "unscoped_ms.train", "jit_trace_s", "setup_spanned_share"} <= names
    assert not {n for n in names
                if n.startswith(("mla_", "mtp_", "kda_", "swa_", "enc_", "dec_", "jumbo_"))}
    for name, unit, better in (("sconv_proj_ms.lm", "ms", "lower"),
                               ("sconv_mix_ms.lm", "ms", "lower"),
                               ("sconv_mix_roofline.lm", "%", "higher")):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry == {"name": name, "unit": unit, "better": better, "source": "device_trace",
                         "layer": "step program", "moves": "train_img_per_s",
                         "workloads": [CELL]}
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert workload["config"] == CONFIG and len(workload["why"]) <= 200
    assert "1/8" in workload["why"] and "8x" in workload["why"]  # how near its deployment's load


def test_the_new_readers_find_nothing_where_the_program_has_no_such_part():
    """On a record of another family, or of a program without the scopes (the
    parent of the PR that added them), each reader returns None and does not
    raise; on this family's it reads the part's time and its share of the
    roofline. The older readers find this family's parts under their names."""
    part = lambda name, record: harness.load_module("metrics", name).read(record)
    other = {"_scope_table": {("fwd", "trunk_attn_core"): 9.0, ("bwd", "trunk_gqa_proj"): 6.0},
             "kernel_work": {"attn_core": {"flops": 1e12, "bytes": 1e9}},
             "device_kind": "TPU v5 lite"}
    for record in ({}, {"_scope_table": None}, other):
        assert part("sconv_proj_ms", record) is None and part("sconv_mix_ms", record) is None
        assert part("sconv_mix_roofline", record) is None
    record = {"_scope_table": {("fwd", "trunk_sconv_proj"): 30.0, ("bwd", "trunk_sconv_proj"): 60.0,
                               ("fwd", "trunk_sconv_mix"): 4.0, ("recompute", "trunk_sconv_mix"): 4.0,
                               ("bwd", "trunk_sconv_mix"): 12.0, ("fwd", "trunk_attn_core"): 9.0,
                               ("fwd", "trunk_gqa_proj"): 7.0, ("bwd", "trunk_rope"): 1.0,
                               ("recompute", "trunk_router"): 2.0},
              "kernel_work": {"sconv_mix": {"flops": 6.6e9, "bytes": 8.19e9}},
              "device_kind": "TPU v5 lite"}
    assert (part("sconv_proj_ms", record), part("sconv_mix_ms", record)) == (90.0, 20.0)
    assert (part("attn_core_ms", record), part("gqa_proj_ms", record), part("rope_ms", record),
            part("router_ms", record)) == (9.0, 7.0, 1.0, 2.0)
    flops = harness.load_module("metrics", "sconv_mix_roofline").flops
    with pytest.MonkeyPatch.context() as patch:  # the real table of peaks: the bytes bind
        patch.setattr(flops, "peak", lambda kind, key="bf16_flops": {
            "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}[key])
        assert part("sconv_mix_roofline", record) == pytest.approx(50.0)  # 10 ms of 20


def test_the_scope_table_is_the_grouped_query_familys_over_nine_blocks_and_two_new_parts():
    from benchmarks import scope_reduce

    root = harness.ROOT / "benchmarks" / "scopes"
    new, gqa = (scope_reduce.vocabulary(root / f"{n}.json") for n in ("conv_moe_lm", "gqa_lm"))
    assert set(new["parts"]) - set(gqa["parts"]) == {
        "trunk_sconv_proj", "trunk_sconv_mix", "mtp_sconv_proj", "mtp_sconv_mix"}
    assert set(gqa["parts"]) <= set(new["parts"])
    path = "jit(_train_step)/jvp(MlaMoeLM)/block_{}/{}"
    for where, want in [((0, "conv/sconv_in/in_proj/dot_general"), "trunk_sconv_proj"),
                        ((8, "conv/sconv_out/out_proj/dot_general"), "trunk_sconv_proj"),
                        ((4, "conv/sconv_mix/mul"), "trunk_sconv_mix"),
                        ((0, "mlp/dense_mlp/gate/dot_general"), "trunk_dense_mlp"),
                        ((1, "attn/gqa_proj/q_norm/rsqrt"), "trunk_gqa_proj"),
                        ((1, "attn/attn_core/causal_attention_fwd/pallas_call"), "trunk_attn_core"),
                        ((5, "attn/rope/rope_half/pallas_call"), "trunk_rope"),
                        ((5, "attn/attn_out/out/dot_general"), "trunk_attn_out"),
                        ((2, "moe/router/top_k"), "trunk_router"),
                        ((2, "moe/moe_dispatch/while/body/experts/gmm/pallas_call"),
                         "trunk_experts")]:
        assert scope_reduce.classify(path.format(*where), new) == ("fwd", want)
    table = lambda name: json.loads((root / f"{name}.json").read_text())
    assert [r for r in table("conv_moe_lm")["in_a_tower"] if r not in table("gqa_lm")[
        "in_a_tower"]] == [{"scope": "sconv_mix", "part": "{tower}_sconv_mix"},
                           {"scope": "sconv_in", "part": "{tower}_sconv_proj"},
                           {"scope": "sconv_out", "part": "{tower}_sconv_proj"}]
    assert [r for r in table("conv_moe_lm")["rules"] if r not in table("gqa_lm")["rules"]] == [
        {"scope": "block_8", "tower": "trunk"}]


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
            "smallthinker_pretrain_1x16k"} <= set(before)
    listed = [m for key in ("end_to_end", "per_layer") for m in bench[key] if "workloads" in m]
    mine = [m for m in listed if CELL in m["workloads"]]
    assert {"train_img_per_s", "attn_core_ms.lm", "rope_ms.lm", "sconv_mix_roofline.lm"} <= {
        m["name"] for m in mine}
    for metric in listed:
        assert metric["workloads"] == [name for name in order if name in metric["workloads"]]
    for metric in mine:
        at = metric["workloads"].index(CELL)
        assert set(metric["workloads"][:at]) <= set(before)
    configs = [c["name"] for c in bench["configs"]]
    assert set(configs[: configs.index(CONFIG)]) >= {"laguna_xs2_share", "smallthinker_21b_ep4"}
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("sconv_proj_ms.lm") > names.index("expert_zero_share.lm")
    assert re.fullmatch(r"[\w.\-]{1,64}", CELL)
