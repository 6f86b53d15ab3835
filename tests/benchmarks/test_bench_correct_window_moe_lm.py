"""``correct`` has to be able to come out false in the window / rope-free-full
grouped-query family's cell: two mutations of the program (the router fed
the post-attention norm, a SiLU gate in the ReLU's: a run of the harness each,
so the two that only this family's expert layer can show; all four of
``tests/test_window_moe_lm_model.py`` are held there against the reference
itself) fail the cell's check at the test size, the lower-precision control
fails it, and the driver has an
account of every key of the configuration file. ``test_bench_rehearsal`` and
``test_bench_yardstick`` run the cell traced and untraced and hold its FLOP
count to the program's, as they do for every cell of ``BENCHMARK.json``.

What these tests say of ``BENCHMARK.json``'s lists is containment and
relative order only — no length, no last place — so that the next cell
breaks none of them."""

import contextlib
import io
import re
import time

import pytest

from bench_tiny import driver_of, load_bench, tiny_cell
from benchmarks import harness
from benchmarks.drivers import train_loop
from jumbo_mae_tpu_tpu.models import lm

pytestmark = pytest.mark.usefixtures("cpu_has_no_peak")
CELL, CONFIG = "smallthinker_pretrain_1x16k", "smallthinker_21b_ep4"
SEED = 2_147_484_011


def load_cell(name: str = CELL) -> dict:
    return harness.load_cell(name)


def _router_fed_the_post_attention_norm():
    """The expert layer's second input dropped: the router reads what the
    experts read."""
    real = lm.SparseExperts.__call__
    return lm.SparseExperts, "__call__", lambda self, x, router_x=None: real(self, x)


def _silu_for_relu():
    return lm.nn, "relu", lm.nn.silu


# name -> () -> (owner, attribute, replacement)
MUTATIONS = {
    "router_fed_the_post_attention_norm": _router_fed_the_post_attention_norm,
    "silu_for_relu": _silu_for_relu,
}


def _peaked(monkeypatch):
    """The seeded query, key, router and expert matrices scaled up, in the
    program and the reference alike (both take their weights from
    ``window_moe_lm_params.make_params``): the scores, the routers' logits and
    the experts' outputs then spread as the real cut's do at its seeded
    weights (2560 inputs of 0.02 against the tiny cut's 32, which leave every
    softmax flat, every expert's output a thousandth of the stream and a
    mutation of them without effect)."""
    from benchmarks.reference import window_moe_lm_params

    real = window_moe_lm_params.make_params

    def make_params(seed, c):
        params = real(seed, c)
        for name in [n for n in params if n.startswith("block_")]:
            blk = params[name]
            for leaf in (blk["attn"]["q"], blk["attn"]["k"], blk["moe"]["router"],
                         blk["moe"]["gate"], blk["moe"]["up"], blk["moe"]["down"]):
                leaf["kernel"] = leaf["kernel"] * 8.0
        return params

    monkeypatch.setattr(window_moe_lm_params, "make_params", make_params)


def _run_in_float32(scratch) -> tuple[dict, str]:
    """The tiny cell computed in float32 (at 32 wide bfloat16's rounding alone
    reads more on a gradient leaf than some mutations move it): ``(result,
    what the run printed)``."""
    cell = tiny_cell(load_cell())
    cell["config"]["compute_dtype"] = "float32"
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        result = harness.run_cell(cell, seed=SEED, seconds=0.4, trace=False,
                                  t0=time.perf_counter(), require_tpu=False,
                                  compile_cache=False, scratch=scratch)
    return result, printed.getvalue()


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
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
        result, printed = _run_in_float32(tmp_path_factory.mktemp("sound"))
    assert result["correct"] and len(kept) == 1, printed
    sound = {name: float(value) for name, value in re.findall(
        r"^check (\w+_gap): (\S+) \(limit", printed, re.M)}
    assert set(sound) == set(mod.TINY_LIMITS)
    assert all(3 * sound[key] < mod.TINY_LIMITS[key] for key in sound), sound
    # the run's own line of counters: a ReLU's zeros, nothing dropped, where the router reads
    counters = re.search(r'^counters over \d+ steps: (\{.*\})$', printed, re.M).group(1)
    assert '"router_input": "block_input"' in counters and '"dropped": 0.0' in counters
    assert 0.3 < float(re.search(r'"act_zero_share": ([\d.]+)', counters).group(1)) < 0.7
    return {key: 3 * reading for key, reading in sound.items()}, kept[0]


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_a_mutated_program_is_not_correct(mutation, tmp_path, monkeypatch, sound):
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
    result, printed = _run_in_float32(tmp_path)
    assert result["correct"] is False, printed
    assert "FAILED" in printed and result["failed"] == 0  # the losses stay finite


def test_the_lower_precision_control_fails_the_limits_the_sound_run_passes(sound, monkeypatch):
    """The reference in the program's place at test size, on ``sound``'s seed,
    weights and tokens (its float32 reference is the one already computed, and
    the program itself has passed three times under these limits there):
    computed in fp8 (the control) it fails one of them."""
    cell = tiny_cell(load_cell())
    mod = driver_of(cell)
    _peaked(monkeypatch)
    config, t = cell["config"], cell["traffic"]
    gen = mod.token_batches(SEED, config, t["sequences_per_chip"], t["seq"], 2)
    batches = [next(gen)["tokens"] for _ in range(train_loop.CHECK_STEPS)]
    ref = sound[1]
    control = mod.reference_run(config, SEED, batches, rounding=mod.CONTROL)
    limits = mod.LIMITS | mod.TINY_LIMITS
    assert not all(v <= limit for _, v, limit in train_loop.compare(control, ref, limits))


# the catalog's ``config`` of SmallThinker-21BA3B-Instruct, every key
# (/opt/skills/guides/model-configs/architectures.jsonl): a number the file
# changes is in ``reduced``
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1] * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936,
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
    kinds = [set(mod._FIELDS) | set(mod._PUBLISHED), mod._DERIVED,
             set(mod._REQUIRED) - {"num_nextn_predict_layers"},
             set(mod._CONSISTENT) - set(mod._PUBLISHED), mod._ABOUT]
    assert sum(map(len, kinds)) == len(mod.KEYS)  # one account a key
    assert {"router_input", "rope_pairing", "dense_layers", "attention_bias_and_qk_norm",
            "secondary_experts", "expert_activation", "init", "optim"} <= set(config["assumed"])
    assert all("other reading" in config["assumed"][key] for key in (
        "router_input", "rope_pairing", "dense_layers", "attention_bias_and_qk_norm",
        "secondary_experts", "expert_activation"))
    entry = next(c for c in load_bench()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and entry["file"].endswith(f"{CONFIG}.json")
    assert set(entry["reduced"]) == set(config["reduced"]) == set(config["reduced_why"])
    published = config["published"]
    for key, value in CATALOG.items():
        if key in entry["reduced"]:
            assert config[key] != value and published[key] == value, key
        else:
            assert config[key] == value, key
    assert {k for k in CATALOG if k in entry["reduced"]} == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"}
    # the guide's floors: a whole period and four layers, 8 experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 4 and config["moe_num_primary_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    assert "4 chips share each layer" in config["deployment"] and "13 pipeline stages" in config[
        "deployment"]
    cfg = MlaMoeConfig(**mod.lm_fields(config))
    assert (cfg.n_routed_experts, cfg.held, cfg.vocab_size, cfg.rows) == (
        64, (0, 16), 151936, (0, 37984))
    assert (cfg.layers, cfg.first_k_dense, cfg.mtp_layers, cfg.shared_hidden) == (4, 0, 0, 0)
    assert cfg.kinds == ("full_attention", "sliding_attention", "sliding_attention",
                         "sliding_attention")
    assert (cfg.dim, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.sliding_window) == (
        2560, 28, 4, 128, 4096)
    assert (cfg.expert_hidden, cfg.experts_per_token, cfg.routed_scaling_factor, cfg.rms_eps,
            cfg.attn_gate) == (768, 6, 1.0, 1e-6, False)
    assert (cfg.router_input, cfg.router_scoring, cfg.expert_act) == (
        "block_input", "softmax_topk", "relu")
    assert cfg.rope("full_attention") is None
    assert cfg.rope("sliding_attention") == lm.Rope(rope_theta=1500000)
    # a key it has no account of, a value that is not implemented and a
    # restated key that contradicts its source are each refused
    with pytest.raises(ValueError, match="no account of.*intermediate_size"):
        mod.lm_fields(config | {"intermediate_size": 6912})
    for key, other in [("model_name", "smallthinker_4b_instruct"),
                       ("moe_primary_router_apply_softmax", False), ("norm_topk_prob", False),
                       ("rope_scaling", {"rope_type": "yarn"}), ("tie_word_embeddings", True),
                       ("num_nextn_predict_layers", 1)]:
        with pytest.raises(ValueError, match=f"{key} = .* is implemented"):
            mod.lm_fields(config | {key: other})
    for key, other in [("rope_layout", [1] * 52), ("moe_num_primary_experts", 64),
                       ("vocab_size", 151936)]:
        with pytest.raises(ValueError, match=f"{key} = .* contradicts"):
            mod.lm_fields(config | {key: other})


def test_the_cell_is_the_other_language_cells_tokens_in_one_row():
    """1 x 16384 tokens a step — the other language cells' 16 384, in one row
    at the configuration's ``max_position_embeddings`` — 8 distinct batches,
    a fetch every 5th step, a 4 s traced window, through the same generator.
    A sample is one sequence, and ``train_tok_per_s.lm`` the same in tokens.
    The cell is on one chip."""
    cell, other = load_cell(), load_cell("joyai_flash_pretrain_2x8k")
    numbers = lambda t: {k: v for k, v in t.items() if k not in ("driver", "why")}
    assert numbers(cell["traffic"]) == {
        "sequences_per_chip": 1, "seq": 16384, "distinct_batches": 8, "fetch_every": 5,
        "trace_seconds": 4}
    assert cell["traffic"]["seq"] == cell["config"]["max_position_embeddings"]
    same = lambda t: {k: t[k] for k in ("distinct_batches", "fetch_every", "trace_seconds")}
    assert same(cell["traffic"]) == same(other["traffic"])
    tokens = lambda t: t["sequences_per_chip"] * t["seq"]
    assert tokens(cell["traffic"]) == tokens(other["traffic"]) == 16384
    mod = driver_of(cell)
    assert mod.token_batches.__module__ == "benchmarks.drivers.lm_steps"
    assert {"train_img_per_s", "setup_s"} == {m["name"] for m in cell["end_to_end"]}
    assert cell["chips"] == 1
    batch = next(mod.token_batches(3_000_000_123, cell["config"], 1, 16384, 8))["tokens"]
    assert batch.shape == (1, 16385) and 0 <= batch.min() and batch.max() < 37984
    driver = object.__new__(mod.Driver)
    t = cell["traffic"]
    driver.config, driver.batch, driver.seq = cell["config"], t["sequences_per_chip"], t["seq"]
    record = driver.work(20) | {"window_s": 22.0, "chips": 1}
    img = harness.load_module("metrics", "train_img_per_s").read(record)
    tok = harness.load_module("metrics", "train_tok_per_s").read(record)
    assert record["images"] == 20 and tok == pytest.approx(img * 16384, rel=1e-12)
    assert record["work_flops"] == pytest.approx(20 * 34.70e12, rel=1e-3)


def test_the_cell_reports_the_grouped_query_familys_parts_and_one_new_counter():
    """The benchmark gained one configuration, one cell and one per-layer
    entry; the cell is in the lists of the readers whose parts it runs and in
    none whose reader would find nothing."""
    cell, bench = load_cell(), load_bench()
    names = {m["name"] for m in cell["per_layer"]}
    assert {"gqa_proj_ms.lm", "attn_core_ms.lm", "swa_core_ms.lm", "rope_ms.lm", "router_ms.lm",
            "moe_dispatch_ms.lm", "experts_ms.lm", "lm_head_ms.lm", "moe_imbalance.lm",
            "moe_dropped.lm", "swa_overcompute.lm", "train_tok_per_s.lm", "attn_core_roofline.lm",
            "swa_core_roofline.lm", "experts_roofline.lm", "expert_zero_share.lm", "mfu.train",
            "device_step_ms.train", "fwd_ms.train", "unscoped_ms.train", "jit_trace_s",
            "setup_spanned_share"} <= names
    assert not {n for n in names if n.startswith(("mla_", "mtp_", "kda_", "enc_", "dec_", "jumbo_"))}
    entry = next(m for m in bench["per_layer"] if m["name"] == "expert_zero_share.lm")
    assert entry == {"name": "expert_zero_share.lm", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "step program",
                     "moves": "train_img_per_s", "workloads": [CELL]}
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert workload["config"] == CONFIG and len(workload["why"]) <= 200
    assert "1/4" in workload["why"] and "4x" in workload["why"]  # how near its deployment's load


def test_the_new_reader_finds_nothing_where_the_program_has_no_such_counter():
    """On a record of another family, or of a program without the counter,
    the reader returns None and does not raise; on this family's it reads
    percent. The older readers find this family's parts under their names."""
    read = harness.load_module("metrics", "expert_zero_share").read
    assert read({}) is None
    assert read({"moe": {"imbalance": 3.0, "held_share": 0.07, "dropped": 0.0}}) is None
    assert read({"moe": {"act_zero_share": 0.0}}) == 0.0
    assert read({"moe": {"act_zero_share": 0.5004}}) == pytest.approx(50.04)
    record = {"_scope_table": {("fwd", "trunk_swa_core"): 30.0, ("fwd", "trunk_gqa_proj"): 7.0,
                               ("bwd", "trunk_gqa_proj"): 6.0, ("fwd", "trunk_attn_core"): 9.0,
                               ("recompute", "trunk_router"): 2.0, ("bwd", "trunk_rope"): 1.0},
              "attn_pairs": {"sliding_attention": {"visited": 73400320, "needed": 58722304}}}
    part = lambda name: harness.load_module("metrics", name).read(record)
    assert (part("swa_core_ms"), part("gqa_proj_ms"), part("attn_core_ms"), part("router_ms"),
            part("rope_ms")) == (30.0, 13.0, 9.0, 2.0, 1.0)
    assert part("swa_overcompute") == pytest.approx(1.25, abs=1e-3)


def test_the_scope_table_is_the_grouped_query_familys_over_four_blocks():
    import json

    from benchmarks import scope_reduce

    root = harness.ROOT / "benchmarks" / "scopes"
    new, gqa = (scope_reduce.vocabulary(root / f"{n}.json") for n in ("window_moe_lm", "gqa_lm"))
    assert set(new["parts"]) == set(gqa["parts"])
    path = "jit(_train_step)/jvp(MlaMoeLM)/block_{}/{}"
    for where, want in [((0, "attn/gqa_proj/q/dot_general"), "trunk_gqa_proj"),
                        ((0, "attn/attn_core/causal_attention_fwd/pallas_call"), "trunk_attn_core"),
                        ((1, "attn/swa_core/causal_attention_fwd/pallas_call"), "trunk_swa_core"),
                        ((3, "attn/rope/rope_half/pallas_call"), "trunk_rope"),
                        ((3, "attn/attn_out/out/dot_general"), "trunk_attn_out"),
                        ((2, "moe/router/dot_general"), "trunk_router"),
                        ((2, "moe/router/top_k"), "trunk_router"),
                        ((2, "moe/moe_dispatch/while/body/experts/gmm/pallas_call"),
                         "trunk_experts")]:
        assert scope_reduce.classify(path.format(*where), new) == ("fwd", want)
    table = lambda name: json.loads((root / f"{name}.json").read_text())
    assert table("window_moe_lm")["in_a_tower"] == table("gqa_lm")["in_a_tower"]
    assert [r for r in table("gqa_lm")["rules"] if r not in table("window_moe_lm")["rules"]] == [
        {"scope": f"block_{i}", "tower": "trunk"} for i in range(4, 8)]


def test_every_list_that_names_the_cell_is_in_the_benchmarks_own_order():
    """A PR appends: each list that names the cell names cells in the order
    ``workloads`` has them, with the cell after every cell that was there
    before it (containment and relative order: nothing here counts the
    benchmark or names a last place)."""
    bench = load_bench()
    order = [w["name"] for w in bench["workloads"]]
    before = order[: order.index(CELL)]
    assert {"l16_pretrain_b128", "joyai_flash_pretrain_2x8k", "ling3_flash_pretrain_8k",
            "laguna_xs2_pretrain_2x8k", "solar_open2_pretrain_2x8k"} <= set(before)
    listed = [m for key in ("end_to_end", "per_layer") for m in bench[key] if "workloads" in m]
    mine = [m for m in listed if CELL in m["workloads"]]
    assert {"train_img_per_s", "swa_core_ms.lm", "rope_ms.lm", "expert_zero_share.lm"} <= {
        m["name"] for m in mine}
    for metric in listed:
        assert metric["workloads"] == [name for name in order if name in metric["workloads"]]
    for metric in mine:
        at = metric["workloads"].index(CELL)
        assert set(metric["workloads"][:at]) <= set(before)
    configs = [c["name"] for c in bench["configs"]]
    assert set(configs[: configs.index(CONFIG)]) >= {"laguna_xs2_share", "solar_open2_share"}
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("expert_zero_share.lm") > names.index("kda_neg_eig_share.lm")
