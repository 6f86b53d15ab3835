"""``correct`` has to be able to come out false in the linear-attention /
grouped-query family's cell: four mutations of the program's mixers (``beta``
not doubled, the safe gate in the softplus gate's place, a rotation applied,
a full-rank ``f``) fail the cell's check at the test size, the lower-precision
control fails it, and the driver has an account of every key of the
configuration file. ``test_bench_rehearsal`` and ``test_bench_yardstick`` run
the cell traced and untraced and hold its FLOP count to the program's, as they
do for every cell of ``BENCHMARK.json``."""

import contextlib
import io
import re
import time

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import driver_of, load_bench, tiny_cell
from benchmarks import harness
from benchmarks.drivers import train_loop
from jumbo_mae_tpu_tpu.models import lm

pytestmark = pytest.mark.usefixtures("cpu_has_no_peak")
CELL = "solar_open2_pretrain_2x8k"
LANGUAGE_CELLS = ["joyai_flash_pretrain_2x8k", "ling3_flash_pretrain_8k",
                  "laguna_xs2_pretrain_2x8k", CELL]
SEED = 2_147_483_999


def load_cell(name: str = CELL) -> dict:
    return harness.load_cell(name)


def _beta_not_doubled():
    """``beta = sigmoid(.)``: every eigenvalue stays in (0, 1)."""
    real = lm.kda_chunked
    return lm, "kda_chunked", lambda q, k, v, g, beta, **kw: real(q, k, v, g, beta / 2.0, **kw)


def _safe_gate_in_the_softplus_gates_place():
    """A bounded gate from the same leaves, swapped where the gate is
    computed (the one 4-D ``softplus`` of the step): ``g = −exp(A_log) · 5/16 ·
    sigmoid(f + dt_bias)``, which lies in (−5, 0) for ``exp(A_log) <= 16``."""
    real = jax.nn.softplus
    return jax.nn, "softplus", lambda x: 5.0 / 16.0 * jax.nn.sigmoid(x) if x.ndim == 4 else real(x)


def _a_rotation_applied():
    """The grouped-query layer's ``q`` and ``k`` turned by the plain rotary
    embedding (theta 1e4, every dimension) on their way to the core."""
    real, rope = lm.causal_attention, lm.Rope(rope_theta=1e4)

    def attention(q, q_b, k, k_b, v, **kw):
        return real(lm.rope_half(q, rope), q_b, lm.rope_half(k, rope), k_b, v, **kw)

    return lm, "causal_attention", attention


def _a_full_rank_f():
    """The decay gate's second factor applied to the block input's first
    ``rank`` columns: what a full-rank ``f`` of the same second factor would
    read, with no first factor."""
    real = lm.Proj.__call__

    def call(self, x):
        y = real(self, x)  # the leaf stays in the tree; its product goes unused
        return x[..., : self.shape[1]].astype(y.dtype) if self.name == "f_a" else y

    return lm.Proj, "__call__", call


# name -> () -> (owner, attribute, replacement)
MUTATIONS = {
    "beta_not_doubled": _beta_not_doubled,
    "safe_gate": _safe_gate_in_the_softplus_gates_place,
    "rotation_applied": _a_rotation_applied,
    "full_rank_f": _a_full_rank_f,
}


def _peaked(monkeypatch):
    """The seeded query, key and decay-gate projections scaled up, in the
    program and the reference alike (both take their weights from
    ``kda_gqa_lm_params.make_params``): the scores and the gates then spread
    as the real cut's do at its seeded weights (4096 inputs of 0.02 against
    the tiny cut's 32, which leave every softmax flat, every decay at its
    bias and a mutation of them without effect)."""
    from benchmarks.reference import kda_gqa_lm_params

    real = kda_gqa_lm_params.make_params

    def make_params(seed, c):
        params = real(seed, c)
        for name in [n for n in params if n.startswith("block_")]:
            attn = params[name]["attn"]
            for w in ("q", "k", "f_a", "f_b", "b"):
                if w in attn and not (w in "qk" and "A_log" in attn):
                    attn[w]["kernel"] = attn[w]["kernel"] * 8.0
        return params

    monkeypatch.setattr(kda_gqa_lm_params, "make_params", make_params)


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
    return {key: 3 * reading for key, reading in sound.items()}, kept[0]


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_a_mutated_mixer_is_not_correct(mutation, tmp_path, monkeypatch, sound):
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


def test_the_driver_has_an_account_of_every_key_of_the_configuration_file():
    """Every key is translated, required to hold the one value that is
    implemented, held to the keys it restates, named inert, or about the
    file — and none of those accounts names a key the file lacks; every
    number of the catalog's ``config`` is in the file under its own key, the
    published one or, where ``reduced`` names it, this chip's share."""
    from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig

    cell = load_cell()
    mod, config = driver_of(cell), cell["config"]
    assert set(config) == mod.KEYS
    kinds = [set(mod._FIELDS) | set(mod._PUBLISHED), mod._DERIVED,
             set(mod._REQUIRED) - {"num_nextn_predict_layers"},
             set(mod._CONSISTENT) - set(mod._PUBLISHED), mod._INERT, mod._ABOUT]
    assert sum(map(len, kinds)) == len(mod.KEYS)  # one account a key
    assert mod._INERT | {"intermediate_size"} <= set(config["assumed"]) | {"partial_rotary_factor",
                                                                          "rope_theta"}
    assert {"use_rope", "softplus_gate", "kda_allow_neg_eigval", "kda_use_full_proj",
            "use_gqa_gate", "router", "optimizer"} <= set(config["assumed"])
    entry = next(c for c in load_bench()["configs"] if c["name"] == "solar_open2_share")
    assert set(entry["reduced"]) == set(config["reduced"]) == set(config["reduced_why"])
    published = config["published"]
    for key in ("num_hidden_layers", "n_routed_experts", "vocab_size", "num_attention_heads",
                "num_key_value_heads", "linear_attn_config"):
        assert key in entry["reduced"] and config[key] != published[key]
    assert (published["num_attention_heads"], published["num_key_value_heads"],
            published["linear_attn_config"]["num_heads"]) == (64, 8, 64)
    # no width is cut
    assert (config["hidden_size"], config["head_dim"], config["moe_intermediate_size"],
            config["intermediate_size"], config["num_experts_per_tok"]) == (4096, 128, 1280, 10240, 8)
    assert config["linear_attn_config"] == published["linear_attn_config"] | {"num_heads": 8}
    assert "40 chips share each layer" in config["deployment"] and "12 pipeline stages" in config[
        "deployment"]
    cfg = MlaMoeConfig(**mod.lm_fields(config))
    assert (cfg.n_routed_experts, cfg.held, cfg.vocab_size, cfg.rows) == (
        320, (0, 8), 196608, (0, 24576))
    assert (cfg.layers, cfg.first_k_dense, cfg.layer_group_size, cfg.mtp_layers) == (4, 0, 0, 0)
    assert cfg.kinds == ("full_attention", "kda", "kda", "kda")
    assert (cfg.dim, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.kda_heads, cfg.kda_head_dim,
            cfg.kda_conv) == (4096, 8, 1, 128, 8, 128, 4)
    assert (cfg.kda_gate, cfg.kda_beta_scale, cfg.kda_gate_rank, cfg.kda_out_gate,
            cfg.attn_gate) == ("softplus", 2.0, 128, "element", True)
    assert (cfg.expert_hidden, cfg.shared_hidden, cfg.experts_per_token,
            cfg.routed_scaling_factor, cfg.rms_eps) == (1280, 1280, 8, 1, 1e-5)
    assert cfg.rope("full_attention") is None
    # a key it has no account of, a value that is not implemented and a
    # restated key that contradicts its source are each refused
    with pytest.raises(ValueError, match="no account of.*sliding_window"):
        mod.lm_fields(config | {"sliding_window": 4096})
    for key, other in [("model_type", "laguna"), ("use_rope", True), ("use_gqa_gate", False),
                       ("kda_use_full_proj", True), ("kda_allow_neg_eigval", False),
                       ("norm_topk_prob", False), ("num_nextn_predict_layers", 1)]:
        with pytest.raises(ValueError, match=f"{key} = .* is implemented"):
            mod.lm_fields(config | {key: other})
    for key, other in [("gqa_interval", 5), ("n_routed_experts", 16), ("vocab_size", 196608),
                       ("heads_held", config["heads_held"] | {"kda": [0, 16]})]:
        with pytest.raises(ValueError, match=f"{key} = .* contradicts"):
            mod.lm_fields(config | {key: other})
    with pytest.raises(ValueError, match="num_kv_heads"):
        mod.lm_fields(config | {"linear_attn_config": config["linear_attn_config"]
                                | {"num_kv_heads": 2}})


@pytest.mark.parametrize("name", LANGUAGE_CELLS[1:])
def test_a_language_cell_is_the_first_ones_traffic_to_the_number(name):
    """2 x 8192 tokens, 8 distinct batches, a fetch every 5th step, a 4 s
    traced window, through the same generator: the four language models are
    read against each other. A sample is one sequence, and
    ``train_tok_per_s.lm`` the same in tokens. Every cell is on one chip."""
    cell, other = load_cell(name), load_cell(LANGUAGE_CELLS[0])
    same = lambda t: {k: v for k, v in t.items() if k not in ("driver", "why")}
    assert same(cell["traffic"]) == same(other["traffic"]) == {
        "sequences_per_chip": 2, "seq": 8192, "distinct_batches": 8, "fetch_every": 5,
        "trace_seconds": 4}
    mod = driver_of(cell)
    assert mod.token_batches.__module__ == "benchmarks.drivers.lm_steps"
    assert {"train_img_per_s", "setup_s"} == {m["name"] for m in cell["end_to_end"]}
    assert all(w["chips"] == 1 for w in load_bench()["workloads"])
    driver = object.__new__(mod.Driver)
    t = cell["traffic"]
    driver.config, driver.batch, driver.seq = cell["config"], t["sequences_per_chip"], t["seq"]
    record = driver.work(20) | {"window_s": 22.0, "chips": 1}
    img = harness.load_module("metrics", "train_img_per_s").read(record)
    tok = harness.load_module("metrics", "train_tok_per_s").read(record)
    assert record["images"] == 40 and tok == pytest.approx(img * 8192, rel=1e-12)


def test_the_cell_reports_both_older_families_parts_and_one_new_counter():
    """The benchmark gained one configuration, one cell and one per-layer
    entry, all last in their lists; the cell is in the lists of the readers whose parts it runs and in
    none whose reader would find nothing."""
    cell, bench = load_cell(), load_bench()
    names = {m["name"] for m in cell["per_layer"]}
    assert {"kda_proj_ms.lm", "kda_conv_gate_ms.lm", "kda_core_ms.lm", "kda_core_roofline.lm",
            "kda_state_absmax.lm", "kda_neg_eig_share.lm", "gqa_proj_ms.lm", "attn_core_ms.lm",
            "attn_core_roofline.lm", "router_ms.lm", "moe_dispatch_ms.lm", "experts_ms.lm",
            "experts_roofline.lm", "lm_head_ms.lm", "moe_imbalance.lm", "moe_dropped.lm",
            "train_tok_per_s.lm", "mfu.train", "device_step_ms.train", "fwd_ms.train"} <= names
    assert not {"rope_ms.lm", "mla_latent_ms.lm", "mtp_ms.lm", "swa_core_ms.lm",
                "swa_core_roofline.lm", "swa_overcompute.lm"} & names
    assert [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]] == [
        "kda_neg_eig_share.lm"]
    assert bench["per_layer"][-1]["name"] == "kda_neg_eig_share.lm"  # new entries go last
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == \
        "solar_open2_share"
    assert len(bench["workloads"][-1]["why"]) <= 200
    work = driver_of(cell).flops_family
    config = cell["config"]
    assert 16384 * work.token_step(config, 8192) == pytest.approx(25.05e12, rel=5e-3)


def test_the_new_reader_finds_nothing_where_the_program_has_no_such_counter():
    """On a record of another family, or of the parent's program (whose
    ``kda`` counters end at ``decay_mean``), the reader returns None and does
    not raise; on this family's it reads percent."""
    read = harness.load_module("metrics", "kda_neg_eig_share").read
    assert read({}) is None
    assert read({"moe": {"imbalance": 3.0}, "kda": {"state_absmax": 0.5, "decay_mean": 0.97}}) is None
    assert read({"kda": {"neg_eig_share": 0.0}}) == 0.0
    assert read({"kda": {"neg_eig_share": 0.4996}}) == pytest.approx(49.96)
    record = {"_scope_table": {("fwd", "trunk_kda_core"): 30.0, ("fwd", "trunk_gqa_proj"): 7.0,
                               ("bwd", "trunk_gqa_proj"): 6.0, ("fwd", "trunk_attn_core"): 9.0,
                               ("fwd", "trunk_kda_proj"): 2.0, ("bwd", "trunk_kda_out"): 1.0}}
    part = lambda name: harness.load_module("metrics", name).read(record)
    assert (part("kda_core_ms"), part("gqa_proj_ms"), part("attn_core_ms"), part("kda_proj_ms")) \
        == (30.0, 13.0, 9.0, 3.0)


def test_the_scope_table_names_both_older_families_parts():
    import json

    from benchmarks import scope_reduce

    root = harness.ROOT / "benchmarks" / "scopes"
    new, hybrid, gqa = (scope_reduce.vocabulary(root / f"{n}.json")
                        for n in ("kda_gqa_lm", "hybrid_lm", "gqa_lm"))
    assert set(new["parts"]) == set(hybrid["parts"]) | {"trunk_gqa_proj", "mtp_gqa_proj"}
    assert set(new["parts"]) - set(gqa["parts"]) == {
        f"{tower}_kda_{part}" for tower in ("trunk", "mtp")
        for part in ("proj", "conv", "gate", "core", "out")} - set()
    assert set(gqa["parts"]) - set(new["parts"]) == {"trunk_swa_core", "mtp_swa_core"}
    path = "jit(_train_step)/jvp(MlaMoeLM)/block_{}/attn/{}"
    for where, want in [((0, "gqa_proj/q/dot_general"), "trunk_gqa_proj"),
                        ((0, "attn_core/causal_attention_fwd/pallas_call"), "trunk_attn_core"),
                        ((0, "attn_out/out/dot_general"), "trunk_attn_out"),
                        ((2, "kda_proj/f_a/dot_general"), "trunk_kda_proj"),
                        ((2, "kda_core/kda_chunk_fwd/pallas_call"), "trunk_kda_core"),
                        ((3, "kda_out/kda_gate/mul"), "trunk_kda_gate")]:
        assert scope_reduce.classify(path.format(*where), new) == ("fwd", want)
    assert json.loads((root / "kda_gqa_lm.json").read_text())["rules"] == \
        json.loads((root / "mla_moe_lm.json").read_text())["rules"]


def test_every_list_of_cells_is_in_the_benchmarks_own_order():
    """A PR appends: the cell is the last of ``workloads`` and of every
    metric's list that names it, and each such list names cells in the order
    ``workloads`` has them, so none was put first or in the middle."""
    bench = load_bench()
    order = [w["name"] for w in bench["workloads"]]
    assert order[-1] == CELL
    listed = [m for key in ("end_to_end", "per_layer") for m in bench[key] if "workloads" in m]
    assert sum(CELL in m["workloads"] for m in listed) == 30
    for metric in listed:
        assert metric["workloads"] == [name for name in order if name in metric["workloads"]]
