"""``correct`` has to be able to come out false in the hybrid family's cell:
the program with its inter-chunk state zeroed fails the cell's check at the
test size, the lower-precision control fails it, and the driver has an
account of every key of the configuration file."""

import json

import jax.numpy as jnp
import pytest

from bench_tiny import driver_of, run_tiny, tiny_cell
from benchmarks import harness
from benchmarks.drivers import train_loop

pytestmark = pytest.mark.usefixtures("cpu_has_no_peak")
CELL = "ling3_flash_pretrain_8k"


@pytest.fixture
def tiny_limits(monkeypatch):
    mod = driver_of(harness.load_cell(CELL))
    for key, limit in mod.TINY_LIMITS.items():
        monkeypatch.setitem(mod.LIMITS, key, limit)


@pytest.mark.parametrize("carry", ["kept", "zeroed"])
def test_a_scan_that_loses_its_carry_is_not_correct(carry, tmp_path, monkeypatch, capsys,
                                                    tiny_limits):
    """The mutation: every chunk of the linear-attention scan starts from a
    zero state. Each chunk is then right alone — a first chunk's output does
    not move — and only what crosses a chunk boundary is lost."""
    from jumbo_mae_tpu_tpu.ops import kda

    if carry == "zeroed":
        real = kda._chunk
        monkeypatch.setattr(kda, "_chunk", lambda state, xs, **kw: real(
            jnp.zeros_like(state), xs, **kw))
    result = run_tiny(CELL, False, tmp_path, seconds=0.4, seed=2_147_483_999)[1]
    out = capsys.readouterr().out
    assert result["correct"] is (carry == "kept"), out
    if carry == "zeroed":
        assert "FAILED" in out and result["failed"] == 0  # the losses stay finite


def test_the_lower_precision_control_fails_the_limits_the_sound_run_passes():
    """The reference in the program's place at test size: computed in
    bfloat16 (the configuration's own precision) it passes the test-size
    limits, computed in fp8 (the control) it fails one of them."""
    cell = tiny_cell(harness.load_cell(CELL))
    mod = driver_of(cell)
    config, t, seed = cell["config"], cell["traffic"], 77
    gen = mod.token_batches(seed, config, t["sequences_per_chip"], t["seq"], 2)
    batches = [next(gen)["tokens"] for _ in range(train_loop.CHECK_STEPS)]
    ref = mod.reference_run(config, seed, batches)
    sound = mod.reference_run(config, seed, batches, rounding="bfloat16")
    control = mod.reference_run(config, seed, batches, rounding=mod.CONTROL)
    limits = mod.LIMITS | mod.TINY_LIMITS
    ok = lambda checks: all(v <= limit for _, v, limit in checks)
    assert ok(train_loop.compare(sound, ref, limits))
    assert not ok(train_loop.compare(control, ref, limits))


def test_the_driver_has_an_account_of_every_key_of_the_configuration_file():
    """Every key is translated, required to hold the one value that is
    implemented, held to the keys it restates, named inert, or about the
    file — and none of those accounts names a key the file lacks."""
    from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig

    cell = harness.load_cell(CELL)
    mod, config = driver_of(cell), cell["config"]
    assert set(config) == mod.KEYS
    kinds = [set(mod._FIELDS) | set(mod._PUBLISHED), mod._DERIVED,
             set(mod._REQUIRED) - {"q_lora_rank"}, set(mod._CONSISTENT) - set(mod._PUBLISHED),
             mod._INERT, mod._ABOUT]
    assert sum(map(len, kinds)) == len(mod.KEYS)  # one account a key
    assert mod._INERT <= set(config["assumed"])
    cfg = MlaMoeConfig(**mod.lm_fields(config))
    assert (cfg.n_routed_experts, cfg.held, cfg.vocab_size, cfg.rows) == (
        512, (0, 8), 157184, (0, 19648))
    assert (cfg.layers, cfg.first_k_dense, cfg.layer_group_size, cfg.mtp_layers) == (7, 1, 6, 0)
    assert [cfg.is_kda(i) for i in range(7)] == [True] * 5 + [False, True]
    assert (cfg.dim, cfg.heads, cfg.kda_head_dim, cfg.kda_conv, cfg.kda_lower_bound) == (
        2560, 32, 128, 4, -5)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.attn_gate) == (None, 512, True)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.dense_hidden, cfg.expert_hidden, cfg.shared_hidden, cfg.rope_theta) == (
        6144, 768, 768, 6e6)
    assert (cfg.n_group, cfg.topk_group, cfg.experts_per_token, cfg.routed_scaling_factor) == (
        8, 4, 8, 2.5)
    # a key it has no account of, a value that is not implemented and a
    # restated key that contradicts its source are each refused
    with pytest.raises(ValueError, match="no account of.*sliding_window"):
        mod.lm_fields(config | {"sliding_window": 4096})
    for key, other in [("kda_safe_gate", False), ("use_kda_lora", True), ("value_norm", True),
                       ("num_kv_heads_for_linear_attn", 8), ("q_lora_rank", 1536),
                       ("rope_scaling", {"type": "yarn"}), ("hidden_act", "gelu")]:
        with pytest.raises(ValueError, match=f"{key} = .* is implemented"):
            mod.lm_fields(config | {key: other})
    for key, other in [("num_key_value_heads", 8), ("rotary_dim", 32), ("num_experts", 16)]:
        with pytest.raises(ValueError, match=f"{key} = .* contradicts"):
            mod.lm_fields(config | {key: other})
    with pytest.raises(ValueError, match="head-wise"):
        mod.lm_fields(config | {"gated_attention_proj_granularity_type": "element_wise"})


def test_the_cell_is_the_other_language_cells_traffic_to_the_number():
    """2 x 8192 tokens, 8 distinct batches, a fetch every 5th step, through
    the same generator: the two language models are read against each other.
    A sample is one sequence, and ``train_tok_per_s.lm`` the same in tokens."""
    cell, other = harness.load_cell(CELL), harness.load_cell("joyai_flash_pretrain_2x8k")
    same = lambda t: {k: v for k, v in t.items() if k not in ("driver", "why")}
    assert same(cell["traffic"]) == same(other["traffic"])
    mod = driver_of(cell)
    assert mod.token_batches.__module__ == "benchmarks.drivers.lm_steps"
    assert {"train_img_per_s", "setup_s"} == {m["name"] for m in cell["end_to_end"]}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"kda_proj_ms.lm", "kda_conv_gate_ms.lm", "kda_core_ms.lm", "kda_core_roofline.lm",
            "kda_state_absmax.lm", "attn_core_roofline.lm", "experts_roofline.lm",
            "train_tok_per_s.lm"} <= names and "mtp_ms.lm" not in names
    driver = object.__new__(mod.Driver)
    t = cell["traffic"]
    driver.config, driver.batch, driver.seq = cell["config"], t["sequences_per_chip"], t["seq"]
    record = driver.work(20) | {"window_s": 22.0, "chips": 1}
    img = harness.load_module("metrics", "train_img_per_s").read(record)
    tok = harness.load_module("metrics", "train_tok_per_s").read(record)
    assert record["images"] == 40 and tok == pytest.approx(img * 8192, rel=1e-12)


def test_the_new_readers_find_nothing_where_the_program_has_no_such_part():
    """On a record of the all-MLA family (or of the parent's program) the
    five readers return None and do not raise."""
    for name in ("kda_proj_ms", "kda_conv_gate_ms", "kda_core_ms", "kda_core_roofline",
                 "kda_state_absmax"):
        read = harness.load_module("metrics", name).read
        assert read({}) is None
        assert read({"moe": {"imbalance": 3.0}, "kernel_work": {"attn_core": {}},
                     "_scope_table": {("fwd", "trunk_attn_core"): 1.0},
                     "device_kind": "TPU v5 lite"}) is None
    record = {"_scope_table": {("fwd", "trunk_kda_core"): 30.0, ("bwd", "trunk_kda_core"): 54.0,
                               ("fwd", "trunk_kda_gate"): 2.0, ("bwd", "trunk_kda_conv"): 3.0,
                               ("fwd", "trunk_kda_proj"): 7.0, ("bwd", "trunk_kda_out"): 4.0},
              "kernel_work": {"kda_core": {"flops": 1e9, "bytes": 13.76e9}},
              "kda": {"state_absmax": 0.5}, "device_kind": "cpu"}
    read = lambda name: harness.load_module("metrics", name).read(record)
    assert (read("kda_core_ms"), read("kda_conv_gate_ms"), read("kda_proj_ms")) == (84.0, 5.0, 11.0)
    assert read("kda_state_absmax") == 0.5
    # the test peak is 1e12 of either: bytes bound, 13.76 ms of 84
    assert read("kda_core_roofline") == pytest.approx(100 * 13.76 / 84.0)


def test_the_scope_table_names_the_old_parts_and_the_new():
    from benchmarks import scope_reduce

    root = harness.ROOT / "benchmarks" / "scopes"
    new, old = (scope_reduce.vocabulary(root / f"{n}.json") for n in ("hybrid_lm", "mla_moe_lm"))
    assert set(old["parts"]) < set(new["parts"])
    assert set(new["parts"]) - set(old["parts"]) == {
        f"{tower}_kda_{part}" for tower in ("trunk", "mtp")
        for part in ("proj", "conv", "gate", "core", "out")}
    path = "jit(_train_step)/jvp(MlaMoeLM)/block_2/attn/{}/dot_general"
    assert scope_reduce.classify(path.format("kda_out/kda_gate"), new) == ("fwd", "trunk_kda_gate")
    assert scope_reduce.classify(path.format("kda_out/out"), new) == ("fwd", "trunk_kda_out")
    assert scope_reduce.classify(path.format("kda_core/while/body"), new) == ("fwd", "trunk_kda_core")
    assert json.loads((root / "hybrid_lm.json").read_text())["rules"] == \
        json.loads((root / "mla_moe_lm.json").read_text())["rules"]
