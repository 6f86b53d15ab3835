"""``correct`` has to be able to come out false in the grouped-query family's
cell: five mutations of the program's attention fail the cell's check at the
test size, the lower-precision control fails it, and the driver has an
account of every key of the configuration file."""

import contextlib
import io
import json
import re
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import driver_of, load_bench, tiny_cell
from benchmarks import harness
from benchmarks.drivers import train_loop
from jumbo_mae_tpu_tpu.models import lm

pytestmark = pytest.mark.usefixtures("cpu_has_no_peak")
CELL = "laguna_xs2_pretrain_2x8k"
SEED = 2_147_483_999


def _window(change):
    """``models/lm.causal_attention`` with a layer's window put through ``change``."""
    real = lm.causal_attention
    return lm, "causal_attention", lambda *xs, impl, window=None: real(
        *xs, impl=impl, window=None if window is None else change(window))


def _kv_head_by_remainder():
    """Query head ``h`` reads key/value head ``h % G`` instead of ``h // group``."""
    real = lm.causal_attention

    def attention(q, q_b, k, k_b, v, *, impl, window=None):
        pick = jnp.arange(q.shape[1]) % k.shape[1]
        return real(q, q_b, k[:, pick], k_b, v[:, pick], impl=impl, window=window)

    return lm, "causal_attention", attention


def _rope_on_adjacent_pairs():
    """The same angles and factor, dimension ``2j`` paired with ``2j + 1``."""
    def rope(x, rope):
        seq, d = x.shape[-2:]
        r = int(d * rope.partial_rotary_factor)
        angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(
            rope.inv_freq(r), jnp.float32)
        cos, sin = rope.attention_factor * jnp.cos(angle), rope.attention_factor * jnp.sin(angle)
        a, b = (x[..., i:r:2].astype(jnp.float32) for i in (0, 1))
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(*x.shape[:-1], r)
        return jnp.concatenate([out.astype(x.dtype), x[..., r:]], -1)

    return lm, "rope_half", rope


def _yarn_factor_dropped():
    """Every pair frequency the plain ``θ^(−2j/r)``: no blend with ``f / factor``."""
    return lm.Rope, "inv_freq", lambda self, r: self.rope_theta ** (
        -2.0 * np.arange(r // 2, dtype=np.float64) / r)


# name -> () -> (owner, attribute, replacement)
MUTATIONS = {
    "window_off_by_one": lambda: _window(lambda w: w + 1),
    "window_ignored": lambda: _window(lambda w: None),
    "rope_interleaved": _rope_on_adjacent_pairs,
    "yarn_factor_dropped": _yarn_factor_dropped,
    "kv_head_by_remainder": _kv_head_by_remainder,
}


def _peaked_attention(monkeypatch):
    """The seeded query and key projections times 8, in the program and the
    reference alike (both take their weights from ``gqa_lm_params.make_params``):
    the scores then spread as the real cut's do at its seeded weights (a
    deviation of 0.8: 2048 inputs of 0.02 a query entry, 128 products a score,
    against the tiny cut's 32 and 16, which leave every softmax flat and the
    rope and the masks all but without effect)."""
    from benchmarks.reference import gqa_lm_params

    real = gqa_lm_params.make_params

    def make_params(seed, c):
        params = real(seed, c)
        for name in [n for n in params if n.startswith("block_")]:
            for w in ("q", "k"):
                params[name]["attn"][w]["kernel"] = params[name]["attn"][w]["kernel"] * 8.0
        return params

    monkeypatch.setattr(gqa_lm_params, "make_params", make_params)


def _run_in_float32(scratch) -> tuple[dict, str]:
    """The tiny cell computed in float32 (at 32 wide bfloat16's rounding alone
    reads 0.04 on a gradient leaf, more than some mutations move it):
    ``(result, what the run printed)``."""
    cell = tiny_cell(harness.load_cell(CELL))
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
    mod = driver_of(harness.load_cell(CELL))
    kept = []
    with pytest.MonkeyPatch.context() as patch:
        _peaked_attention(patch)
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
def test_a_mutated_attention_is_not_correct(mutation, tmp_path, monkeypatch, sound):
    """At limits the sound program passes (``sound`` has held it to them
    three times over) each mutation fails by at least one number, with every
    loss finite."""
    mod = driver_of(harness.load_cell(CELL))
    limits, reference = sound
    _peaked_attention(monkeypatch)
    for key, limit in limits.items():
        monkeypatch.setitem(mod.LIMITS, key, limit)
    monkeypatch.setattr(mod, "reference_run", lambda *a, **k: reference)
    monkeypatch.setattr(*MUTATIONS[mutation]())
    result, printed = _run_in_float32(tmp_path)
    assert result["correct"] is False, printed
    assert "FAILED" in printed and result["failed"] == 0  # the losses stay finite


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
    implemented, held to the keys it restates, or about the file — and none
    of those accounts names a key the file lacks."""
    from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig

    cell = harness.load_cell(CELL)
    mod, config = driver_of(cell), cell["config"]
    assert set(config) == mod.KEYS
    kinds = [set(mod._FIELDS) | set(mod._PUBLISHED), mod._DERIVED,
             set(mod._REQUIRED) - {"num_nextn_predict_layers"},
             set(mod._CONSISTENT) - set(mod._PUBLISHED), mod._ABOUT]
    assert sum(map(len, kinds)) == len(mod.KEYS)  # one account a key
    assert {"gating", "router", "qk_norm", "rope_pairing", "init", "optim"} <= set(
        config["assumed"])
    cfg = MlaMoeConfig(**mod.lm_fields(config))
    assert (cfg.n_routed_experts, cfg.held, cfg.vocab_size, cfg.rows) == (
        256, (0, 16), 100352, (0, 12544))
    assert (cfg.layers, cfg.first_k_dense, cfg.layer_group_size, cfg.mtp_layers) == (8, 1, 0, 0)
    assert cfg.layer_types == ("full_attention", *["sliding_attention"] * 3) * 2
    assert cfg.heads_per_layer == (48, 64, 64, 64) * 2
    assert (cfg.dim, cfg.kv_heads, cfg.head_dim, cfg.sliding_window, cfg.attn_gate) == (
        2048, 8, 128, 512, True)
    assert (cfg.dense_hidden, cfg.expert_hidden, cfg.shared_hidden) == (8192, 512, 512)
    assert (cfg.experts_per_token, cfg.routed_scaling_factor, cfg.rms_eps) == (8, 2.5, 1e-6)
    full, sliding = cfg.rope("full_attention"), cfg.rope("sliding_attention")
    assert (full.rope_theta, full.rope_type, full.partial_rotary_factor, full.factor) == (
        500000, "yarn", 0.5, 64)
    assert (full.original_max_position_embeddings, full.beta_fast, full.beta_slow,
            full.attention_factor) == (4096, 64, 1, 1.4158883083359672)
    assert (sliding.rope_theta, sliding.rope_type, sliding.partial_rotary_factor) == (
        10000, "default", 1)
    # a key it has no account of, a value that is not implemented and a
    # restated key that contradicts its source are each refused
    with pytest.raises(ValueError, match="no account of.*kv_lora_rank"):
        mod.lm_fields(config | {"kv_lora_rank": 512})
    for key, other in [("model_type", "bailing_hybrid"), ("attention_bias", True),
                       ("tie_word_embeddings", True), ("num_nextn_predict_layers", 1),
                       ("moe_apply_router_weight_on_input", True)]:
        with pytest.raises(ValueError, match=f"{key} = .* is implemented"):
            mod.lm_fields(config | {key: other})
    for key, other in [("num_attention_heads", 64), ("partial_rotary_factor", 1.0),
                       ("num_experts", 32), ("vocab_size", 100352)]:
        with pytest.raises(ValueError, match=f"{key} = .* contradicts"):
            mod.lm_fields(config | {key: other})
    with pytest.raises(ValueError, match="gating"):
        mod.lm_fields(config | {"gating": False})
    with pytest.raises(ValueError, match="rope_parameters"):
        mod.lm_fields(config | {"rope_parameters": config["rope_parameters"]
                                | {"chunked_attention": {}}})


def test_the_cell_is_the_other_language_cells_traffic_to_the_number():
    """2 x 8192 tokens, 8 distinct batches, a fetch every 5th step, through
    the same generator: the three language models are read against each
    other. A sample is one sequence, and ``train_tok_per_s.lm`` the same in
    tokens. The benchmark gained one configuration, one cell and four
    per-layer entries, none on four chips."""
    cell, other = harness.load_cell(CELL), harness.load_cell("joyai_flash_pretrain_2x8k")
    same = lambda t: {k: v for k, v in t.items() if k not in ("driver", "why")}
    assert same(cell["traffic"]) == same(other["traffic"])
    mod = driver_of(cell)
    assert mod.token_batches.__module__ == "benchmarks.drivers.lm_steps"
    assert {"train_img_per_s", "setup_s"} == {m["name"] for m in cell["end_to_end"]}
    names = {m["name"] for m in cell["per_layer"]}
    new = {"gqa_proj_ms.lm", "swa_core_ms.lm", "swa_core_roofline.lm", "swa_overcompute.lm"}
    assert new | {"attn_core_ms.lm", "attn_core_roofline.lm", "experts_roofline.lm",
                  "train_tok_per_s.lm", "mfu.train", "device_step_ms.train"} <= names
    assert not {"mtp_ms.lm", "mla_latent_ms.lm", "kda_core_ms.lm"} & names
    bench = load_bench()
    assert [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]] == sorted(new)
    assert len(bench["workloads"]) == 4 and all(w["chips"] == 1 for w in bench["workloads"])
    driver = object.__new__(mod.Driver)
    t = cell["traffic"]
    driver.config, driver.batch, driver.seq = cell["config"], t["sequences_per_chip"], t["seq"]
    record = driver.work(20) | {"window_s": 22.0, "chips": 1}
    img = harness.load_module("metrics", "train_img_per_s").read(record)
    tok = harness.load_module("metrics", "train_tok_per_s").read(record)
    assert record["images"] == 40 and tok == pytest.approx(img * 8192, rel=1e-12)
    assert record["work_flops"] == pytest.approx(20 * 53.9e12, rel=2e-3)


def test_the_new_readers_find_nothing_where_the_program_has_no_such_part():
    """On a record of the all-MLA family (or of the parent's program) the
    four readers return None and do not raise."""
    for name in ("gqa_proj_ms", "swa_core_ms", "swa_core_roofline", "swa_overcompute"):
        read = harness.load_module("metrics", name).read
        assert read({}) is None
        assert read({"moe": {"imbalance": 3.0}, "kernel_work": {"attn_core": {}},
                     "_scope_table": {("fwd", "trunk_attn_core"): 1.0},
                     "device_kind": "TPU v5 lite"}) is None
    record = {"_scope_table": {("fwd", "trunk_swa_core"): 30.0, ("bwd", "trunk_swa_core"): 54.0,
                               ("fwd", "trunk_gqa_proj"): 7.0, ("recompute", "trunk_gqa_proj"): 6.0,
                               ("fwd", "trunk_attn_core"): 9.0},
              "attn_pairs": {"sliding_attention": {"visited": 8, "needed": 4},
                             "full_attention": {"visited": 9, "needed": 8}}}
    read = lambda name: harness.load_module("metrics", name).read(record)
    assert (read("swa_core_ms"), read("gqa_proj_ms"), read("attn_core_ms")) == (84.0, 13.0, 9.0)
    assert read("swa_overcompute") == 2.0


def test_the_scope_table_names_the_old_parts_and_the_new():
    from benchmarks import scope_reduce

    root = harness.ROOT / "benchmarks" / "scopes"
    new, old = (scope_reduce.vocabulary(root / f"{n}.json") for n in ("gqa_lm", "mla_moe_lm"))
    assert set(old["parts"]) < set(new["parts"])
    assert set(new["parts"]) - set(old["parts"]) == {
        f"{tower}_{part}" for tower in ("trunk", "mtp") for part in ("gqa_proj", "swa_core")}
    path = "jit(_train_step)/jvp(MlaMoeLM)/block_{}/attn/{}"
    kernel = "causal_attention_fwd/pallas_call"
    assert scope_reduce.classify(path.format(1, f"swa_core/{kernel}"), new) == (
        "fwd", "trunk_swa_core")
    assert scope_reduce.classify(path.format(4, f"attn_core/{kernel}"), new) == (
        "fwd", "trunk_attn_core")
    assert scope_reduce.classify(path.format(2, "gqa_proj/q/dot_general"), new) == (
        "fwd", "trunk_gqa_proj")
    assert scope_reduce.classify(path.format(2, "attn_out/out/dot_general"), new) == (
        "fwd", "trunk_attn_out")
    assert json.loads((root / "gqa_lm.json").read_text())["rules"] == \
        json.loads((root / "mla_moe_lm.json").read_text())["rules"]
