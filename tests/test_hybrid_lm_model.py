"""The hybrid family of ``models/lm.py`` (Kimi delta attention five layers
out of six, MLA with no query latent the sixth, a head-wise output gate,
group-limited routing) against the benchmark's plain reference
(``benchmarks/reference/hybrid_lm_model.py``: KDA as the token-by-token
recurrence) on seeded weights, float32, at a cut that keeps the structure: a
dense KDA block, four KDA expert blocks, the MLA expert block, 16 experts in
4 groups of which 2 stay, top-4, 4 held; in one case the MTP module."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.reference import hybrid_lm_model as ref_model
from benchmarks.reference import hybrid_lm_params as ref_shapes
from benchmarks.reference import params as ref_params
from jumbo_mae_tpu_tpu.models import lm
from jumbo_mae_tpu_tpu.models.lm import MOE_COUNTERS, MlaMoeConfig, MlaMoeLM, SparseExperts

CELL = "ling3_flash_pretrain_8k"
DRIVER = harness.load_module("drivers", "hybrid_lm_steps")


@functools.cache
def _setup(mtp: int = 0, seed: int = 11):
    config = DRIVER.tiny(harness.load_cell(CELL))["config"]
    config |= {"num_nextn_predict_layers": mtp, "mtp_loss_scaling_factor": 0.3 * mtp}
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config) | {"dtype": "float32"})
    params = ref_shapes.make_params(seed, config)
    biases = ref_shapes.make_biases(seed, config)
    first, rows = config["vocab_rows"]
    # 27 positions: three chunks of 8 and a ragged fourth
    tokens = np.random.default_rng(seed).integers(first, first + rows, (3, 28 + mtp),
                                                  dtype=np.int32)
    return config, cfg, params, biases, jnp.asarray(tokens)


def _flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in leaves}


def test_the_tiny_cut_holds_every_kind_of_block():
    config, cfg, params, *_ = _setup(1)
    kinds = [("kda" if cfg.is_kda(i) else "mla", "moe" if i >= cfg.first_k_dense else "mlp")
             for i in range(cfg.layers)]
    assert kinds == [("kda", "mlp")] + [("kda", "moe")] * 4 + [("mla", "moe")]
    assert "A_log" in params["block_0"]["attn"] and "mlp" in params["block_0"]
    assert "kv_a" in params["block_5"]["attn"] and "q_a" not in params["block_5"]["attn"]
    assert "kv_a" in params["mtp_block"]["attn"] and "moe" in params["mtp_block"]
    # the seeded decays a step at a zero gate input lie in 0.9 .. 0.999
    attn = params["block_1"]["attn"]
    alpha = np.exp(config["kda_lower_bound"] / (1 + np.exp(
        -np.exp(attn["A_log"])[:, None] * attn["dt_bias"])))
    assert 0.9 - 1e-6 <= alpha.min() < 0.93 and 0.99 < alpha.max() <= 0.999 + 1e-6


@pytest.mark.parametrize("mtp", [0, 1])
def test_logits_match_the_reference(mtp):
    config, cfg, params, biases, tokens = _setup(mtp)
    # jitted, both: op by op every layer compiles a program an operation
    got = jax.jit(lambda p: MlaMoeLM(cfg).apply({"params": p, "batch_stats": biases}, tokens,
                                                method="logits"))(params)
    assert len(got) == 1 + mtp and got[0].shape == (3, 27, config["vocab_size"])
    ops = ref_model.Ops()

    @jax.jit
    def reference(ids):
        hidden = ref_model.hidden_states(ops, params, biases, ids, config)[0]
        return [ref_model.head_logits(ops, params, h, config) for h in hidden]

    for row in range(tokens.shape[0]):
        for head, want in enumerate(reference(tokens[row] - config["vocab_rows"][0])):
            np.testing.assert_allclose(got[head][row], want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("mtp,leaves", [(0, 124), (1, 142)])
def test_loss_and_every_gradient_leaf_match_the_reference(mtp, leaves):
    config, cfg, params, biases, tokens = _setup(mtp)
    model = MlaMoeLM(cfg)

    def program(p):
        out = model.apply({"params": p, "batch_stats": biases}, tokens)
        return out["loss"], out

    (loss, out), grads = jax.jit(jax.value_and_grad(program, has_aux=True))(params)
    (want, _), want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref_model.batch_loss(p, biases, tokens, config), has_aux=True))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert float(out["moe_dropped"]) == 0.0
    got, ref = _flat(grads), _flat(want_grads)
    assert got.keys() == ref.keys() and len(got) == leaves
    for name, g in got.items():
        assert np.abs(ref[name]).max() > 0, name  # every leaf takes part
        np.testing.assert_allclose(g, ref[name], rtol=2e-3,
                                   atol=2e-4 * np.abs(ref[name]).max(), err_msg=name)


def test_the_kda_counters_are_the_references_final_states():
    config, cfg, params, biases, tokens = _setup()
    out = MlaMoeLM(cfg).apply({"params": params, "batch_stats": biases}, tokens)
    ops = ref_model.Ops()
    largest = {}
    for row in range(tokens.shape[0]):
        ids = tokens[row] - config["vocab_rows"][0]
        _, _, states = ref_model.hidden_states(ops, params, biases, ids, config)
        for name, state in states.items():
            largest[name] = max(largest.get(name, 0.0), float(jnp.abs(state).max()))
    assert sorted(largest) == [f"block_{i}" for i in range(5)]
    for name, want in largest.items():
        got = float(out[f"kda_state_absmax_l{name.split('_')[1]}"])
        assert got == pytest.approx(want, rel=1e-4)
    assert float(out["kda_state_absmax"]) == pytest.approx(max(largest.values()), rel=1e-4)
    assert 0.9 < float(out["kda_decay_mean"]) < 1.0
    assert "kda_state_absmax_l5" not in out  # block 5 is the MLA block


def _layer(config, cfg, seed=5, tokens=40):
    """One expert layer's full weights (all 16 experts), biases and input."""
    whole = config | {"num_experts": config["published"]["num_experts"],
                      "experts_held": [0, config["published"]["num_experts"]]}
    p = ref_params.make_params(seed, ref_shapes._block(whole, True, True)["moe"])
    bias = 0.01 * jax.random.normal(jax.random.key(seed), (16,), jnp.float32)
    x = jax.random.normal(jax.random.key(seed + 1), (1, tokens, cfg.dim), jnp.float32)
    return whole, p, bias, x


def _share(p, first, held):
    cut = lambda k: {"kernel": p[k]["kernel"][first:first + held]}
    return {**p, "gate": cut("gate"), "up": cut("up"), "down": cut("down")}


def _apply_layer(cfg, p, bias, x, first, held):
    layer = SparseExperts(cfg.replace(experts_held=(first, held)))
    variables = {"params": _share(p, first, held), "batch_stats": {"router_bias": bias}}
    return layer.apply(variables, x)


def test_the_shares_add_up_to_the_uncut_layer_under_group_limited_routing():
    """The routed parts that the 4 chips of a 4-way expert split compute (a
    chip holds one whole group here), with the shared expert counted once,
    are the uncut reference layer."""
    config, cfg, *_ = _setup()
    whole, p, bias, x = _layer(config, cfg)
    ops = ref_model.Ops()
    want, counts = ref_model.expert_layer(ops, x[0], p, bias, whole, first=0)
    # the limit binds: some token's plain top-4 lies in more than 2 groups
    _, plain = jax.lax.top_k(jax.nn.sigmoid(x[0] @ p["router"]["kernel"]) + bias, 4)
    assert int((jax.vmap(lambda c: jnp.unique(c // 4, size=4, fill_value=-1))(plain) >= 0)
               .sum(axis=1).max()) > 2
    shared = ref_model.gated_mlp(ops, x[0], p["shared"])
    outs = [_apply_layer(cfg, p, bias, x, first, 4)[0][0] for first in (0, 4, 8, 12)]
    np.testing.assert_allclose(sum(o - shared for o in outs) + shared, want,
                               rtol=1e-4, atol=1e-6)
    for first, out in zip((0, 4, 8, 12), outs):
        ref, _ = ref_model.expert_layer(ops, x[0], _share(p, first, 4), bias, whole, first=first)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-6)
    stats = dict(zip(MOE_COUNTERS, np.asarray(_apply_layer(cfg, p, bias, x, 4, 4)[1])))
    assert stats["dropped"] == 0
    assert stats["held_share"] == pytest.approx(float(counts[4:8].sum()) / (40 * 4))


def _choice_by_loop(biased, n_group, topk_group, k):
    """Group-limited choice one token at a time, in numpy."""
    chosen = []
    for t in np.asarray(biased, np.float64):
        groups = t.reshape(n_group, -1)
        score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = sorted(range(n_group), key=lambda g: (-score[g], g))[:topk_group]
        eligible = [e for e in range(t.size) if e // groups.shape[1] in kept]
        chosen.append(sorted(eligible, key=lambda e: (-t[e], e))[:k])
    return np.asarray(chosen)


@pytest.mark.parametrize("experts,n_group,topk_group,k", [(16, 4, 2, 4), (512, 8, 4, 8),
                                                          (64, 8, 1, 8)])
def test_group_limited_choice_against_a_loop(experts, n_group, topk_group, k):
    biased = jax.random.uniform(jax.random.key(experts), (50, experts))
    _, got = jax.lax.top_k(lm._group_limited(biased, n_group, topk_group), k)
    want = _choice_by_loop(biased, n_group, topk_group, k)
    np.testing.assert_array_equal(np.sort(got, axis=1), np.sort(want, axis=1))
    assert len({int(e) // (experts // n_group) for e in got[0]}) <= topk_group
    # and the reference's own form of the rule picks the same experts
    c = {"n_group": n_group, "topk_group": topk_group, "num_experts_per_tok": k,
         "routed_scaling_factor": 2.5}
    logits = jnp.log(biased / (1 - biased))  # sigmoid's inverse: s = biased, b = 0
    p = {"router": {"kernel": jnp.eye(experts)}}
    ref, _, _ = ref_model.route(ref_model.Ops(), logits, p, jnp.zeros(experts), c)
    np.testing.assert_array_equal(np.sort(ref, axis=1), np.sort(want, axis=1))


def test_one_group_is_todays_choice_exactly():
    """``n_group = 1`` traces the router the all-MLA family had: the same
    program text for the expert layer, so the same chosen experts."""
    config, cfg, *_ = _setup()
    whole, p, bias, x = _layer(config, cfg)
    one = cfg.replace(n_group=1, topk_group=1, experts_held=(4, 4))
    variables = {"params": _share(p, 4, 4), "batch_stats": {"router_bias": bias}}
    text = lambda c: jax.make_jaxpr(lambda v, x: SparseExperts(c).apply(v, x))(variables, x)
    assert "top_k" in str(text(one)) and str(text(one)).count("top_k") == 1
    assert str(text(cfg.replace(experts_held=(4, 4)))).count("top_k") == 3
    biased = jax.nn.sigmoid(x[0] @ p["router"]["kernel"]) + bias
    _, today = jax.lax.top_k(biased, 4)
    _, limited = jax.lax.top_k(lm._group_limited(biased, 1, 1), 4)
    np.testing.assert_array_equal(today, limited)


def test_a_non_zero_swiglu_limit_is_refused():
    with pytest.raises(ValueError, match="SwiGLU limit"):
        MlaMoeConfig(expert_swiglu_limit=4.0)
    with pytest.raises(ValueError, match="SwiGLU limit"):
        MlaMoeConfig(shared_expert_swiglu_limit=5.0)
    config = harness.load_cell(CELL)["config"]
    clamped = config | {"num_hidden_layers": 36,
                        "published": config["published"] | {"num_hidden_layers": 42}}
    with pytest.raises(ValueError, match="SwiGLU limit"):  # layer 35 is a clamped one
        MlaMoeConfig(**DRIVER.lm_fields(clamped))


def test_groups_that_do_not_fit_are_refused():
    with pytest.raises(ValueError, match="n_group"):
        MlaMoeConfig(n_routed_experts=256, n_group=7, topk_group=2)
    with pytest.raises(ValueError, match="fewer experts"):
        MlaMoeConfig(n_routed_experts=16, n_group=8, topk_group=2, experts_per_token=8)


def test_parameters_here_is_the_trees_count_and_the_recipe_is_the_file():
    """The program's own tree at the real cut, shapes only."""
    from jumbo_mae_tpu_tpu.cli.train import build_model
    from jumbo_mae_tpu_tpu.config import load_config

    config = harness.load_cell(CELL)["config"]
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    shapes = jax.eval_shape(lambda: MlaMoeLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 66), jnp.int32)))["params"]
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == config["parameters_here"] == 822_033_344
    want = ref_params.flat_shapes(ref_shapes.shapes(config))
    assert ref_params.flat_shapes(jax.tree_util.tree_map(lambda s: tuple(s.shape), shapes)) == want
    recipe = build_model(load_config(str(harness.ROOT / config["recipe"])))[1]
    assert recipe == cfg  # the recipe states the sizes the benchmark's file translates to


def test_token_flops_count_matches_the_issue_s_reckoning():
    """About 53 TF a step of 16 384 tokens (ISSUE 31), and independent of
    the sequence but for the one MLA core."""
    from benchmarks import flops_hybrid_lm

    config = harness.load_cell(CELL)["config"]
    bench, program = DRIVER.flops_pair(config)
    assert bench == pytest.approx(program, rel=1e-12)
    assert bench * 16384 == pytest.approx(53.5e12, rel=5e-3)
    core = 3 * 2 * 32 * (128 + 64 + 128)  # one MLA core: 3 x 2 h (qk + v) a key position
    grown = flops_hybrid_lm.token_step(config, 16384) - flops_hybrid_lm.token_step(config, 8192)
    assert grown == pytest.approx(core * 4096, rel=1e-12)
    tiny = DRIVER.tiny(harness.load_cell(CELL))["config"] | {
        "num_nextn_predict_layers": 1, "mtp_loss_scaling_factor": 0.3}
    assert DRIVER.flops_pair(tiny)[0] == pytest.approx(DRIVER.flops_pair(tiny)[1], rel=1e-12)
    flops, moved = flops_hybrid_lm.kda_core_step(config, 2, 8192)
    assert flops == 6 * 3 * 6 * 128 * 128 * 32 * 16384 and moved == 6 * 32 * 16384 * 4364


def test_the_configuration_file_holds_every_number_of_the_catalog_row():
    """Every key of the published config, under its own name, changed only
    where ``reduced`` says so (checked against the catalog where it is
    installed)."""
    from pathlib import Path

    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "Ling-3.0-flash")
    config = harness.load_cell(CELL)["config"]
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
    assert differs == set(config["reduced"]) - {"chips", "dataset", "weights"}
    assert set(config["reduced"]) == set(config["reduced_why"])
    assert {k: config["published"][k] for k in differs} == {k: row["config"][k] for k in differs}
