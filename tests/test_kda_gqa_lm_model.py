"""The fourth family of ``models/lm.py`` (``Solar-Open2-250B``: Kimi delta
attention with a softplus decay gate, ``beta`` up to 2 and low-rank gates
three layers in four, rope-free gated grouped-query attention the fourth, a
share of each layer's heads) against the benchmark's plain reference
(``benchmarks/reference/kda_gqa_lm_model.py``) on seeded weights, float32, at
a cut that holds one layer of each kind; the head shares and the expert
shares of a layer added up to the uncut layer; the configuration's one list
of attention kinds."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.reference import kda_gqa_lm_model as ref_model
from benchmarks.reference import kda_gqa_lm_params as ref_shapes
from benchmarks.reference import params as ref_params
from jumbo_mae_tpu_tpu.models import lm
from jumbo_mae_tpu_tpu.models.lm import (GroupedQueryAttention, KdaAttention, MlaMoeConfig,
                                         MlaMoeLM)

DRIVER = harness.load_module("drivers", "kda_gqa_lm_steps")


def _config() -> dict:
    """The configuration as its cell runs it."""
    return harness.load_cell("solar_open2_pretrain_2x8k")["config"]


@functools.cache
def _setup(seed: int = 11):
    config = DRIVER.tiny({"config": _config(), "traffic": {}})["config"]
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config) | {"dtype": "float32"})
    params = jax.jit(lambda s: ref_shapes.make_params(s, config))(seed)
    biases = ref_shapes.make_biases(seed, config)
    first, rows = config["vocab_rows"]
    # 27 positions: three chunks of 8 and a ragged fourth
    tokens = np.random.default_rng(seed).integers(first, first + rows, (3, 28), dtype=np.int32)
    return config, cfg, params, biases, jnp.asarray(tokens)


def _flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in leaves}


def test_the_tiny_cut_holds_one_layer_of_each_kind_and_the_published_gates():
    config, cfg, params, *_ = _setup()
    assert cfg.kinds == ("full_attention", "kda") and cfg.first_k_dense == 0
    assert (cfg.kda_gate, cfg.kda_beta_scale, cfg.kda_gate_rank, cfg.kda_out_gate) == (
        "softplus", 2.0, 16, "element")
    assert cfg.rope("full_attention") is None and cfg.attn_gate
    assert cfg.attn_heads() == {"full_attention": (4, 16), "kda": (2, 8)}
    assert cfg.attn_pairs(27).keys() == {"full_attention"}
    attn = params["block_1"]["attn"]
    assert {"f_a", "f_b", "gate_a", "gate_b"} <= set(attn) and "f" not in attn
    # the seeded log-decay a step at a zero gate input: -exp(A_log) softplus(dt_bias)
    g = -np.exp(attn["A_log"])[:, None] * np.log1p(np.exp(attn["dt_bias"]))
    assert -1.6 - 1e-6 <= g.min() and g.max() <= -1e-3 + 1e-9 and g.min() < -0.05


def test_loss_and_every_gradient_leaf_match_the_reference():
    config, cfg, params, biases, tokens = _setup()
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
    assert got.keys() == ref.keys() and len(got) == 41
    for name, g in got.items():
        assert np.abs(ref[name]).max() > 0, name  # every leaf takes part
        np.testing.assert_allclose(g, ref[name], rtol=2e-3,
                                   atol=2e-4 * np.abs(ref[name]).max(), err_msg=name)
    # the counters: beta = 2 sigmoid(.) passes 1 about half the time at seeded weights
    assert 0.3 < float(out["kda_neg_eig_share"]) < 0.7 and 1.0 < float(out["kda_beta_max"]) < 2.0
    assert float(out["kda_neg_eig_share_l1"]) == float(out["kda_neg_eig_share"])
    assert 0 < float(out["kda_state_absmax"]) < 10 and "kda_beta_max_l0" not in out
    # beta = sigmoid(.) never does: the same weights at scale 1 read 0 exactly
    plain = MlaMoeLM(cfg.replace(kda_beta_scale=1.0)).apply(
        {"params": params, "batch_stats": biases}, tokens)
    assert float(plain["kda_neg_eig_share"]) == 0.0 and float(plain["kda_beta_max"]) < 1.0


def test_no_rope_scope_opens_and_no_rope_is_traced(monkeypatch):
    """``use_rope`` false: the step's text holds no ``rope`` scope, and
    ``rope_half`` is not called at all."""
    config, cfg, params, biases, tokens = _setup()
    monkeypatch.setattr(lm, "rope_half", lambda *a, **k: pytest.fail("rope_half was called"))
    text = jax.jit(lambda p: MlaMoeLM(cfg).apply({"params": p, "batch_stats": biases},
                                                tokens)["loss"]).lower(params).as_text(
        debug_info=True)
    assert "/attn_core/" in text and "/gqa_proj/" in text and "/kda_core/" in text
    assert "/rope" not in text


# ------------------------------------------------------- the shares add up
# guide §4: at a small size, the parts of the result that all the shares give,
# with what every chip computes alike counted once, add up to the uncut
# layer's. Heads in 4 slices (8 KDA heads; 8 query heads over 4 key/value
# heads), experts in 4 shares of 4.

def _whole():
    config, cfg, *_ = _setup()
    linear = config["linear_attn_config"] | {"num_heads": 8}
    whole = config | {"num_attention_heads": 8, "num_key_value_heads": 4,
                      "linear_attn_config": linear, "n_routed_experts": 16,
                      "experts_held": [0, 16]}
    params = jax.jit(lambda s: ref_shapes.make_params(s, whole))(5)
    bias = 0.01 * jax.random.normal(jax.random.key(5), (16,), jnp.float32)
    x = jax.random.normal(jax.random.key(6), (1, 27, cfg.dim), jnp.float32)
    return whole, cfg, params, bias, x


def _head_share(attn: dict, s: int, slices: int = 4) -> dict:
    """Slice ``s`` of a layer's heads: every leaf with a head axis cut to the
    slice's heads (a grouped-query layer's key/value heads to the slice's
    groups), the low-rank gates' first factors and the output norm whole."""
    def cut(leaf, axis):
        n = leaf.shape[axis] // slices
        return jax.lax.slice_in_dim(leaf, s * n, (s + 1) * n, axis=axis)

    head_axis = {"q": 1, "k": 1, "v": 1, "f_b": 1, "gate_b": 1, "b": 1, "gate": 1, "q_conv": 1,
                 "k_conv": 1, "v_conv": 1, "out": 0, "A_log": 0, "dt_bias": 0}
    alike = {"f_a", "gate_a", "o_norm"}
    assert set(attn) <= set(head_axis) | alike
    return {name: leaf if name in alike else jax.tree_util.tree_map(
        lambda w, name=name: cut(w, head_axis[name]), leaf) for name, leaf in attn.items()}


@pytest.mark.parametrize("layer,kind", [(0, "full_attention"), (1, "kda")])
def test_the_head_shares_and_the_expert_shares_add_up_to_the_uncut_block(layer, kind):
    whole, cfg, params, bias, x = _whole()
    ops = ref_model.Ops()
    p = params[f"block_{layer}"]
    eps = whole["rms_norm_eps"]
    inner = ref_model.rms_norm(x[0], p["ln1"], eps)
    # jitted, all of it: op by op every contraction compiles a program of its own
    mixer = jax.jit(lambda attn: (ref_model.attention(ops, inner, attn, whole) if kind != "kda"
                                  else ref_model.linear_attention(ops, inner, attn, whole)[0]))
    uncut = mixer(p["attn"])
    # the program's module on each slice: the heads this chip holds, nothing else
    share_cfg = cfg.replace(heads=2, kv_heads=1, kda_heads=2)
    module = (KdaAttention(share_cfg) if kind == "kda"
              else GroupedQueryAttention(share_cfg, 2, False))
    shares, apply = [], jax.jit(lambda part: module.apply({"params": part}, inner[None]))
    for s in range(4):
        part = _head_share(p["attn"], s)
        y = apply(part)
        shares.append((y[0] if kind == "kda" else y)[0])
        np.testing.assert_allclose(shares[-1], mixer(part), rtol=2e-4, atol=2e-6)
    assert all(float(jnp.abs(part).max()) > 1e-3 for part in shares)  # each slice says something
    np.testing.assert_allclose(sum(shares), uncut, rtol=2e-4, atol=2e-6)
    # ... and with the expert shares, the shared expert counted once, the block
    after = x[0] + sum(shares)
    inner2 = ref_model.rms_norm(after, p["ln2"], eps)
    cut = lambda k, first: {"kernel": p["moe"][k]["kernel"][first:first + 4]}
    total = ref_model.gated_mlp(ops, inner2, p["moe"]["shared"])
    routed = jax.jit(lambda held, first: ref_model.expert_layer(
        ops, inner2, held, bias, whole, first=first, shared=False)[0])
    for first in range(0, 16, 4):
        held = {**p["moe"], "gate": cut("gate", first), "up": cut("up", first),
                "down": cut("down", first)}
        total = total + routed(held, first)
    want, _ = jax.jit(lambda p: ref_model.block(
        ops, x[0], p, {"moe": {"router_bias": bias}}, whole, layer))(p)
    np.testing.assert_allclose(after + total, want, rtol=2e-4, atol=2e-6)


# ---------------------------------------------- the one list of layer kinds

def test_one_list_of_kinds_holds_every_family_and_refuses_what_does_not_fit():
    _, cfg, *_ = _setup()
    base = dict(vocab_size=64, dim=32, layers=4, heads=2, n_routed_experts=4, experts_per_token=2,
                mtp_layers=0)
    # the hybrid family's rule fills the same list
    assert MlaMoeConfig(**base, layer_group_size=2).kinds == ("kda", "mla") * 2
    assert MlaMoeConfig(**base).kinds == ("mla",) * 4
    mixed = MlaMoeConfig(**base, kv_heads=1, layer_types=(
        "kda", "mla", "full_attention", "sliding_attention"), rope_parameters={
            "full_attention": None, "sliding_attention": {"rope_theta": 1e4}})
    assert mixed.kinds == ("kda", "mla", "full_attention", "sliding_attention")
    assert (mixed.kda_layers, mixed.is_kda(0), mixed.query_heads(2)) == (1, True, 2)
    assert mixed.rope("full_attention") is None and mixed.rope("sliding_attention").rope_theta == 1e4
    assert mixed.attn_heads() == {k: (2, 2) for k in mixed.kinds}
    assert set(mixed.attn_pairs(16)) == {"mla", "full_attention", "sliding_attention"}
    with pytest.raises(ValueError, match="give one"):
        cfg.replace(layer_group_size=2)
    with pytest.raises(ValueError, match="no MTP module"):
        cfg.replace(mtp_layers=1)
    with pytest.raises(ValueError, match="rope_parameters"):
        cfg.replace(rope_parameters=())  # the grouped-query kind among the layers is not named
    with pytest.raises(ValueError, match="safe or softplus"):
        cfg.replace(kda_gate="hard")
    # an MTP module beside linear and latent layers named by the list is the hybrid's own
    assert MlaMoeConfig(**base | {"mtp_layers": 1}, layer_types=("kda", "mla") * 2).mtp_layers == 1


def test_parameters_here_is_the_trees_count_and_the_recipe_is_the_file():
    """The program's own tree at the real cut, shapes only."""
    from jumbo_mae_tpu_tpu.cli.train import build_model
    from jumbo_mae_tpu_tpu.config import load_config

    config = _config()
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    shapes = jax.eval_shape(lambda: MlaMoeLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 66), jnp.int32)))["params"]
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == config["parameters_here"] == 836_709_784
    want = ref_params.flat_shapes(ref_shapes.shapes(config))
    assert ref_params.flat_shapes(jax.tree_util.tree_map(lambda s: tuple(s.shape), shapes)) == want
    recipe = build_model(load_config(str(harness.ROOT / config["recipe"])))[1]
    assert recipe == cfg  # the recipe states the sizes the benchmark's file translates to
    assert cfg.attn_heads() == {"full_attention": (8, 64), "kda": (8, 64)}
    taken = [r for r in config["ladder"]["rungs"] if r.get("verdict") == "taken"]
    assert [r["heads_held"] for r in taken] == [cfg.heads] and taken[0]["program_bytes"] <= 15.2e9


def test_token_flops_three_layers_in_four_do_not_grow_with_the_sequence():
    from benchmarks import flops_kda_gqa_lm as flops
    from jumbo_mae_tpu_tpu.obs.mfu import lm_flops_per_token

    config = _config()
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    grow = flops.token_forward(config, 16384) - flops.token_forward(config, 8192)
    assert grow == 2 * (8192 / 2) * 8 * 256  # the one grouped-query core, at 8 heads held
    for seq in (8192, 1000):
        assert lm_flops_per_token(cfg, seq) == pytest.approx(flops.token_step(config, seq), rel=1e-12)
    work, moved = flops.kda_core_step(config, 2, 8192)
    assert work == 3 * (3 * 6 * 128 * 128 * 2 * 8192 * 8) and moved > 0
    core, _ = flops.causal_core_step(config, 2, 8192)
    assert core == 2 * 6 * 2 * 128 * 8 * (8192 * 8193 // 2)
