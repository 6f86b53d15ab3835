"""The fifth family of ``models/lm.py`` (``SmallThinker-21BA3B-Instruct``: one
rope-free full-attention layer to three sliding-window layers with rope, a
group of 7 query heads a key/value head, a router that reads the block's
input before attention and weighs its chosen experts by a softmax over their
logits, ReGLU experts, no shared expert, no dense layer) against the
benchmark's plain reference (``benchmarks/reference/window_moe_lm_model.py``)
on seeded weights, float32, at a cut that holds one layer of each kind: loss,
every gradient leaf and three AdamW steps; one mutation of each of the four
things that set the family apart, which the comparison catches; the expert
shares of a layer added up to the uncut layer; ``routed_experts`` with a ReLU
gate against autodiff of the dense form."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.reference import optim as ref_optim
from benchmarks.reference import params as ref_params
from benchmarks.reference import window_moe_lm_model as ref_model
from benchmarks.reference import window_moe_lm_params as ref_shapes
from jumbo_mae_tpu_tpu.models import lm
from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig, MlaMoeLM, Rope, SparseExperts

DRIVER = harness.load_module("drivers", "window_moe_lm_steps")
CELL = "smallthinker_pretrain_1x16k"


def _config() -> dict:
    """The configuration as its cell runs it."""
    return harness.load_cell(CELL)["config"]


def _peaked(params: dict) -> dict:
    """The seeded query, key, router and expert gate/up matrices scaled up,
    for program and reference alike: 32 inputs of 0.02 leave every softmax
    flat and every expert's output a thousandth of the stream, so that a
    mutation of them would move nothing a float32 comparison sees; the real
    cut's 2560 inputs spread them as this does."""
    params = jax.tree_util.tree_map(lambda x: x, params)  # a copy of the tree
    for name in [n for n in params if n.startswith("block_")]:
        blk = params[name]
        for leaf in (blk["attn"]["q"], blk["attn"]["k"], blk["moe"]["router"], blk["moe"]["gate"],
                     blk["moe"]["up"], blk["moe"]["down"]):
            leaf["kernel"] = leaf["kernel"] * 8.0
    return params


@functools.cache
def _setup(seed: int = 11):
    # the cell's embedding rows of 1.0 would lead a 32-wide stream a hundred
    # to one, and a mutated sublayer move nothing: rows of 0.02 here (the
    # benchmark's own tests run the tiny cut at the cell's 1.0)
    config = DRIVER.tiny({"config": _config(), "traffic": {}})["config"] | {
        "embedding_init_std": 0.02}
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config) | {"dtype": "float32"})
    params = _peaked(jax.jit(lambda s: ref_shapes.make_params(s, config))(seed))
    first, rows = config["vocab_rows"]
    # 24 positions: the window of 11 ends inside the sequence
    tokens = np.random.default_rng(seed).integers(first, first + rows, (3, 25), dtype=np.int32)
    return config, cfg, params, jnp.asarray(tokens)


def _flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in leaves}


def _loss_and_grads(cfg, params, tokens, variables=None):
    def program(p):
        out = MlaMoeLM(cfg).apply({"params": p, **(variables or {})}, tokens)
        return out["loss"], out

    return jax.jit(jax.value_and_grad(program, has_aux=True))(params)


@functools.cache
def _reference():
    config, _, params, tokens = _setup()
    return jax.jit(jax.value_and_grad(
        lambda p: ref_model.batch_loss(p, tokens, config)))(params)


def _worst_leaf_gap(grads, want) -> float:
    """The largest gap of a gradient leaf, over that leaf's largest entry."""
    got, ref = _flat(grads), _flat(want)
    assert got.keys() == ref.keys()
    return max(float(np.abs(got[k] - ref[k]).max() / np.abs(ref[k]).max()) for k in ref)


def test_the_tiny_cut_holds_one_layer_of_each_kind_and_this_familys_expert_layer():
    config, cfg, params, tokens = _setup()
    assert cfg.kinds == ("full_attention", "sliding_attention") and cfg.first_k_dense == 0
    assert (cfg.heads, cfg.kv_heads, cfg.sliding_window) == (7, 1, 11)  # the group of 7
    assert cfg.rope("full_attention") is None and cfg.rope("sliding_attention").rope_theta == 100
    assert (cfg.router_input, cfg.router_scoring, cfg.expert_act) == (
        "block_input", "softmax_topk", "relu")
    assert (cfg.n_routed_experts, cfg.held, cfg.experts_per_token, cfg.shared_hidden) == (
        8, (2, 2), 3, 0)
    assert cfg.moe_counters == lm.MOE_COUNTERS + ("act_zero_share",)
    assert MlaMoeConfig().moe_counters == lm.MOE_COUNTERS  # the other families' vector is theirs
    # the program's own tree: no batch_stats collection (no router bias), no
    # shared expert module (not a zero-width leaf), no dense MLP
    variables = jax.eval_shape(lambda: MlaMoeLM(cfg).init(jax.random.key(0), tokens))
    assert set(variables) == {"params"}
    shapes = jax.tree_util.tree_map(lambda s: tuple(s.shape), variables["params"])
    assert ref_params.flat_shapes(shapes) == ref_params.flat_shapes(ref_shapes.shapes(config))
    assert set(shapes["block_0"]) == {"ln1", "ln2", "attn", "moe"}
    assert set(shapes["block_0"]["moe"]) == {"router", "gate", "up", "down"}
    assert set(shapes["block_1"]["attn"]) == {"q", "k", "v", "out"}
    assert ref_shapes.bias_shapes(config) == {} and ref_shapes.make_biases(0, config) is None
    for name, other in [("router_input", "attention"), ("router_scoring", "softmax"),
                        ("expert_act", "gelu")]:
        with pytest.raises(ValueError, match=name):
            cfg.replace(**{name: other})
    with pytest.raises(ValueError, match="no group limit"):
        cfg.replace(n_group=2, topk_group=1)


def test_the_embedding_rows_are_seeded_at_their_own_scale():
    """``embedding_init_std`` 1.0 beside every other matrix's 0.02, in the
    benchmark's seeded weights and in the program's own initialiser: the
    routers read the un-normalised stream (the file's ``assumed.init``)."""
    config = DRIVER.tiny({"config": _config(), "traffic": {}})["config"]
    assert config["embedding_init_std"] == 1.0
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    assert cfg.embed_init_std == 1.0 and MlaMoeConfig().embed_init_std is None
    seeded = ref_shapes.make_params(5, config)
    plain = ref_params.make_params(5, ref_shapes.shapes(config))
    np.testing.assert_allclose(seeded["embedding"], 50.0 * plain["embedding"], rtol=1e-6)
    seeded["embedding"] = plain["embedding"]
    jax.tree_util.tree_map(np.testing.assert_array_equal, seeded, plain)  # nothing else moves
    own = jax.jit(MlaMoeLM(cfg).init)(jax.random.key(0), jnp.zeros((1, 13), jnp.int32))["params"]
    assert 0.9 < float(jnp.std(own["embedding"])) < 1.1
    assert 0.015 < float(jnp.std(own["head"]["kernel"])) < 0.025


def test_loss_every_gradient_leaf_and_the_counters_match_the_reference():
    config, cfg, params, tokens = _setup()
    (loss, out), grads = _loss_and_grads(cfg, params, tokens)
    want, want_grads = _reference()
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert float(out["moe_dropped"]) == 0.0 and float(out["moe_rounds"]) >= 1.0
    got, ref = _flat(grads), _flat(want_grads)
    assert got.keys() == ref.keys() and len(got) == 23
    for name, g in got.items():
        assert np.abs(ref[name]).max() > 0, name  # every leaf takes part, the routers too
        np.testing.assert_allclose(g, ref[name], rtol=2e-3,
                                   atol=2e-4 * np.abs(ref[name]).max(), err_msg=name)
    # a ReLU gate closes on a negative pre-activation: about half the held
    # rows' hidden entries are exactly zero, layer by layer and in the mean
    shares = [float(out[f"moe_act_zero_share_l{i}"]) for i in range(2)]
    assert all(0.3 < s < 0.7 for s in shares)
    assert float(out["moe_act_zero_share"]) == pytest.approx(np.mean(shares), rel=1e-6)
    # the other families' programs carry no such counter
    assert "moe_act_zero_share" not in MlaMoeLM(cfg.replace(expert_act="silu")).apply(
        {"params": params}, tokens)


def test_three_adamw_steps_follow_the_reference():
    """Loss by loss over three steps of the reference's AdamW, each side on
    its own gradients, and the parameters' change at the end leaf by leaf."""
    config, cfg, params, tokens = _setup()
    optim = config["optim"] | {"warmup_steps": 2, "init_lr": 1e-3, "peak_lr": 3e-3}
    program = jax.jit(jax.value_and_grad(
        lambda p, t: MlaMoeLM(cfg).apply({"params": p}, t)["loss"]))
    reference = jax.jit(jax.value_and_grad(
        lambda p, t: ref_model.batch_loss(p, t, config)))
    copy = lambda tree: jax.tree_util.tree_map(jnp.array, tree)
    sides = {}
    for name, fn in (("program", program), ("reference", reference)):
        p, state, losses = copy(params), None, []
        for step in range(3):
            loss, g = fn(p, jnp.roll(tokens, step, axis=0))
            losses.append(float(loss))
            p, state = ref_optim.adamw_step(p, g, state or ref_optim.adamw_init(p), optim)
        sides[name] = (losses, _flat(jax.tree_util.tree_map(jnp.subtract, p, params)))
    np.testing.assert_allclose(sides["program"][0], sides["reference"][0], rtol=2e-5)
    assert sides["reference"][0][2] < sides["reference"][0][0]
    for name, want in sides["reference"][1].items():
        norm = np.linalg.norm(want)
        assert norm > 0 and np.linalg.norm(sides["program"][1][name] - want) < 0.02 * norm, name


def test_the_control_can_round_in_one_block_alone():
    """``rounding="fp8@<l>"``: the one-layer fault the chip's limits are shown
    to catch (PERF.md §2). It moves the loss, by another amount than rounding
    in the other block or in every contraction."""
    config, _, params, tokens = _setup()
    loss = lambda r: float(jax.jit(lambda p: ref_model.batch_loss(p, tokens, config, r))(params))
    assert len({loss(r) for r in ("float32", "fp8@0", "fp8@1", "fp8")}) == 4


def _sigmoid_for_softmax(cfg):
    """Sigmoid scores normalised over the chosen, biases at zero: the other
    families' rule on the same logits."""
    blocks = {f"block_{i}": {"moe": {"router_bias": jnp.zeros((cfg.n_routed_experts,))}}
              for i in range(cfg.layers)}
    return cfg.replace(router_scoring="sigmoid_bias"), {"batch_stats": blocks}


# name -> cfg -> (the mutated cfg, further variables)
MUTATIONS = {
    "router_fed_the_post_attention_norm": lambda cfg: (cfg.replace(router_input="ffn_norm"), None),
    "sigmoid_for_softmax": _sigmoid_for_softmax,
    "silu_for_relu": lambda cfg: (cfg.replace(expert_act="silu"), None),
    "rope_on_the_full_layer": lambda cfg: (cfg.replace(rope_parameters=(
        ("full_attention", Rope(rope_theta=100.0)),
        ("sliding_attention", cfg.rope("sliding_attention")))), None),
}


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_a_mutation_of_what_sets_the_family_apart_is_caught(mutation):
    """The sound program's worst gradient leaf is within 0.2% of the
    reference's; each mutation moves a leaf by over 5% and the loss by more
    than the comparison allows."""
    _, cfg, params, tokens = _setup()
    want, want_grads = _reference()
    (loss, _), grads = _loss_and_grads(cfg, params, tokens)
    assert _worst_leaf_gap(grads, want_grads) < 2e-3 and abs(float(loss / want) - 1) < 1e-5
    mutated, variables = MUTATIONS[mutation](cfg)
    (loss, _), grads = _loss_and_grads(mutated, params, tokens, variables)
    assert _worst_leaf_gap(grads, want_grads) > 5e-2
    assert abs(float(loss / want) - 1) > 1e-5


# ------------------------------------------------------- the shares add up
# guide §4: at a small size, the parts of the result that all the shares give
# add up to the uncut layer's: 8 experts in 4 shares of 2, no shared expert to
# count once, the router reading another input than the experts.

def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    config, cfg, params, _ = _setup()
    whole = config | {"moe_num_primary_experts": 8, "experts_held": [0, 8]}
    p = _peaked({"block_0": jax.jit(lambda s: ref_shapes.make_params(s, whole))(5)[
        "block_0"]})["block_0"]["moe"]
    u = jax.random.normal(jax.random.key(6), (1, 40, cfg.dim), jnp.float32)
    router_x = jax.random.normal(jax.random.key(7), (1, 40, cfg.dim), jnp.float32)
    ops = ref_model.Ops()
    want = jax.jit(lambda p: ref_model.expert_layer(ops, u[0], router_x[0], p, whole, first=0))(p)
    cut = lambda k, first: {"kernel": p[k]["kernel"][first:first + 2]}
    total = 0.0
    for first in range(0, 8, 2):
        share = {"router": p["router"], **{k: cut(k, first) for k in ("gate", "up", "down")}}
        layer = SparseExperts(cfg.replace(experts_held=(first, 2)))
        out, stats = layer.apply({"params": share}, u, router_x)
        ref = ref_model.expert_layer(ops, u[0], router_x[0], share, whole, first=first)
        np.testing.assert_allclose(out[0], ref, rtol=1e-4, atol=1e-6)
        assert float(jnp.abs(out).max()) > 1e-3  # each share says something
        stats = dict(zip(cfg.moe_counters, np.asarray(stats)))
        assert stats["dropped"] == 0 and 0 < stats["act_zero_share"] < 1
        total = total + out[0]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-6)
    # the router read its own input: fed the experts' it chooses otherwise
    same = SparseExperts(cfg.replace(experts_held=(0, 2))).apply(
        {"params": {"router": p["router"], **{k: cut(k, 0) for k in ("gate", "up", "down")}}}, u)
    assert not np.allclose(same[0], ref_model.expert_layer(
        ops, u[0], router_x[0], {"router": p["router"],
                                 **{k: cut(k, 0) for k in ("gate", "up", "down")}}, whole, first=0),
        rtol=1e-2, atol=1e-4)


# ------------------------------------- routed_experts with a ReLU gate

@pytest.mark.parametrize("impl,interpret", [("ragged_dot", False), ("pallas", True)])
def test_routed_experts_with_a_relu_gate_against_autodiff_of_the_dense_form(impl, interpret):
    """512 tokens, 2 slots, 4 held experts of 16: more held pairs than one
    chunk, so the rounds' loop runs more than once, the last round ragged.
    Output, the count of zeros, and the gradient of every differentiable
    argument against the dense form every (token, slot, expert) of which is
    computed and weighed."""
    tokens, k, d, hidden, held, experts = 512, 2, 32, 16, 4, 16
    keys = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(keys[0], (tokens, d))
    w_gu = 0.3 * jax.random.normal(keys[1], (held, d, 2 * hidden))
    w_down = 0.3 * jax.random.normal(keys[2], (held, hidden, d))
    # skewed towards the held experts, so that they take more than one chunk
    # (and a token's two choices distinct, as a top-k's are)
    first, step = jax.random.randint(keys[3], (2, tokens), 0, held + 1)
    chosen = jnp.stack([first, (first + 1 + step % held) % (held + 1)], axis=1)
    weights = jax.nn.softmax(jax.random.normal(keys[4], (tokens, k)), axis=1)
    here = chosen < held
    key = jnp.where(here, chosen, held).reshape(-1)
    row_to_pair = jnp.argsort(key, stable=True).astype(jnp.int32)
    pair_to_row = jnp.argsort(row_to_pair).astype(jnp.int32).reshape(tokens, k)
    group_sizes = (key[:, None] == jnp.arange(held)).sum(axis=0).astype(jnp.int32)
    chunk = lm.chunk_rows(tokens * k, held, experts)
    rounds = (group_sizes.sum() + chunk - 1) // chunk
    assert int(rounds) > 1 and int(group_sizes.sum()) % chunk
    weight = jax.random.normal(keys[5], (tokens, d))

    def program(x, w_gu, w_down, gate):
        y, zeros = lm.routed_experts(x, w_gu, w_down, gate, row_to_pair, pair_to_row,
                                     group_sizes, rounds, chunk, impl, interpret, "relu")
        return (y * weight).sum(), (y, zeros)

    def dense(x, w_gu, w_down, gate):
        gu = jnp.einsum("td,edh->teh", x, w_gu, precision="highest")
        act = jax.nn.relu(gu[..., :hidden]) * gu[..., hidden:]
        out = jnp.einsum("teh,ehd->ted", act, w_down, precision="highest")
        mine = (chosen[..., None] == jnp.arange(held)) * gate[..., None]  # (tokens, k, held)
        y = jnp.einsum("tke,ted->td", mine, out, precision="highest")
        picked = (chosen[..., None] == jnp.arange(held)).any(axis=1)  # (tokens, held)
        return (y * weight).sum(), (y, ((act == 0) & picked[..., None]).sum())

    gate = jnp.where(here, weights, 0.0)
    args = (x, w_gu, w_down, gate)
    (_, (y, zeros)), grads = jax.jit(jax.value_and_grad(program, argnums=(0, 1, 2, 3),
                                                        has_aux=True))(*args)
    (_, (want, want_zeros)), want_grads = jax.jit(jax.value_and_grad(
        dense, argnums=(0, 1, 2, 3), has_aux=True))(*args)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert float(zeros) == float(want_zeros) > 0
    assert 0.3 < float(zeros) / (int(group_sizes.sum()) * hidden) < 0.7
    for g, ref, name in zip(grads, want_grads, ("x", "w_gu", "w_down", "gate")):
        ref = jnp.where(here, ref, 0.0) if name == "gate" else ref  # a pair held elsewhere has none
        np.testing.assert_allclose(g, ref, rtol=1e-4, atol=1e-5 * float(jnp.abs(ref).max()) + 1e-6,
                                   err_msg=name)
    # the SiLU form returns no count and is the other families' to the bit
    y_silu, none = lm.routed_experts(x, w_gu, w_down, gate, row_to_pair, pair_to_row, group_sizes,
                                     rounds, chunk, impl, interpret)
    assert none is None and not np.allclose(y_silu, y)


# ---------------------------------------------------------- the real cut

def test_parameters_here_is_the_trees_count_and_the_recipe_is_the_file():
    """The program's own tree at the real cut, shapes only."""
    from jumbo_mae_tpu_tpu.cli.train import build_model
    from jumbo_mae_tpu_tpu.config import load_config

    config = _config()
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    variables = jax.eval_shape(lambda: MlaMoeLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 66), jnp.int32)))
    assert set(variables) == {"params"}
    shapes = variables["params"]
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == config["parameters_here"] == 656_529_920
    layer = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes["block_2"]))
    assert layer == 20_971_520 + 5_120 + 163_840 + 16 * 5_898_240 == 115_512_320
    want = ref_params.flat_shapes(ref_shapes.shapes(config))
    assert ref_params.flat_shapes(jax.tree_util.tree_map(lambda s: tuple(s.shape), shapes)) == want
    recipe = build_model(load_config(str(harness.ROOT / config["recipe"])))[1]
    assert recipe == cfg  # the recipe states the sizes the benchmark's file translates to
    assert cfg.kinds == ("full_attention",) + ("sliding_attention",) * 3
    assert cfg.attn_heads() == {"full_attention": (28, 28), "sliding_attention": (28, 28)}
    taken = [r for r in config["ladder"]["rungs"] if r["verdict"] == "taken"]
    assert [r["experts_held"] for r in taken] == [cfg.held[1]]
    assert taken[0]["program_bytes"] <= 15.2e9 and taken[0]["parameters"] == count


def test_token_flops_at_16k_a_window_layer_is_44_percent_of_a_full_one():
    from benchmarks import flops_window_moe_lm as flops
    from jumbo_mae_tpu_tpu.obs.mfu import lm_flops_per_token

    config = _config()
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    full, window = (flops.needed_pairs(config, kind, 16384) for kind in flops.KINDS)
    assert (full, window) == (16384 * 16385 // 2, 4096 * 4097 // 2 + 12288 * 4096)
    assert window / full == pytest.approx(0.4375, abs=2e-4)
    assert flops.needed_pairs(config, "sliding_attention", 8192) / (8192 * 8193 // 2) \
        == pytest.approx(0.75, abs=2e-4)  # at 8192 the window shows little
    assert cfg.attn_pairs(16384) == {
        # the 16 diagonal pairs, and the window's 12 cut ones, at 10 of 16 sub-tiles
        "full_attention": (130 * 1024 * 1024, full),
        "sliding_attention": (70 * 1024 * 1024 - 28 * 6 * 256 * 256, window)}
    for seq in (16384, 8192, 1000):
        assert lm_flops_per_token(cfg, seq) == pytest.approx(flops.token_step(config, seq), rel=1e-12)
    assert 16384 * flops.token_step(config, 16384) == pytest.approx(34.70e12, rel=1e-3)
    core, moved = flops.causal_core_step(config, 1, 16384)
    assert core == 6 * 2 * 128 * 28 * full and moved > 0
    swa, _ = flops.swa_core_step(config, 1, 16384)
    assert swa == 3 * 6 * 2 * 128 * 28 * window
    work, _ = flops.experts_step(config, 16384 * 6 / 4)
    assert work == 4 * 3 * (16384 * 6 / 4) * 2 * 3 * 2560 * 768
