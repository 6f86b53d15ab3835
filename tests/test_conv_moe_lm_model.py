"""The sixth family of ``models/lm.py`` (``LFM2-24B-A2B``: a gated
short-convolution mixer three layers in four, grouped-query attention with
per-head q/k norms in the fourth, a dense first layer, sigmoid-and-bias
routing with no shared expert, a head tied to the embedding) against the
benchmark's plain reference (``benchmarks/reference/conv_moe_lm_model.py``) on
seeded weights, float32, at a cut that holds a block of every kind: logits,
loss, every gradient leaf and three AdamW steps; the convolution is causal and
its hand-written backward is autodiff's of the three shifted products, with and
without SiLU one function; q/k norm on and off differ; the eight expert ranks'
shares of a layer add up to the uncut layer; the tied embedding's gradient is
the lookup's and the head's added."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.reference import conv_moe_lm_model as ref_model
from benchmarks.reference import conv_moe_lm_params as ref_shapes
from benchmarks.reference import optim as ref_optim
from benchmarks.reference import params as ref_params
from jumbo_mae_tpu_tpu.models import lm
from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig, MlaMoeLM, ShortConv, SparseExperts
from jumbo_mae_tpu_tpu.ops import kda
from jumbo_mae_tpu_tpu.ops.pallas.attention import _sub_tile

DRIVER = harness.load_module("drivers", "conv_moe_lm_steps")
CELL = "lfm2_24b_pretrain_2x8k"


def _config() -> dict:
    """The configuration as its cell runs it."""
    return harness.load_cell(CELL)["config"]


def _peaked(params: dict) -> dict:
    """The seeded query, key, filter, router and expert matrices scaled up,
    for program and reference alike: 32 inputs of 0.02 leave every softmax
    flat, the filter's output a thousandth of its input and every expert's
    output a thousandth of the stream, so that a mutation of them would move
    nothing a float32 comparison sees; the real cut's 2048 inputs spread them
    as this does."""
    params = jax.tree_util.tree_map(lambda x: x, params)  # a copy of the tree
    for name in [n for n in params if n.startswith("block_")]:
        blk = params[name]
        leaves = [blk["attn"]["q"], blk["attn"]["k"]] if "attn" in blk else [
            blk["conv"]["in_proj"], blk["conv"]["conv"], blk["conv"]["out_proj"]]
        if "moe" in blk:
            leaves += [blk["moe"][k] for k in ("router", "gate", "up", "down")]
        for leaf in leaves:
            leaf["kernel"] = leaf["kernel"] * 8.0
    return params


@functools.cache
def _setup(seed: int = 11):
    config = DRIVER.tiny({"config": _config(), "traffic": {}})["config"]
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config) | {"dtype": "float32"})
    params = _peaked(jax.jit(lambda s: ref_shapes.make_params(s, config))(seed))
    biases = jax.jit(lambda s: ref_shapes.make_biases(s, config))(seed)
    first, rows = config["vocab_rows"]
    tokens = np.random.default_rng(seed).integers(first, first + rows, (3, 25), dtype=np.int32)
    return config, cfg, params, biases, jnp.asarray(tokens)


def _flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in leaves}


def _loss_and_grads(cfg, params, biases, tokens):
    def program(p):
        out = MlaMoeLM(cfg).apply({"params": p, "batch_stats": biases}, tokens)
        return out["loss"], out

    return jax.jit(jax.value_and_grad(program, has_aux=True))(params)


@functools.cache
def _reference():
    config, _, params, biases, tokens = _setup()
    return jax.jit(jax.value_and_grad(
        lambda p: ref_model.batch_loss(p, biases, tokens, config)[0]))(params)


def test_the_tiny_cut_holds_a_block_of_every_kind_and_a_tied_head():
    config, cfg, params, biases, tokens = _setup()
    assert cfg.kinds == ("conv", "full_attention") and cfg.first_k_dense == 1
    assert cfg.layers_by_kind == {"conv": 1, "full_attention": 1}
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.conv_taps) == (4, 2, 8, 3)
    assert cfg.qk_norm and cfg.tie_embeddings and cfg.shared_hidden == 0
    # a conv block has no heads and no (query, key) pairs
    assert cfg.attn_heads() == {"full_attention": (4, 4)}
    # one clamped block of 128, which a masked pair computes whole (one strip)
    assert cfg.attn_pairs(24) == {"full_attention": (128 * 128, 24 * 25 // 2)}
    assert _sub_tile(128) == 128
    # the other families' defaults stay theirs
    assert (MlaMoeConfig().qk_norm, MlaMoeConfig().tie_embeddings) == (False, False)
    assert MlaMoeConfig().layers_by_kind == {"mla": 40}
    variables = jax.eval_shape(lambda: MlaMoeLM(cfg).init(jax.random.key(0), tokens))
    assert set(variables) == {"params", "batch_stats"}
    shapes = jax.tree_util.tree_map(lambda s: tuple(s.shape), variables["params"])
    assert ref_params.flat_shapes(shapes) == ref_params.flat_shapes(ref_shapes.shapes(config))
    assert "head" not in shapes and set(shapes["block_0"]) == {"ln1", "ln2", "conv", "mlp"}
    assert shapes["block_0"]["conv"] == {"in_proj": {"kernel": (32, 96)},
                                         "conv": {"kernel": (3, 32)},
                                         "out_proj": {"kernel": (32, 32)}}
    assert set(shapes["block_1"]["attn"]) == {"q", "k", "v", "q_norm", "k_norm", "out"}
    assert shapes["block_1"]["attn"]["q_norm"] == {"scale": (8,)}
    assert set(shapes["block_1"]["moe"]) == {"router", "gate", "up", "down"}
    stats = jax.tree_util.tree_map(lambda s: tuple(s.shape), variables["batch_stats"])
    assert ref_params.flat_shapes(stats) == ref_params.flat_shapes(ref_shapes.bias_shapes(config))
    with pytest.raises(ValueError, match="layer_types name the kinds"):
        cfg.replace(layer_types=("conv", "convolution"))
    # a conv layer asks no rope and no head count
    only = cfg.replace(layers=1, layer_types=("conv",), rope_parameters=None, first_k_dense=0)
    assert only.attn_heads() == {} and only.attn_pairs(24) == {}


def test_logits_loss_and_every_gradient_leaf_match_the_reference():
    config, cfg, params, biases, tokens = _setup()
    (loss, out), grads = _loss_and_grads(cfg, params, biases, tokens)
    want, want_grads = _reference()
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert float(out["moe_dropped"]) == 0.0
    got, ref = _flat(grads), _flat(want_grads)
    assert got.keys() == ref.keys() and len(got) == 22
    for name, g in got.items():
        assert np.abs(ref[name]).max() > 0, name  # every leaf takes part, the taps too
        np.testing.assert_allclose(g, ref[name], rtol=2e-3,
                                   atol=2e-4 * np.abs(ref[name]).max(), err_msg=name)
    (logits,) = MlaMoeLM(cfg).apply({"params": params, "batch_stats": biases}, tokens,
                                    method=MlaMoeLM.logits)
    ids = tokens[0] - config["vocab_rows"][0]
    hidden, _, ops = ref_model.hidden_states(params, biases, ids, config)
    np.testing.assert_allclose(logits[0], ref_model.head_logits(ops, params, hidden, config),
                               rtol=1e-4, atol=1e-5)


def test_three_adamw_steps_follow_the_reference():
    """Loss by loss over three steps of the reference's AdamW, each side on
    its own gradients and with its own biases moved by the same rule, and the
    parameters' change at the end leaf by leaf."""
    config, cfg, params, biases, tokens = _setup()
    optim = config["optim"] | {"warmup_steps": 2, "init_lr": 1e-3, "peak_lr": 3e-3}

    def program(p, b, t):
        out, moved = MlaMoeLM(cfg).apply({"params": p, "batch_stats": b}, t,
                                         mutable=["batch_stats"])
        return out["loss"], moved["batch_stats"]

    def reference(p, b, t):
        loss, counts = ref_model.batch_loss(p, b, t, config)
        return loss, ref_model.next_biases(b, counts, config["router_bias_rate"])

    copy = lambda tree: jax.tree_util.tree_map(jnp.array, tree)
    sides = {}
    for name, fn in (("program", program), ("reference", reference)):
        step_fn = jax.jit(jax.value_and_grad(fn, has_aux=True))
        p, b, state, losses = copy(params), copy(biases), None, []
        for step in range(3):
            (loss, b), g = step_fn(p, b, jnp.roll(tokens, step, axis=0))
            losses.append(float(loss))
            p, state = ref_optim.adamw_step(p, g, state or ref_optim.adamw_init(p), optim)
        sides[name] = (losses, _flat(jax.tree_util.tree_map(jnp.subtract, p, params)), _flat(b))
    np.testing.assert_allclose(sides["program"][0], sides["reference"][0], rtol=2e-5)
    assert sides["reference"][0][2] < sides["reference"][0][0]
    for name, want in sides["reference"][1].items():
        norm = np.linalg.norm(want)
        assert norm > 0 and np.linalg.norm(sides["program"][1][name] - want) < 0.02 * norm, name
    for name, want in sides["reference"][2].items():  # the biases moved alike
        np.testing.assert_allclose(sides["program"][2][name], want, atol=1e-7, err_msg=name)


def test_the_control_can_round_in_one_block_alone():
    """``rounding="fp8@<l>"``: the one-layer fault the chip's limits are shown
    to catch (PERF.md §2), in a conv block or in the attention block."""
    config, _, params, biases, tokens = _setup()
    loss = lambda r: float(jax.jit(
        lambda p: ref_model.batch_loss(p, biases, tokens, config, r)[0])(params))
    assert len({loss(r) for r in ("float32", "fp8@0", "fp8@1", "fp8")}) == 4
    assert set(DRIVER.ONE_BLOCK_CONTROLS) == {"fp8@0", "fp8@1"}


# ------------------------------------------------------- the convolution

def _mixer(cfg, seed=3, seq=20):
    x = jax.random.normal(jax.random.key(seed), (2, seq, cfg.dim), jnp.float32)
    layer = ShortConv(cfg)
    params = layer.init(jax.random.key(seed + 1), x)["params"]
    params = jax.tree_util.tree_map(lambda leaf: leaf * 8.0, params)
    return layer, params, x


def test_the_conv_mixer_is_causal_and_reaches_two_tokens_back():
    """Moving token ``t`` leaves every output before ``t`` as it was, moves
    those at ``t``, ``t + 1`` and ``t + 2`` (three taps), and none after."""
    _, cfg, _, _, _ = _setup()
    layer, params, x = _mixer(cfg)
    t = 9
    y = layer.apply({"params": params}, x)
    moved = layer.apply({"params": params}, x.at[:, t].add(1.0))
    change = np.abs(np.asarray(moved - y)).max(axis=(0, 2))
    assert np.all(change[:t] == 0) and np.all(change[t:t + 3] > 1e-4) and np.all(
        change[t + 3:] == 0)
    # and it is the reference's mixer, a sequence at a time
    want = ref_model.short_conv(ref_model.Ops(), x[0], params)
    np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("act", [None, "silu"])
def test_the_filters_backward_rule_is_autodiff_of_the_shifted_products(act):
    """``causal_conv`` with and without SiLU is one function over one set of
    shifted sums: its value and its hand-written gradients against plain
    autodiff of ``Σ_j w_j ⊙ x_{t−K+1+j}`` written as K shifted products."""
    keys = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(keys[0], (2, 17, 12), jnp.float32)
    w = jax.random.normal(keys[1], (3, 12), jnp.float32)
    weight = jax.random.normal(keys[2], x.shape, jnp.float32)

    def plain(x, w):
        seq = x.shape[-2]
        z = sum(w[j] * jnp.pad(x, ((0, 0), (2 - j, 0), (0, 0)))[:, :seq] for j in range(3))
        return jax.nn.silu(z) if act == "silu" else z

    np.testing.assert_allclose(kda.causal_conv(x, w, act), plain(x, w), rtol=1e-6, atol=1e-6)
    got = jax.grad(lambda *a: (kda.causal_conv(*a, act) * weight).sum(), (0, 1))(x, w)
    want = jax.grad(lambda *a: (plain(*a) * weight).sum(), (0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-5)
    # SiLU is the default, the linear-attention layers' form; None is another function of x
    assert np.array_equal(kda.causal_conv(x, w), kda.causal_conv(x, w, act)) == (act == "silu")
    # nothing sequence-long is kept in float32: the residuals are the arguments
    kept = jax.vjp(lambda x, w: kda.causal_conv(x.astype(jnp.bfloat16), w, act), x, w)[1]
    dtypes = {leaf.dtype for leaf in jax.tree_util.tree_leaves(kept) if leaf.ndim == 3}
    assert dtypes == {jnp.dtype(jnp.bfloat16)}


def test_qk_norm_on_and_off_differ():
    _, cfg, params, biases, tokens = _setup()
    off = cfg.replace(qk_norm=False)
    bare = jax.tree_util.tree_map(lambda x: x, params)
    bare["block_1"]["attn"] = {k: v for k, v in bare["block_1"]["attn"].items()
                               if k not in ("q_norm", "k_norm")}
    shapes = jax.eval_shape(lambda: MlaMoeLM(off).init(jax.random.key(0), tokens))["params"]
    assert set(shapes["block_1"]["attn"]) == {"q", "k", "v", "out"}
    (on_loss, _), on = _loss_and_grads(cfg, params, biases, tokens)
    (off_loss, _), without = _loss_and_grads(off, bare, biases, tokens)
    assert float(on_loss) != float(off_loss)
    # the norm takes q's and k's length out of the scores: their kernels'
    # gradients are another thing altogether
    for name in ("q", "k"):
        a, b = (g["block_1"]["attn"][name]["kernel"] for g in (on, without))
        assert float(jnp.abs(a - b).max() / jnp.abs(b).max()) > 0.2, name


# ------------------------------------------------------- the shares add up
# guide §4: at a small size, the parts of the result that all the shares give
# add up to the uncut layer's: 16 experts in 8 ranks of 2, top-4, no shared
# expert to count once.

def test_the_eight_expert_ranks_shares_add_up_to_the_uncut_layer():
    config, cfg, _, _, _ = _setup()
    whole = config | {"num_experts": 16, "experts_held": [0, 16]}
    p = _peaked({"block_1": jax.jit(lambda s: ref_shapes.make_params(s, whole))(5)[
        "block_1"]})["block_1"]["moe"]
    bias = 0.01 * jax.random.normal(jax.random.key(8), (16,), jnp.float32)
    u = jax.random.normal(jax.random.key(6), (1, 40, cfg.dim), jnp.float32)
    ops = ref_model.Ops()
    want, _ = jax.jit(lambda p: ref_model.expert_layer(ops, u[0], p, bias, whole, first=0,
                                                       shared=False))(p)
    cut = lambda k, first: {"kernel": p[k]["kernel"][first:first + 2]}
    total = 0.0
    for first in range(0, 16, 2):
        share = {"router": p["router"], **{k: cut(k, first) for k in ("gate", "up", "down")}}
        layer = SparseExperts(cfg.replace(experts_held=(first, 2)))
        out, stats = layer.apply(
            {"params": share, "batch_stats": {"router_bias": bias}}, u)
        ref, _ = ref_model.expert_layer(ops, u[0], share, bias, whole, first=first, shared=False)
        np.testing.assert_allclose(out[0], ref, rtol=1e-4, atol=1e-6)
        assert float(jnp.abs(out).max()) > 1e-3  # each rank says something
        assert dict(zip(cfg.moe_counters, np.asarray(stats)))["dropped"] == 0
        total = total + out[0]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-6)


# ------------------------------------------------------- the tied head

def test_the_tied_embeddings_gradient_is_the_lookups_and_the_heads():
    """An untied program whose head kernel is the embedding transposed
    computes the same loss; the tied embedding's gradient is that program's
    embedding gradient (the lookup's scatter) plus its head gradient
    transposed, and every other leaf's is the same."""
    _, cfg, params, biases, tokens = _setup()
    (loss, _), grads = _loss_and_grads(cfg, params, biases, tokens)
    untied = params | {"head": {"kernel": params["embedding"].T}}
    (loss_untied, _), parts = _loss_and_grads(cfg.replace(tie_embeddings=False), untied, biases,
                                              tokens)
    np.testing.assert_allclose(loss, loss_untied, rtol=1e-6)
    lookup, head = parts["embedding"], parts["head"]["kernel"].T
    assert float(jnp.abs(lookup).max()) > 0 and float(jnp.abs(head).max()) > 0
    np.testing.assert_allclose(grads["embedding"], lookup + head, rtol=1e-5,
                               atol=1e-6 * float(jnp.abs(head).max()))
    np.testing.assert_allclose(grads["block_0"]["conv"]["conv"]["kernel"],
                               parts["block_0"]["conv"]["conv"]["kernel"], rtol=1e-5, atol=1e-9)


# ---------------------------------------------------------- the real cut

def test_parameters_here_is_the_trees_count_and_the_recipe_is_the_file():
    """The program's own tree at the real cut, shapes only."""
    from jumbo_mae_tpu_tpu.cli.train import build_model
    from jumbo_mae_tpu_tpu.config import load_config

    config = _config()
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    variables = jax.eval_shape(lambda: MlaMoeLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 66), jnp.int32)))
    shapes = variables["params"]
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == config["parameters_here"] == 832_651_520
    assert count(shapes["block_0"]["conv"]) == 16_783_360  # W_in, the taps, W_out
    assert count(shapes["block_1"]["attn"]) == 10_485_888  # q, k, v, out and two norms of 64
    assert (count(shapes["block_0"]), count(shapes["block_1"]), count(shapes["block_2"])) == (
        89_139_200, 86_118_528, 92_416_000)
    assert count(shapes["embedding"]) == 16_777_216 and "head" not in shapes
    want = ref_params.flat_shapes(ref_shapes.shapes(config))
    assert ref_params.flat_shapes(jax.tree_util.tree_map(lambda s: tuple(s.shape), shapes)) == want
    recipe = build_model(load_config(str(harness.ROOT / config["recipe"])))[1]
    assert recipe == cfg  # the recipe states the sizes the benchmark's file translates to
    assert cfg.kinds == ("conv", "full_attention", "conv", "conv", "conv") + (
        "full_attention", "conv", "conv", "conv")
    assert cfg.layers_by_kind == {"conv": 7, "full_attention": 2}
    assert cfg.attn_heads() == {"full_attention": (32, 32)}
    taken = [r for r in config["ladder"]["rungs"] if r["verdict"] == "taken"]
    assert [r["layers"] for r in taken] == [cfg.layers]
    assert taken[0]["program_bytes"] <= 15.2e9 and taken[0]["parameters"] == count(shapes)
    assert config["ladder"]["rungs"][1]["parameters"] == 469_284_992


def test_token_flops_three_layers_in_four_do_not_grow_with_the_sequence():
    from benchmarks import flops_conv_moe_lm as flops
    from jumbo_mae_tpu_tpu.obs.mfu import lm_flops_per_token

    config = _config()
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    assert flops.needed_pairs(config, "full_attention", 8192) == 8192 * 8193 // 2
    assert flops.needed_pairs(config, "conv", 8192) == 0
    for seq in (8192, 16384, 1000):
        assert lm_flops_per_token(cfg, seq) == pytest.approx(flops.token_step(config, seq),
                                                             rel=1e-12)
    # only the two attention layers' cores grow: 2 · (seq / 2) · 32 heads · (64 + 64) a layer
    grown = flops.token_forward(config, 16384) - flops.token_forward(config, 8192)
    assert grown == pytest.approx(2 * 2 * 4096 * 32 * 128, rel=1e-9)
    assert 16384 * flops.token_step(config, 8192) == pytest.approx(29.48e12, rel=1e-3)
    core, moved = flops.causal_core_step(config, 2, 8192)
    assert core == 2 * 2 * 6 * 2 * 64 * 32 * (8192 * 8193 // 2) and moved > 0
    mix_flops, mix_bytes = flops.sconv_mix_step(config, 2, 8192)
    assert mix_bytes == 7 * 16384 * 2048 * 11 * 2 and mix_flops == 7 * 16384 * 2048 * 28
    assert mix_bytes / 819e9 > mix_flops / 197e12  # the bytes bind
    work, _ = flops.experts_step(config, 16384 * 4 / 8)
    assert work == 8 * 3 * (16384 * 4 / 8) * 2 * 3 * 2048 * 1536
