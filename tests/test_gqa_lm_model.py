"""The grouped-query family of ``models/lm.py`` (full and sliding-window
softmax attention in a published pattern, two head counts over shared
key/value heads, rotate-half rope that is partial and YaRN-scaled on the full
kind, a head-wise output gate) against the benchmark's plain reference
(``benchmarks/reference/gqa_lm_model.py``: every (query, key) pair, both
masks as comparisons of positions) on seeded weights, float32, at a cut that
keeps the structure: two periods, 6 and 8 query heads over 2 key/value heads,
a window of 11 tokens (smaller than the sequence and no multiple of any
block), the first layer dense, 16 experts top-4 of which 4 are held."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.reference import gqa_lm_model as ref_model
from benchmarks.reference import gqa_lm_params as ref_shapes
from benchmarks.reference import params as ref_params
from jumbo_mae_tpu_tpu.models import lm
from jumbo_mae_tpu_tpu.models.lm import MOE_COUNTERS, MlaMoeConfig, MlaMoeLM, SparseExperts

CELL = "laguna_xs2_pretrain_2x8k"
DRIVER = harness.load_module("drivers", "gqa_lm_steps")


@functools.cache
def _setup(seed: int = 11):
    # the driver's tiny cut holds a layer of each kind; here the real cut's two periods
    config = DRIVER.tiny(harness.load_cell(CELL))["config"] | {"num_hidden_layers": 8}
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config) | {"dtype": "float32"})
    params = ref_shapes.make_params(seed, config)
    biases = ref_shapes.make_biases(seed, config)
    first, rows = config["vocab_rows"]
    tokens = np.random.default_rng(seed).integers(first, first + rows, (3, 28), dtype=np.int32)
    return config, cfg, params, biases, jnp.asarray(tokens)


def _flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in leaves}


def test_the_tiny_cut_holds_both_kinds_in_two_periods():
    config, cfg, params, *_ = _setup()
    kinds = [(cfg.attention_kind(i), cfg.heads_per_layer[i], i >= cfg.first_k_dense)
             for i in range(cfg.layers)]
    period = [("full_attention", 6), ("sliding_attention", 8), ("sliding_attention", 8),
              ("sliding_attention", 8)]
    assert [k[:2] for k in kinds] == period * 2
    assert [k[2] for k in kinds] == [False] + [True] * 7
    assert params["block_0"]["attn"]["q"]["kernel"].shape == (32, 6, 16)
    assert params["block_1"]["attn"]["q"]["kernel"].shape == (32, 8, 16)
    assert params["block_1"]["attn"]["k"]["kernel"].shape == (32, 2, 16)
    assert "mlp" in params["block_0"] and "moe" in params["block_4"]
    assert cfg.sliding_window == 11 < 27 and cfg.kda_layers == 0
    # the YaRN blend falls among the tiny full layer's four pair frequencies
    full = cfg.rope("full_attention")
    plain = full.rope_theta ** (-2.0 * np.arange(4) / 8)
    ratio = full.inv_freq(8) / plain
    assert list(ratio[:2]) == [1.0, 1.0] and ratio[2] == pytest.approx((1 + 1 / 64) / 2)
    assert ratio[3] == pytest.approx(1 / 64)


def test_logits_match_the_reference():
    config, cfg, params, biases, tokens = _setup()
    # jitted, both: op by op the eight layers compile a program an operation
    (got,) = jax.jit(lambda p: MlaMoeLM(cfg).apply({"params": p, "batch_stats": biases}, tokens,
                                                   method="logits"))(params)
    assert got.shape == (3, 27, config["vocab_size"])
    ops = ref_model.Ops()

    @jax.jit
    def reference(ids):
        hidden, _ = ref_model.hidden_states(ops, params, biases, ids, config)
        return ref_model.head_logits(ops, params, hidden, config)

    for row in range(tokens.shape[0]):
        want = reference(tokens[row] - config["vocab_rows"][0])
        np.testing.assert_allclose(got[row], want, rtol=2e-4, atol=2e-5)


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
    assert float(out["moe_dropped"]) == 0.0 and "loss_mtp" not in out
    got, ref = _flat(grads), _flat(want_grads)
    assert got.keys() == ref.keys() and len(got) == 111
    for name, g in got.items():
        assert np.abs(ref[name]).max() > 0, name  # every leaf takes part
        np.testing.assert_allclose(g, ref[name], rtol=2e-3,
                                   atol=2e-4 * np.abs(ref[name]).max(), err_msg=name)


@pytest.mark.parametrize("kind,heads", [("full_attention", 6), ("sliding_attention", 8)])
def test_a_layer_through_the_interpreted_kernels_is_the_references(kind, heads, monkeypatch):
    """One attention layer of each kind with the Pallas kernels (interpreted,
    blocks of 8) for its core: the path the chip takes, against the
    reference's every-pair form."""
    from jumbo_mae_tpu_tpu.ops.pallas.attention import pallas_causal_attention

    config, cfg, params, *_ = _setup()
    layer = config["layer_types"].index(kind)
    monkeypatch.setattr(lm, "causal_attention", lambda *xs, impl=None, window=None:
                        pallas_causal_attention(*xs, 8, True, window))
    x = jax.random.normal(jax.random.key(3), (2, 27, cfg.dim), jnp.float32)
    p = params[f"block_{layer}"]["attn"]
    module = lm.GroupedQueryAttention(cfg, heads, kind == "sliding_attention")
    got = module.apply({"params": p}, x)
    ops = ref_model.Ops()
    for row in range(x.shape[0]):
        want = ref_model.attention(ops, x[row], p, config, layer)
        np.testing.assert_allclose(got[row], want, rtol=2e-4, atol=2e-6)


def test_rope_half_against_complex_rotation():
    """Rotate-half, partial, YaRN: pair (j, j + r/2) as a complex number
    turns by position x inv_freq_j and grows by the attention factor; the
    dimensions past r pass through."""
    _, cfg, *_ = _setup()
    rope = cfg.rope("full_attention").__class__(
        rope_theta=5e5, rope_type="yarn", partial_rotary_factor=0.5, factor=64,
        original_max_position_embeddings=4096, beta_fast=64, beta_slow=1,
        attention_factor=1.4158883083359672)
    x = jax.random.normal(jax.random.key(0), (2, 40, 128), jnp.float32)
    got = np.asarray(lm.rope_half(x, rope), np.float64)
    z = np.asarray(x[..., :32], np.float64) + 1j * np.asarray(x[..., 32:64], np.float64)
    freq = rope.inv_freq(64)
    # by the published formulas: low 5, high 16 of 32 pair frequencies
    plain = 5e5 ** (-2.0 * np.arange(32) / 64)
    blend = np.clip((np.arange(32) - 5) / (16 - 5), 0, 1)
    np.testing.assert_allclose(freq, (1 - blend) * plain + blend * plain / 64, rtol=1e-12)
    turned = 1.4158883083359672 * z * np.exp(1j * np.arange(40)[:, None] * freq[None, :])
    np.testing.assert_allclose(got[..., :32], turned.real, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[..., 32:64], turned.imag, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(got[..., 64:], np.asarray(x[..., 64:], np.float64))
    plain_rope = cfg.rope("sliding_attention")
    np.testing.assert_allclose(plain_rope.inv_freq(128), 1e4 ** (-np.arange(64) / 64), rtol=1e-12)


# a bfloat16 output may differ by one rounding; a float32 one by the order of
# a multiply and an add
ROPE_TOL = {"bfloat16": dict(rtol=2**-7, atol=2**-9), "float32": dict(rtol=1e-5, atol=1e-5)}


@pytest.mark.parametrize("case,kind,dtype,shape", [
    *[(case, kind, dtype, (2, 4, 128, 128)) for case in ("forward", "vjp")
      for kind in lm.GQA_KINDS for dtype in ROPE_TOL],
    ("pass_through", "full_attention", "bfloat16", (2, 4, 128, 128)),
    ("pass_through", "full_attention", "float32", (2, 4, 128, 128)),
    ("falls_back", "full_attention", "bfloat16", (2, 4, 40, 128)),  # no block of whole sublane tiles
    ("falls_back", "sliding_attention", "float32", (2, 4, 64, 96)),  # no whole 128-lane tile
    ("falls_back", "full_attention", "float32", (2, 40, 128)),  # not head-major
])
def test_rope_kernel_against_the_jax_numpy_form(case, kind, dtype, shape, monkeypatch):
    """The one-pass kernel (``ops/pallas/rope.py``, in the Pallas
    interpreter, at blocks cut so that every grid axis has two steps: a
    block's positions come from its block of the tables), for the cell's own
    two kinds of ``Rope`` (θ 1e4 on all 128 dimensions; θ 5e5, YaRN factor 64
    on the first 64, attention factor 1.4159), is ``rope_half``'s
    ``jax.numpy`` form, which is what runs here without ``interpret``: the
    output and, with no residual kept, the transpose; the dimensions past the
    rotary part to the bit; a shape the kernel does not take goes to the
    ``jax.numpy`` form and raises nothing."""
    from jax._src.ad_checkpoint import saved_residuals

    from jumbo_mae_tpu_tpu.ops.pallas import rope as kernel

    monkeypatch.setattr(kernel, "SEQ_BLOCK", 64)
    monkeypatch.setattr(kernel, "BLOCK_ELEMENTS", 2 * 64 * 128)
    rope = MlaMoeConfig(**DRIVER.lm_fields(harness.load_cell(CELL)["config"])).rope(kind)
    assert (rope.partial_rotary_factor, rope.attention_factor > 1) == (
        (0.5, True) if kind == "full_attention" else (1.0, False))
    x, w = (jax.random.normal(jax.random.key(i), shape, jnp.float32).astype(dtype)
            for i in (0, 1))
    f32 = lambda a: np.asarray(a, np.float32)
    if case == "falls_back":
        assert len(shape) != 4 or kernel.rope_blocks(*shape[1:]) is None
        np.testing.assert_array_equal(f32(lm.rope_half(x, rope, interpret=True)),
                                      f32(lm.rope_half(x, rope)))
        return
    assert kernel.rope_blocks(*shape[1:]) == (2, 64)
    got = lm.rope_half(x, rope, interpret=True)
    assert got.dtype == x.dtype
    if case == "forward":
        np.testing.assert_allclose(f32(got), f32(lm.rope_half(x, rope)), **ROPE_TOL[dtype])
    elif case == "pass_through":
        r = int(shape[-1] * rope.partial_rotary_factor)
        np.testing.assert_array_equal(f32(got[..., r:]), f32(x[..., r:]))
        assert not np.array_equal(f32(got[..., :r]), f32(x[..., :r]))
    else:
        ct, = jax.vjp(lambda x: lm.rope_half(x, rope, interpret=True), x)[1](w)
        want, = jax.vjp(lambda x: lm.rope_half(x, rope), x)[1](w)
        assert ct.dtype == x.dtype
        np.testing.assert_allclose(f32(ct), f32(want), **ROPE_TOL[dtype])
        # nothing is kept for the transpose, which makes its tables again
        assert saved_residuals(lambda x: lm.rope_half(x, rope, interpret=True), x) == []


def _layer(config, cfg, seed=5, tokens=40):
    """One expert layer's full weights (all 16 experts), biases and input."""
    whole = config | {"num_experts": config["published"]["num_experts"],
                      "experts_held": [0, config["published"]["num_experts"]]}
    p = ref_params.make_params(seed, ref_shapes._block(whole, 1, True)["moe"])
    bias = 0.01 * jax.random.normal(jax.random.key(seed), (16,), jnp.float32)
    x = jax.random.normal(jax.random.key(seed + 1), (1, tokens, cfg.dim), jnp.float32)
    return whole, p, bias, x


def _share(p, first, held):
    cut = lambda k: {"kernel": p[k]["kernel"][first:first + held]}
    return {**p, "gate": cut("gate"), "up": cut("up"), "down": cut("down")}


@pytest.mark.parametrize("shares", [16, 4])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The routed parts that the chips of a ``shares``-way expert split
    compute (16 shares of one expert each: the cell's 16-way deployment at
    the small layer's size), with the shared expert counted once, are the
    uncut reference layer."""
    config, cfg, *_ = _setup()
    whole, p, bias, x = _layer(config, cfg)
    held = 16 // shares
    ops, routing = ref_model.Ops(), ref_model._routing(whole)
    want, counts = ref_model.expert_layer(ops, x[0], p, bias, routing, first=0)
    shared = ref_model.gated_mlp(ops, x[0], p["shared"])

    def apply(first):
        layer = SparseExperts(cfg.replace(experts_held=(first, held)))
        variables = {"params": _share(p, first, held), "batch_stats": {"router_bias": bias}}
        return layer.apply(variables, x)

    outs = [apply(first) for first in range(0, 16, held)]
    np.testing.assert_allclose(sum(o[0] - shared for o, _ in outs) + shared, want,
                               rtol=1e-4, atol=1e-6)
    for first, (out, stats) in zip(range(0, 16, held), outs):
        ref, _ = ref_model.expert_layer(ops, x[0], _share(p, first, held), bias, routing,
                                        first=first)
        np.testing.assert_allclose(out[0], ref, rtol=1e-4, atol=1e-6)
        stats = dict(zip(MOE_COUNTERS, np.asarray(stats)))
        assert stats["dropped"] == 0
        assert stats["held_share"] == pytest.approx(
            float(counts[first:first + held].sum()) / (40 * 4))


def test_lists_that_do_not_fit_are_refused():
    _, cfg, *_ = _setup()
    with pytest.raises(ValueError, match="name each of the 8 layers"):
        cfg.replace(heads_per_layer=(6, 8, 8, 8))
    with pytest.raises(ValueError, match="no multiple of 2"):
        cfg.replace(heads_per_layer=(6, 8, 8, 7) * 2)
    with pytest.raises(ValueError, match="name the kinds"):
        cfg.replace(layer_types=("full_attention", "chunked_attention") * 4)
    with pytest.raises(ValueError, match="no MTP module"):
        cfg.replace(mtp_layers=1)
    with pytest.raises(ValueError, match="only default and yarn"):
        lm.Rope(rope_theta=1e4, rope_type="llama3")


def test_parameters_here_is_the_trees_count_and_the_recipe_is_the_file():
    """The program's own tree at the real cut, shapes only."""
    from jumbo_mae_tpu_tpu.cli.train import build_model
    from jumbo_mae_tpu_tpu.config import load_config

    config = harness.load_cell(CELL)["config"]
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    shapes = jax.eval_shape(lambda: MlaMoeLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 66), jnp.int32)))["params"]
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == config["parameters_here"] == 765_954_048
    want = ref_params.flat_shapes(ref_shapes.shapes(config))
    assert ref_params.flat_shapes(jax.tree_util.tree_map(lambda s: tuple(s.shape), shapes)) == want
    recipe = build_model(load_config(str(harness.ROOT / config["recipe"])))[1]
    assert recipe == cfg  # the recipe states the sizes the benchmark's file translates to


def test_token_flops_count_three_layers_in_four_do_not_grow_with_the_sequence():
    """About 53.9 TF a step of 16 384 tokens, and from 8192 to 16 384 tokens
    only the two full layers' cores grow: a sliding layer's core counts
    ``min(i + 1, window)`` keys a query."""
    from benchmarks import flops_gqa_lm

    config = harness.load_cell(CELL)["config"]
    bench, program = DRIVER.flops_pair(config)
    assert bench == pytest.approx(program, rel=1e-12)
    assert bench * 16384 == pytest.approx(53.9e12, rel=2e-3)
    grown = flops_gqa_lm.token_step(config, 16384) - flops_gqa_lm.token_step(config, 8192)
    full = 3 * 2 * 48 * (128 + 128)  # a full layer's core: 3 x 2 h (qk + v) a key position
    sliding = 3 * 2 * 64 * 256 * (512 * 513 / 2 + 15872 * 512) / 16384 \
        - 3 * 2 * 64 * 256 * (512 * 513 / 2 + 7680 * 512) / 8192  # the window's ramp, amortised
    assert grown == pytest.approx(2 * full * 4096 + 6 * sliding, rel=1e-9)
    assert 6 * sliding < 1e-2 * grown
    tiny = DRIVER.tiny(harness.load_cell(CELL))["config"]
    assert DRIVER.flops_pair(tiny)[0] == pytest.approx(DRIVER.flops_pair(tiny)[1], rel=1e-12)
    flops, moved = flops_gqa_lm.swa_core_step(config, 2, 8192)
    assert flops == 2 * 6 * 64 * 6 * 2 * 128 * (512 * 513 // 2 + 7680 * 512)
    # three kernels: q, k, v in each, dO in the two backward ones; o, dQ, dK and dV out
    assert moved == 2 * 6 * ((3 + 2 + 2) * 64 + (3 * 2 + 2) * 8) * 8192 * 128 * 2
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    pairs = cfg.attn_pairs(8192)
    assert set(pairs) == set(flops_gqa_lm.KINDS)
    for kind, (visited, needed) in pairs.items():
        assert needed == flops_gqa_lm.needed_pairs(config, kind, 8192) <= visited
    assert MlaMoeConfig(layers=2, mtp_layers=1).attn_pairs(8192) == {
        "mla": (33 * 1024 * 1024, 8192 * 8193 // 2)}  # 8 diagonal pairs at 10 of 16 sub-tiles


def test_the_configuration_file_keeps_the_published_config_but_for_what_reduced_names():
    """The file is the published ``config.json`` key for key (the driver
    holds it against the catalog's row): what ``reduced`` names differs from
    ``published``'s copy and has its reason, the three per-layer lists are
    kept whole, and every published width stands at the top level."""
    config = harness.load_cell(CELL)["config"]
    assert config["source"] == "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    cut = set(config["reduced"]) - {"chips", "dataset", "weights"}
    assert cut == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert {k: (config[k], config["published"][k]) for k in sorted(cut)} == {
        "num_experts": (16, 256), "num_hidden_layers": (8, 40), "vocab_size": (12544, 100352)}
    assert set(config["reduced"]) == set(config["reduced_why"])
    assert len(config["layer_types"]) == len(config["num_attention_heads_per_layer"]) == len(
        config["mlp_layer_types"]) == 40
    widths = ("hidden_size", "head_dim", "num_key_value_heads", "sliding_window",
              "intermediate_size", "moe_intermediate_size", "shared_expert_intermediate_size",
              "num_experts_per_tok", "moe_routed_scaling_factor")
    assert [config[k] for k in widths] == [2048, 128, 8, 512, 8192, 512, 512, 8, 2.5]
