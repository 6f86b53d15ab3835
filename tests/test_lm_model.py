"""The latent-attention sparse-expert language model (``models/lm.py``)
against the benchmark's plain reference (``benchmarks/reference/lm_model.py``)
on seeded weights, float32, at a cut that keeps the structure: q/kv latent
ranks, the nope ‖ rope split, 1 dense + 2 expert layers + the MTP module, 16
experts top-4 of which 4 are held, a slice of the vocabulary."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.reference import lm_model, lm_params
from benchmarks.reference import params as ref_params
from jumbo_mae_tpu_tpu.models import lm
from jumbo_mae_tpu_tpu.models.lm import MOE_COUNTERS, MlaMoeConfig, MlaMoeLM, SparseExperts
from jumbo_mae_tpu_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul, grouped_outer

CELL = "joyai_flash_pretrain_2x8k"


@functools.cache
def _setup(seed: int = 11):
    driver = harness.load_module("drivers", "lm_steps")
    config = driver.tiny(harness.load_cell(CELL))["config"]
    cfg = MlaMoeConfig(**driver.lm_fields(config) | {"dtype": "float32"})
    params = ref_params.make_params(seed, lm_params.lm_shapes(config))
    biases = lm_params.make_biases(seed, config)
    first, rows = config["vocab_rows"]
    tokens = np.random.default_rng(seed).integers(first, first + rows, (3, 22), dtype=np.int32)
    return config, cfg, params, biases, jnp.asarray(tokens)


def _flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in leaves}


def test_both_heads_logits_match_the_reference():
    config, cfg, params, biases, tokens = _setup()
    # jitted, both: op by op every layer compiles a program an operation
    got = jax.jit(lambda p: MlaMoeLM(cfg).apply({"params": p, "batch_stats": biases}, tokens,
                                                method="logits"))(params)
    assert len(got) == 2 and got[0].shape == (3, 20, config["vocab_size"])
    ops = lm_model.Ops()

    @jax.jit
    def reference(ids):
        hidden = lm_model.hidden_states(ops, params, biases, ids, config)[0]
        return [lm_model.head_logits(ops, params, h, config) for h in hidden]

    for row in range(tokens.shape[0]):
        for head, want in enumerate(reference(tokens[row] - config["vocab_rows"][0])):
            np.testing.assert_allclose(got[head][row], want, rtol=2e-4, atol=2e-5)


def test_loss_and_every_gradient_leaf_match_the_reference():
    config, cfg, params, biases, tokens = _setup()
    model = MlaMoeLM(cfg)

    def program(p):
        out = model.apply({"params": p, "batch_stats": biases}, tokens)
        return out["loss"], out

    (loss, out), grads = jax.jit(jax.value_and_grad(program, has_aux=True))(params)
    (want, _), want_grads = jax.jit(jax.value_and_grad(
        lambda p: lm_model.batch_loss(p, biases, tokens, config), has_aux=True))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert float(out["moe_dropped"]) == 0.0
    got, ref = _flat(grads), _flat(want_grads)
    assert got.keys() == ref.keys() and len(got) == 66
    for name, g in got.items():
        assert np.abs(ref[name]).max() > 0, name  # every leaf takes part
        np.testing.assert_allclose(g, ref[name], rtol=2e-3,
                                   atol=2e-4 * np.abs(ref[name]).max(), err_msg=name)


def _layer(config, cfg, seed=5, tokens=40):
    """One expert layer's full weights (all 16 experts), biases and input."""
    whole = config | {"n_routed_experts": config["published"]["n_routed_experts"],
                      "experts_held": [0, config["published"]["n_routed_experts"]]}
    p = ref_params.make_params(seed, lm_params._block(whole, True)["moe"])
    bias = 0.01 * jax.random.normal(jax.random.key(seed), (16,), jnp.float32)
    x = jax.random.normal(jax.random.key(seed + 1), (1, tokens, cfg.dim), jnp.float32)
    return whole, p, bias, x


def _share(p, first, held):
    cut = lambda k: {"kernel": p[k]["kernel"][first:first + held]}
    return {**p, "gate": cut("gate"), "up": cut("up"), "down": cut("down")}


def _apply_layer(cfg, p, bias, x, first, held, mutable=False):
    layer = SparseExperts(cfg.replace(experts_held=(first, held)))
    variables = {"params": _share(p, first, held), "batch_stats": {"router_bias": bias}}
    return layer.apply(variables, x, mutable=["batch_stats"] if mutable else False)


def test_the_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the 4 chips of a 4-way expert
    split compute, with the shared expert counted once, are the uncut
    reference layer."""
    config, cfg, *_ = _setup()
    whole, p, bias, x = _layer(config, cfg)
    ops = lm_model.Ops()
    want, _ = lm_model.expert_layer(ops, x[0], p, bias, whole, first=0)
    shared = lm_model.gated_mlp(ops, x[0], p["shared"])
    outs = [_apply_layer(cfg, p, bias, x, first, 4)[0][0] for first in (0, 4, 8, 12)]
    np.testing.assert_allclose(sum(o - shared for o in outs) + shared, want,
                               rtol=1e-4, atol=1e-6)
    # and each share is the reference's own share, shared expert included
    for first, out in zip((0, 4, 8, 12), outs):
        ref, _ = lm_model.expert_layer(ops, x[0], _share(p, first, 4), bias, whole, first=first)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-6)


def test_skewed_routing_drops_nothing():
    """One held expert takes every token and another takes none: the grouped
    product still equals the reference's loop over experts, and the counters
    say so."""
    config, cfg, *_ = _setup()
    whole, p, bias, x = _layer(config, cfg)
    bias = bias.at[4].set(10.0).at[5].set(-10.0)  # everyone picks 4, no one picks 5
    out, stats = _apply_layer(cfg, p, bias, x, 4, 4)
    ref, _ = lm_model.expert_layer(lm_model.Ops(), x[0], _share(p, 4, 4), bias, whole, first=4)
    np.testing.assert_allclose(out[0], ref, rtol=1e-4, atol=1e-6)
    stats = dict(zip(MOE_COUNTERS, np.asarray(stats)))
    assert stats["rows_max"] == x.shape[1] and stats["rows_min"] == 0
    assert stats["dropped"] == 0 and stats["imbalance"] > 2


@pytest.mark.parametrize("pairs,held,experts,want", [
    (131_072, 16, 256, 16_384),   # the cell: 2 x 8192 tokens top-8, 16 of 256 held
    (131_072, 256, 256, 131_072),  # every expert held: one round of every pair
    (160, 4, 16, 160),             # the toy: a row tile is more than all its pairs
    (1024, 2, 16, 256),
    (3000, 2, 16, 768),            # 750 rows, in whole row tiles
])
def test_the_chunk_is_twice_the_uniform_share_in_whole_row_tiles(pairs, held, experts, want):
    assert lm.chunk_rows(pairs, held, experts) == want


# 16 experts top-2 of which 2 are held, 512 tokens: a round takes twice the
# uniform share of the 1024 pairs, 2 · 1024 · 2 / 16 = 256 rows, one row tile
ROUNDS = {
    # every token picks expert 4 and some pick 5 too: 512 + a ragged rest
    "three_rounds_ragged_last": dict(first=4, held=2, bias={4: 10.0, 5: 0.03}, rounds=3),
    # every choice of every token is held: all 1024 pairs, n k / chunk rounds
    "worst_case_every_pair_held": dict(first=4, held=2, bias={4: 10.0, 5: 10.0}, rounds=4),
    # all experts held (experts_held=None): the chunk is every pair
    "all_experts_held_one_round": dict(first=0, held=16, bias={}, rounds=1),
}


@pytest.mark.parametrize("impl,interpret", [("ragged_dot", False), ("pallas", True)])
@pytest.mark.parametrize("routing", list(ROUNDS))
def test_rounds_take_every_held_pair(routing, impl, interpret, monkeypatch):
    """Routing that fills more than one chunk is computed in full: output,
    every gradient leaf, the router's and the input's against the
    reference, nothing dropped, and as many rounds as reckoned by hand."""
    first, held = ROUNDS[routing]["first"], ROUNDS[routing]["held"]
    config, cfg, *_ = _setup()
    tokens, k = 512, 2
    cfg = cfg.replace(experts_per_token=k)
    whole, p, bias, x = _layer(config | {"num_experts_per_tok": k}, cfg, tokens=tokens)
    for expert, value in ROUNDS[routing]["bias"].items():
        bias = bias.at[expert].set(value)
    chunk = tokens * k if held == 16 else ROW_TILE
    assert lm.chunk_rows(tokens * k, held, 16) == chunk
    _, _, counts = lm_model.route(lm_model.Ops(), x[0], p, bias, whole)
    total = int(counts[first:first + held].sum())
    rounds = -(-total // chunk)
    assert rounds == ROUNDS[routing]["rounds"]
    assert total % chunk if "ragged" in routing else total == tokens * k
    monkeypatch.setattr(lm, "routed_experts", functools.partial(
        lm.routed_experts, impl=impl, interpret=interpret))
    layer = SparseExperts(cfg.replace(experts_held=None if held == 16 else (first, held)))
    weight = jax.random.normal(jax.random.key(9), x.shape)
    share = _share(p, first, held)

    def program(params, x):
        out, stats = layer.apply({"params": params, "batch_stats": {"router_bias": bias}}, x)
        return (out * weight).sum(), (out, stats)

    def reference(params, x):
        out, _ = lm_model.expert_layer(lm_model.Ops(), x[0], params, bias, whole, first=first)
        return (out * weight[0]).sum(), out

    (_, (out, stats)), grads = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)(share, x)
    (_, want), want_grads = jax.value_and_grad(reference, argnums=(0, 1), has_aux=True)(share, x)
    np.testing.assert_allclose(out[0], want, rtol=1e-4, atol=1e-6)
    stats = dict(zip(MOE_COUNTERS, np.asarray(stats)))
    assert stats["dropped"] == 0 and stats["rounds"] == rounds
    assert stats["held_share"] == pytest.approx(total / (tokens * k))
    got, ref = _flat(grads), _flat(want_grads)
    assert got.keys() == ref.keys() and len(got) == 8  # router, 3 stacked, 3 shared, the input
    for name, g in got.items():
        assert np.abs(ref[name]).max() > 0, name
        np.testing.assert_allclose(g, ref[name], rtol=2e-3,
                                   atol=2e-4 * np.abs(ref[name]).max(), err_msg=name)


def test_the_bias_rule():
    config, cfg, *_ = _setup()
    whole, p, bias, x = _layer(config, cfg)
    (_, _), updated = _apply_layer(cfg, p, bias, x, 4, 4, mutable=True)
    _, _, counts = lm_model.route(lm_model.Ops(), x[0], p, bias, whole)
    assert counts.sum() == x.shape[1] * cfg.experts_per_token
    want = bias + cfg.router_bias_rate * np.sign(counts.mean() - counts)
    np.testing.assert_allclose(updated["batch_stats"]["router_bias"], want, rtol=0, atol=1e-9)
    assert len(np.unique(np.sign(counts.mean() - counts))) > 1


@pytest.mark.parametrize("impl,interpret", [("ragged_dot", False), ("pallas", True)])
def test_grouped_product_matches_a_loop_over_experts(impl, interpret):
    """Routing skewed so that one group takes most rows and one takes none;
    the rows past the last group come out zero, and so do their gradients."""
    sizes = [41, 0, 9, 6]
    m, k, n = 64, 16, 24
    keys = jax.random.split(jax.random.key(0), 3)
    lhs, rhs = jax.random.normal(keys[0], (m, k)), jax.random.normal(keys[1], (4, k, n))
    w = jax.random.normal(keys[2], (m, n))

    def loop(a, b):
        out, start = jnp.zeros((m, n)), 0
        for g, size in enumerate(sizes):
            out = out.at[start:start + size].set(a[start:start + size] @ b[g])
            start += size
        return out

    f = lambda a, b: grouped_matmul(a, b, jnp.asarray(sizes), impl=impl, interpret=interpret)
    np.testing.assert_allclose(f(lhs, rhs), loop(lhs, rhs), rtol=1e-5, atol=1e-5)
    assert not np.asarray(f(lhs, rhs))[sum(sizes):].any()
    got = jax.grad(lambda a, b: (f(a, b) * w).sum(), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(lambda a, b: (loop(a, b) * w).sum(), argnums=(0, 1))(lhs, rhs)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[1])[1].any()  # the empty group's matrices get no gradient


@pytest.mark.parametrize("impl,interpret", [("ragged_dot", False), ("pallas", True)])
def test_the_gradients_own_products_match_a_loop_over_experts(impl, interpret):
    """What the expert layer's backward pass calls directly: the product
    against the transposed matrices, and the matrices' gradient added onto
    what an earlier round left (an empty group keeps what it had)."""
    sizes = [41, 0, 9, 6]
    m, k, n = 64, 16, 24
    keys = jax.random.split(jax.random.key(1), 4)
    lhs, g = jax.random.normal(keys[0], (m, k)), jax.random.normal(keys[1], (m, n))
    rhs, onto = jax.random.normal(keys[2], (4, k, n)), jax.random.normal(keys[3], (4, k, n))
    back, outer, start = jnp.zeros((m, k)), [], 0
    for e, size in enumerate(sizes):
        rows = slice(start, start + size)
        back = back.at[rows].set(g[rows] @ rhs[e].T)
        outer.append(onto[e] + lhs[rows].T @ g[rows])
        start += size
    kw = dict(impl=impl, interpret=interpret)
    got = grouped_matmul(g, rhs, jnp.asarray(sizes), transpose_rhs=True, **kw)
    np.testing.assert_allclose(got, back, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got)[sum(sizes):].any()
    got = grouped_outer(lhs, g, jnp.asarray(sizes), onto, **kw)
    np.testing.assert_allclose(got, jnp.stack(outer), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[1], onto[1])


def test_token_flops_count_matches_the_issue_s_reckoning():
    """1.13 GF a token forward at 8192 tokens on the real cut (ISSUE 27)."""
    driver = harness.load_module("drivers", "lm_steps")
    config = harness.load_cell(CELL)["config"]
    bench, program = driver.flops_pair(config)
    assert bench == pytest.approx(program, rel=1e-12)
    assert bench / 3 == pytest.approx(1.1327e9, rel=1e-3)


def test_token_batches_are_seeded_and_stay_in_the_rows_held():
    from jumbo_mae_tpu_tpu.data.synthetic import token_batches

    a = token_batches(4, 18, vocab_rows=(64, 64), seed=3)
    b = token_batches(4, 18, vocab_rows=(64, 64), seed=3)
    first = next(a)
    assert first["tokens"].shape == (4, 18) and first["tokens"].dtype == np.int32
    np.testing.assert_array_equal(first["tokens"], next(b)["tokens"])
    assert first["tokens"].min() >= 64 and first["tokens"].max() < 128
    assert not np.array_equal(first["tokens"], next(a)["tokens"])
    accum = next(token_batches(4, 18, vocab_rows=(0, 8), grad_accum=2))
    assert accum["tokens"].shape == (2, 2, 18) and accum["valid"].shape == (2, 2)
