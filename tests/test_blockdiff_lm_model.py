"""The seventh family of ``models/lm.py`` (``SDAR-30B-A3B-Chat``: a
grouped-query trunk with q/k norms and softmax-routed experts, trained as a
block-diffusion model: a clean and a noisy copy of every sequence through one
trunk under a block-causal / block-diagonal mask, a 1/t-weighted loss on the
masked tokens) against the benchmark's plain reference
(``benchmarks/reference/blockdiff_lm_model.py``) on seeded weights, float32,
at a two-layer cut: logits of both copies, loss, every gradient leaf and three
AdamW steps. The core's kernels against the einsum form and the literal
(2 L, 2 L) mask in the Pallas interpreter; the tables and the strips' plan
against every entry of the mask; a clean token's output whatever the noisy
copy holds; block length 1 against the causal model; the noise's marginals;
the eight expert shares against the uncut layer; the FLOP counts."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_blockdiff_lm as flops
from benchmarks import harness
from benchmarks.reference import blockdiff_lm_model as ref_model
from benchmarks.reference import blockdiff_lm_params as ref_shapes
from benchmarks.reference import optim as ref_optim
from benchmarks.reference import params as ref_params
from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig, MlaMoeLM, SparseExperts
from jumbo_mae_tpu_tpu.obs.mfu import lm_flops_per_token
from jumbo_mae_tpu_tpu.ops import attention as ops_attention
from jumbo_mae_tpu_tpu.ops.masking import block_noise
from jumbo_mae_tpu_tpu.ops.pallas import attention as pallas_attention

DRIVER = harness.load_module("drivers", "blockdiff_lm_steps")
CELL = "sdar_blockdiff_pretrain_2x8k"
KEY = 7  # the noise key's seed in the comparisons


def _config() -> dict:
    """The configuration as its cell runs it."""
    return harness.load_cell(CELL)["config"]


def _peaked(params: dict) -> dict:
    """The seeded query, key, router and expert matrices scaled up, for
    program and reference alike: 32 inputs of 0.02 leave every softmax flat
    and every expert's output a thousandth of the stream, so that a fault in
    them would move nothing a float32 comparison sees; the real cut's 2048
    inputs spread them as this does."""
    params = jax.tree_util.tree_map(lambda x: x, params)  # a copy of the tree
    for name in [n for n in params if n.startswith("block_")]:
        blk = params[name]
        for leaf in [blk["attn"]["q"], blk["attn"]["k"],
                     *(blk["moe"][k] for k in ("router", "gate", "up", "down"))]:
            leaf["kernel"] = leaf["kernel"] * 8.0
    return params


@functools.cache
def _setup(seed: int = 11):
    config = DRIVER.tiny({"config": _config(), "traffic": {}})["config"]
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config) | {"dtype": "float32"})
    params = _peaked(jax.jit(lambda s: ref_shapes.make_params(s, config))(seed))
    tokens = next(DRIVER.token_batches(seed, config, 3, 24, 1))["tokens"]
    return config, cfg, params, jnp.asarray(tokens)


def _flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in leaves}


def _program(cfg, tokens, key):
    def loss(p):
        out = MlaMoeLM(cfg).apply({"params": p}, tokens, noise_key=key)
        return out["loss"], out

    return loss


def test_the_tiny_cut_is_the_familys_and_the_other_families_are_as_they_were():
    config, cfg, params, tokens = _setup()
    assert cfg.kinds == ("full_attention",) * 2 and cfg.first_k_dense == 0
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim) == (8, 1, 16)  # a group of 8
    assert (cfg.diffusion_block, cfg.qk_norm) == (4, True)
    assert (cfg.router_scoring, cfg.router_input, cfg.shared_hidden) == ("softmax_topk",
                                                                        "ffn_norm", 0)
    assert cfg.mask_id == 127 == config["mask_token_id"] and cfg.token_row(24) == 24
    assert tokens.shape == (3, 24) and int(tokens.max()) < 127  # the generator never draws it
    # one clamped block of 128 a copy: three pairs, each one strip whole under its mask
    assert cfg.attn_pairs(24) == {"block_diffusion": (3 * 128 * 128, 24 * 24 + 24 * 4)}
    # the causal families: no objective of their own, a shifted row, their own pairs
    assert (MlaMoeConfig().diffusion_block, MlaMoeConfig().token_row(24)) == (0, 26)
    assert "block_diffusion" not in cfg.replace(diffusion_block=0).attn_pairs(24)
    variables = jax.eval_shape(lambda: MlaMoeLM(cfg).init(jax.random.key(0), tokens))
    assert set(variables) == {"params"}  # no router bias, and the objective has no parameter
    shapes = jax.tree_util.tree_map(lambda s: tuple(s.shape), variables["params"])
    assert ref_params.flat_shapes(shapes) == ref_params.flat_shapes(ref_shapes.shapes(config))
    assert set(shapes["block_1"]["attn"]) == {"q", "k", "v", "q_norm", "k_norm", "out"}
    for bad, why in [({"diffusion_block": 3}, "power of two"),
                     ({"layer_types": ("full_attention", "sliding_attention"),
                       "rope_parameters": {"full_attention": None, "sliding_attention": None}},
                      "blocks are full_attention"),
                     ({"layer_types": None, "mtp_layers": 0}, "blocks are full_attention")]:
        with pytest.raises(ValueError, match=why):
            cfg.replace(**bad)


def test_logits_of_both_copies_loss_and_every_gradient_leaf_match_the_reference():
    config, cfg, params, tokens = _setup()
    key = jax.random.key(KEY)
    (loss, out), grads = jax.jit(jax.value_and_grad(_program(cfg, tokens, key), has_aux=True))(
        params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref_model.batch_loss(p, tokens, key, config)))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert float(out["moe_dropped"]) == 0.0
    t, masked = ref_model.draw_noise(key, 3, 24, config)
    np.testing.assert_allclose(out["bd_masked_share"], np.asarray(masked).mean(), rtol=1e-6)
    # values only: each sequence's own weighted sum, whose mean is the loss
    np.testing.assert_allclose(out["loss_per_sample"].mean(), want, rtol=1e-5)
    got, ref = _flat(grads), _flat(want_grads)
    assert got.keys() == ref.keys() and len(got) == 27
    for name, g in got.items():
        assert np.abs(ref[name]).max() > 0, name  # every leaf takes part, the q/k norms too
        np.testing.assert_allclose(g, ref[name], rtol=2e-3,
                                   atol=2e-4 * np.abs(ref[name]).max(), err_msg=name)
    (logits,) = MlaMoeLM(cfg).apply({"params": params}, tokens, noise_key=key,
                                    method=MlaMoeLM.logits)
    assert logits.shape == (3, 48, 64)  # the clean copy's 24 rows, then the noisy one's
    for row in range(3):
        np.testing.assert_allclose(
            logits[row], ref_model.sequence_logits(params, tokens[row], masked[row], config),
            rtol=1e-4, atol=1e-5)
    # the loss is the masked positions' alone, weighted 1 / t, with no shift
    noisy = jax.nn.log_softmax(logits[:, 24:], axis=-1)
    nll = -jnp.take_along_axis(noisy, (tokens - 64)[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(loss, (masked * nll / t).sum() / (3 * 24), rtol=1e-5)


def test_three_adamw_steps_follow_the_reference():
    """Loss by loss over three steps of the reference's AdamW, each side on
    its own gradients under the same three keys, and the parameters' change
    at the end leaf by leaf."""
    config, cfg, params, tokens = _setup()
    optim = config["optim"] | {"warmup_steps": 2, "init_lr": 1e-3, "peak_lr": 3e-3}
    program = lambda p, t, k: MlaMoeLM(cfg).apply({"params": p}, t, noise_key=k)["loss"]
    reference = lambda p, t, k: ref_model.batch_loss(p, t, k, config)
    copy = lambda tree: jax.tree_util.tree_map(jnp.array, tree)
    sides = {}
    for name, fn in (("program", program), ("reference", reference)):
        step_fn = jax.jit(jax.value_and_grad(fn))
        p, state, losses = copy(params), None, []
        for step in range(3):
            loss, g = step_fn(p, jnp.roll(tokens, step, axis=0), jax.random.key(KEY + step))
            losses.append(float(loss))
            p, state = ref_optim.adamw_step(p, g, state or ref_optim.adamw_init(p), optim)
        sides[name] = (losses, _flat(jax.tree_util.tree_map(jnp.subtract, p, params)))
    np.testing.assert_allclose(sides["program"][0], sides["reference"][0], rtol=2e-5)
    for name, want in sides["reference"][1].items():
        norm = np.linalg.norm(want)
        assert norm > 0 and np.linalg.norm(sides["program"][1][name] - want) < 0.02 * norm, name


def test_the_training_draw_is_the_noise_streams_and_evaluation_draws_from_a_fixed_key():
    config, cfg, params, tokens = _setup()
    model = MlaMoeLM(cfg)
    train = lambda key: model.apply({"params": params}, tokens, deterministic=False,
                                    rngs={"noise": key})
    a, b, c = train(jax.random.key(1)), train(jax.random.key(1)), train(jax.random.key(2))
    assert float(a["loss"]) == float(b["loss"]) != float(c["loss"])
    # the driver's probe derives the key the model's root module draws from
    probe = DRIVER.noise_key.__wrapped__  # un-jitted: the folds, then flax's make_rng
    base = jax.random.key(np.uint32(5))
    for fold in (0, 3, 0, 0, 1):
        base = jax.random.fold_in(base, fold)
    want = model.apply({"params": params}, tokens, deterministic=False, rngs={"noise": base})
    given = model.apply({"params": params}, tokens, noise_key=probe(np.uint32(5), 3))
    assert float(want["loss"]) == float(given["loss"])
    # evaluation: no stream asked for, the same noise every time
    quiet = [model.apply({"params": params}, tokens) for _ in range(2)]
    assert float(quiet[0]["loss"]) == float(quiet[1]["loss"]) == float(
        model.apply({"params": params}, tokens, noise_key=jax.random.key(0))["loss"])


def test_the_control_can_round_in_one_block_alone():
    config, _, params, tokens = _setup()
    key = jax.random.key(KEY)
    loss = lambda r: float(jax.jit(
        lambda p: ref_model.batch_loss(p, tokens, key, config, r))(params))
    assert len({loss(r) for r in ("float32", "fp8@0", "fp8@1", "fp8")}) == 4
    assert DRIVER.ONE_BLOCK_CONTROL == "fp8@0" and DRIVER.CONTROL == "fp8"


# ----------------------------------------------------------- the noise

def test_the_noise_masks_a_block_at_its_own_level():
    """One level a (sequence, block), uniform over [eps, 1); a token is masked
    with its block's probability, independently; the same key, the same
    draw, and the reference's own lines draw the same."""
    key = jax.random.key(3)
    t, masked = block_noise(key, 64, 4096, 4, 1e-3)
    assert t.shape == (64, 1024) and masked.shape == (64, 4096) and masked.dtype == bool
    t, masked = np.asarray(t), np.asarray(masked)
    assert 1e-3 <= t.min() < 0.01 and 0.99 < t.max() < 1.0
    assert abs(t.mean() - 0.5) < 5e-3 and abs(masked.mean() - 0.5) < 5e-3
    per_block = masked.reshape(64, 1024, 4).mean(axis=-1)
    # the masked share follows the level: bins of t against the share masked in them
    for lo in (0.0, 0.25, 0.5, 0.75):
        at = (t >= lo) & (t < lo + 0.25)
        assert abs(per_block[at].mean() - (lo + 0.125)) < 0.01, lo
    # within a block the tokens fall independently: all four masked with t^4
    high = t > 0.9
    assert abs((per_block[high] == 1).mean() - (t[high] ** 4).mean()) < 0.02
    again = block_noise(key, 64, 4096, 4, 1e-3)
    assert np.array_equal(again[1], masked)
    config = _config()
    ref_t, ref_masked = ref_model.draw_noise(key, 64, 4096, config)
    assert np.array_equal(ref_masked, masked) and np.array_equal(ref_t[:, ::4], t)


def test_the_generators_never_draw_the_mask_id():
    from jumbo_mae_tpu_tpu.cli import train as cli_train
    from jumbo_mae_tpu_tpu.config import load_config

    config = _config()
    first, rows = config["vocab_rows"]
    pool = next(DRIVER.token_batches(3_000_000_123, config, 2, 8192, 8))["tokens"]
    assert pool.shape == (2, 8192) and pool.min() >= first
    assert pool.max() == first + rows - 2 == config["mask_token_id"] - 1
    cfg = load_config(str(harness.ROOT / config["recipe"]),
                      ["data.seq_len=64", "model.lm.vocab_rows=[8, 5]"])
    batch = next(cli_train._synthetic(cfg, 4, 0, seed=0))["tokens"]
    assert batch.shape == (4, 64) and set(np.unique(batch)) == {8, 9, 10, 11}  # 12 is the mask id


# ------------------------------------------------------------- the core

def _literal_mask(seq: int, block: int) -> np.ndarray:
    """The (2 seq, 2 seq) mask from each position's copy and block, in loops."""
    keep = np.zeros((2 * seq, 2 * seq), bool)
    for i in range(2 * seq):
        for j in range(2 * seq):
            b_i, b_j = i % seq // block, j % seq // block
            if i < seq:
                keep[i, j] = j < seq and b_j <= b_i
            else:
                keep[i, j] = b_j < b_i if j < seq else b_j == b_i
    return keep


@pytest.fixture
def fresh_traces():
    """The kernels' kept traces cleared before and after a test that steers
    the rule a trace reads (``_sub_tile``)."""
    clear = lambda: (pallas_attention._causal_fwd.clear_cache(),
                     pallas_attention._causal_bwd.clear_cache())
    clear()
    yield
    clear()


@pytest.mark.parametrize("seq,block,heads,kv_heads,kernel_block,tile", [
    (32, 4, 2, 1, 16, None),  # two kernel blocks a copy, a masked pair whole under its mask
    (24, 4, 2, 1, 16, None),  # a row that is no multiple of the kernel's block
    (32, 4, 8, 1, 16, 8),  # a group of 8 query heads; masked pairs in strips
    (40, 4, 4, 2, 16, 4),  # sub-tiles as short as a diffusion block, a padded row
    (64, 16, 2, 1, 32, 16),  # blocks of 16
    (32, 1, 2, 1, 16, 8),  # blocks of one token: the clean copy is causal
])
def test_the_kernels_under_the_pattern_against_the_einsum_form_and_the_literal_mask(
        seq, block, heads, kv_heads, kernel_block, tile, monkeypatch, fresh_traces):
    if tile:
        monkeypatch.setattr(pallas_attention, "_sub_tile", lambda _: tile)
    assert np.array_equal(ops_attention.block_diffusion_visible(2 * seq, block),
                          _literal_mask(seq, block))
    keys = jax.random.split(jax.random.key(seq + block), 4)
    d = 16
    q = jax.random.normal(keys[0], (2, heads, 2 * seq, d)) * d ** -0.5
    k = jax.random.normal(keys[1], (2, kv_heads, 2 * seq, d))
    v = jax.random.normal(keys[2], (2, kv_heads, 2 * seq, d))
    ct = jax.random.normal(keys[3], q.shape)

    def literal(q, k, v):
        kk, vv = (jnp.repeat(x, heads // kv_heads, axis=1) for x in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk, precision="highest")
        s = jnp.where(_literal_mask(seq, block), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vv, precision="highest")

    forms = {
        "literal": literal,
        "einsum": lambda q, k, v: ops_attention.xla_causal_attention(q, None, k, None, v, None,
                                                                     block),
        "kernels": lambda q, k, v: pallas_attention.pallas_causal_attention(
            q, None, k, None, v, kernel_block, True, None, block),
    }
    with jax.default_matmul_precision("highest"):
        results = {name: jax.vjp(fn, q, k, v) for name, fn in forms.items()}
        want, want_vjp = results.pop("literal")
        for name, (out, vjp) in results.items():
            np.testing.assert_allclose(out, want, atol=2e-6, err_msg=name)
            for got, ref, what in zip(vjp(ct), want_vjp(ct), "qkv"):
                np.testing.assert_allclose(got, ref, atol=1e-5, err_msg=f"{name} d{what}")


def test_the_rule_sends_a_block_diffusion_call_to_the_kernels_with_its_block(monkeypatch):
    """``causal_attention``'s one rule: on the TPU from 512 rows the kernels,
    told the diffusion block; elsewhere the einsum form under the same mask."""
    seen = {}
    q = jnp.zeros((1, 2, 1024, 16))
    monkeypatch.setattr(pallas_attention, "pallas_causal_attention",
                        lambda *a, **kw: seen.update(kw) or a[0])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ops_attention.causal_attention(q, None, q, None, q, diffusion=4)
    assert seen == {"window": None, "diffusion": 4}
    seen.clear()
    ops_attention.causal_attention(q, None, q, None, q)
    assert seen == {"window": None, "diffusion": None}  # one call for every pattern
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    seen.clear()
    ops_attention.causal_attention(q[:, :, :64], None, q[:, :, :64], None, q[:, :, :64],
                                   diffusion=4)
    assert not seen


def test_the_tables_walk_every_needed_pair_once_and_the_backward_walk_is_theirs():
    n = 4
    qi, kj = pallas_attention._two_copies(n)
    pairs = list(zip(qi.tolist(), kj.tolist()))
    assert len(pairs) == len(set(pairs)) == n * (n + 1) + n
    want = {(i, j) for i in range(n) for j in range(i + 1)}
    want |= {(n + i, j) for i in range(n) for j in range(i + 1)} | {(n + i, n + i) for i in range(n)}
    assert set(pairs) == want and all(j <= i for i, j in pairs)
    # a query block's key blocks rise, its first is 0 and its last the diagonal
    for i in range(2 * n):
        mine = [j for q, j in pairs if q == i]
        assert mine == sorted(mine) and mine[0] == 0 and mine[-1] == i
    # at the cell's shape: 80 pairs where a causal row of 16 384 walks 136
    assert len(pallas_attention._two_copies(8)[0]) == 80
    assert len(pallas_attention._lower_triangle(16)[0]) == 136
    steps = list(zip(*(col.tolist() for col in pallas_attention._backward_walk(
        2 * n, reach=None, group=2, span=2 * n, diffusion=4))))
    assert [(i, j) for i, j, member, _ in steps if member == 0] == pairs
    assert [(i, j) for i, j, member, _ in steps if member == 1] == pairs
    first, last = pallas_attention.ROW_FIRST, pallas_attention.ROW_LAST
    assert all(bool(at & first) == (j == 0) and bool(at & last) == (i == j)
               for i, j, _, at in steps)
    # the sequence-long accumulators of 16 384 rows at widths of 128 fit one span
    assert pallas_attention._causal_span(16, 1024, (128, 128), 2) == 16


@pytest.mark.parametrize("block,tile,unit", [(1024, 256, 4), (1024, 256, 64), (16, 4, 4),
                                             (16, 8, 2), (32, 32, 16), (16, 4, 1)])
def test_the_plan_of_strips_against_every_entry_of_the_three_masks(block, tile, unit):
    """Every visible entry lies in a sub-tile its strip holds; a sub-tile
    called clear is visible whole; a strip that is left out sees nothing."""
    at = np.arange(block) // unit
    apart = at[:, None] - at[None, :]
    cuts = pallas_attention._diffusion_cuts(block, unit)
    assert set(cuts) == {"clean", "strict", "own"}
    for kind, (lo, hi, u) in cuts.items():
        assert u == unit
        visible = (lo <= apart) & (apart < hi)
        want = {"clean": at[None, :] <= at[:, None], "strict": at[None, :] < at[:, None],
                "own": at[None, :] == at[:, None]}[kind]
        assert np.array_equal(visible, want)
        held = np.zeros_like(visible)
        for strip in pallas_attention._strips(block, tile, lo, hi, unit):
            assert strip.row_end - strip.row == tile and strip.col % tile == 0
            held[strip.row:strip.row_end, strip.col:strip.col_end] = True
            assert visible[strip.row:strip.row_end, strip.clear:strip.clear_end].all()
            assert strip.col <= strip.clear <= strip.clear_end <= strip.col_end
        assert not (visible & ~held).any(), kind
    if (block, tile, unit) == (1024, 256, 4):  # the cell's: 10, 10 and 4 of 16 sub-tiles
        area = lambda kind: sum((s.row_end - s.row) * (s.col_end - s.col)
                                for s in pallas_attention._strips(block, tile, *cuts[kind]))
        assert [area(kind) // 256 ** 2 for kind in ("clean", "strict", "own")] == [10, 10, 4]


def test_a_diffusion_block_longer_than_a_sub_tile_is_refused():
    with pytest.raises(ValueError, match="no divisor of the 256-wide sub-tiles"):
        pallas_attention._strips(1024, 256, 0, 2, 512)
    with pytest.raises(ValueError, match="two copies of whole diffusion blocks"):
        pallas_attention.causal_pairs(30, diffusion=4)
    with pytest.raises(ValueError, match="two copies of whole diffusion blocks"):
        pallas_attention._causal_band(64, 16, None, 4)  # no window under the pattern


def test_the_static_count_at_the_cells_shape():
    visited, needed = pallas_attention.causal_pairs(8192, diffusion=4)
    assert needed == 8192 * 8192 + 8192 * 4 == flops.needed_pairs(_config(), 8192)
    # 56 whole pairs, 16 staircases at 10 / 16, 8 block diagonals at 4 / 16
    assert visited == (56 * 16 + 16 * 10 + 8 * 4) * 256 * 256 == 68 * 1024 * 1024
    assert visited / needed == pytest.approx(1.062, abs=1e-3)  # 1.25 with every pair whole
    assert pallas_attention.causal_pairs(8192) == (33 * 1024 * 1024, 8192 * 8193 // 2)


def test_a_clean_tokens_output_is_the_same_to_the_bit_whatever_the_noisy_copy_holds():
    """A clean query never sees a noisy key, in the core or anywhere else: the
    clean copy's logits under two draws of the noise are equal bit for bit,
    and the noisy copy's are not."""
    config, cfg, params, tokens = _setup()
    logits = jax.jit(lambda key: MlaMoeLM(cfg).apply(
        {"params": params}, tokens, noise_key=key, method=MlaMoeLM.logits)[0])
    a, b = logits(jax.random.key(1)), logits(jax.random.key(2))
    assert np.array_equal(a[:, :24], b[:, :24])
    assert not np.allclose(a[:, 24:], b[:, 24:], atol=1e-3)
    # and a noisy token sees no clean token of its own block: moving the clean
    # ids of the last block moves no noisy logit of an earlier block, and of
    # the last block's own only through the noisy copy's unmasked ids
    moved = tokens.at[:, 20:].set((tokens[:, 20:] - 64 + 1) % 63 + 64)
    c = jax.jit(lambda t: MlaMoeLM(cfg).apply(
        {"params": params}, t, noise_key=jax.random.key(1), method=MlaMoeLM.logits)[0])(moved)
    assert np.array_equal(a[:, 24:44], c[:, 24:44]) and np.array_equal(a[:, :20], c[:, :20])
    assert not np.allclose(a[:, 20:24], c[:, 20:24], atol=1e-4)


def test_at_blocks_of_one_token_the_clean_copy_is_the_causal_model():
    """With ``B`` = 1 the clean-to-clean mask is the causal one and a clean
    query sees no noisy key: the clean copy's logits are the causal model's on
    the same ids and weights (the expert layer is token-wise, and routes a
    row alike whichever rows stand beside it)."""
    config, cfg, params, tokens = _setup()
    one = cfg.replace(diffusion_block=1)
    (both,) = MlaMoeLM(one).apply({"params": params}, tokens, noise_key=jax.random.key(4),
                                  method=MlaMoeLM.logits)
    causal = cfg.replace(diffusion_block=0)
    row = jnp.concatenate([tokens, tokens[:, :1]], axis=1)  # a causal row holds one id more
    (want,) = MlaMoeLM(causal).apply({"params": params}, row, method=MlaMoeLM.logits)
    np.testing.assert_allclose(both[:, :24], want, rtol=1e-5, atol=1e-6)


# ------------------------------------------------- the chip's share, the counts

def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    config, cfg, params, _ = _setup()
    whole = config | {"num_experts": 8, "experts_held": [0, 8]}
    p = _peaked({"block_0": jax.jit(lambda s: ref_shapes.make_params(s, whole))(5)[
        "block_0"]})["block_0"]["moe"]
    u = jax.random.normal(jax.random.key(6), (1, 48, cfg.dim), jnp.float32)
    ops = ref_model.Ops()
    want = jax.jit(lambda p: ref_model.expert_layer(ops, u[0], p, whole, first=0))(p)
    cut = lambda k, first: {"kernel": p[k]["kernel"][first:first + 1]}
    total = 0.0
    for first in range(8):  # eight ranks of one expert each
        share = {"router": p["router"], **{k: cut(k, first) for k in ("gate", "up", "down")}}
        out, stats = SparseExperts(cfg.replace(experts_held=(first, 1))).apply(
            {"params": share}, u)
        ref = ref_model.expert_layer(ops, u[0], share, whole, first=first)
        np.testing.assert_allclose(out[0], ref, rtol=1e-4, atol=1e-6)
        assert float(jnp.abs(out).max()) > 1e-3  # each share says something
        assert dict(zip(cfg.moe_counters, np.asarray(stats)))["dropped"] == 0
        total = total + out[0]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-6)
    # softmax over all, the largest, renormalised: the program's softmax over the chosen
    chosen, weights = ref_model.route(ops, u[0], p, whole)
    logits = u[0] @ p["router"]["kernel"]
    picked, same = jax.lax.top_k(logits, 3)
    assert np.array_equal(chosen, same)
    np.testing.assert_allclose(weights, jax.nn.softmax(picked, axis=1), rtol=1e-5)


def test_parameters_here_is_the_trees_count_and_the_recipe_is_the_file():
    """The program's own tree at the real cut, shapes only."""
    from jumbo_mae_tpu_tpu.cli.train import build_model
    from jumbo_mae_tpu_tpu.config import load_config

    config = _config()
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    variables = jax.eval_shape(lambda: MlaMoeLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 64), jnp.int32)))
    shapes = variables["params"]
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == config["parameters_here"] == 645_623_296
    assert count(shapes["block_0"]["attn"]) == 18_874_624  # q, k, v, out and two norms of 128
    assert count(shapes["block_0"]["moe"]) == 262_144 + 75_497_472  # the router, 16 experts
    assert count(shapes["embedding"]) == count(shapes["head"]) == 18_992 * 2048
    want = ref_params.flat_shapes(ref_shapes.shapes(config))
    assert ref_params.flat_shapes(jax.tree_util.tree_map(lambda s: tuple(s.shape), shapes)) == want
    recipe = build_model(load_config(str(harness.ROOT / config["recipe"])))[1]
    assert recipe == cfg  # the recipe states the sizes the benchmark's file translates to
    assert cfg.layers_by_kind == {"full_attention": 6}
    assert cfg.attn_heads() == {"full_attention": (32, 32)}
    assert (cfg.held, cfg.rows, cfg.mask_id) == ((0, 16), (0, 18992), 18991)


def test_token_flops_count_the_trunk_twice_the_head_once_and_the_pattern_s_pairs():
    config = _config()
    cfg = MlaMoeConfig(**DRIVER.lm_fields(config))
    for seq in (8192, 4096, 1000):
        assert lm_flops_per_token(cfg, seq) == pytest.approx(flops.token_step(config, seq),
                                                             rel=1e-12)
    causal = cfg.replace(diffusion_block=0)
    head = 2 * 2048 * 18992
    # a clean token: both copies' token-wise products, one head, seq + B keys for seq / 2
    twice = 2 * (lm_flops_per_token(causal, 8192, training=False) - head
                 - 6 * 2 * 4096.5 * 32 * 256)
    assert lm_flops_per_token(cfg, 8192, training=False) == pytest.approx(
        twice + head + 6 * 2 * (8192 + 4) * 32 * 256, rel=1e-12)
    step = 16384 * flops.token_step(config, 8192)  # 2 x 8192 clean tokens
    assert step == pytest.approx(71.57e12, rel=1e-3)
    core, moved = flops.core_step(config, 2, 8192)
    assert core == 2 * 6 * 6 * 2 * 128 * 32 * (8192 * 8192 + 8192 * 4) and moved > 0
    assert core / step == pytest.approx(0.553, abs=1e-3)  # the core is most of the count
