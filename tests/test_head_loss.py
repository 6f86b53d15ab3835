"""``ops/head_loss.py`` — the head's loss a tile of tokens at a time with its
gradients formed beside the logits — against the plain form it replaced in
``models/lm.py`` (final norm, one product for every token, ``logsumexp −
take_along_axis``, autodiff for the rest)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.reference import lm_params
from benchmarks.reference import params as ref_params
from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig, MlaMoeLM
from jumbo_mae_tpu_tpu.ops.head_loss import TILE_ALIGN, TILE_BYTES, head_loss, head_tile

ROWS, SEQ, DIM, VOCAB, EPS = 3, 8, 16, 40, 1e-6  # 24 tokens: 5, 7 and 16 do not divide them


def _norm(h, scale, dtype):
    x = h.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS)
    return (x * scale).astype(dtype)


def plain(h, scale, kernel, targets, weights, dtype=jnp.float32):
    """``(Σ weights · nll, per-sequence mean nll)`` as ``MlaMoeLM`` had it."""
    logits = jnp.einsum("bsd,dv->bsv", _norm(h, scale, dtype),
                        kernel.astype(dtype)).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (weights * (lse - hit)).sum(), (lse - hit).mean(axis=-1)


def tiled(h, scale, kernel, targets, weights, dtype=jnp.float32, tile=None):
    rows, seq, dim = h.shape
    total, nll = head_loss(_norm(h, scale, dtype).reshape(rows * seq, dim), kernel.astype(dtype),
                           targets.reshape(-1), weights.reshape(-1), tile=tile)
    return total, nll.reshape(rows, seq).mean(axis=-1)


@functools.cache
def _inputs(seed: int = 0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 5)
    # values a bfloat16 holds, so that both dtypes read the same inputs
    held = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    h = held(jax.random.normal(keys[0], (ROWS, SEQ, DIM)))
    scale = held(1.0 + 0.1 * jax.random.normal(keys[1], (DIM,)))
    kernel = held(0.5 * jax.random.normal(keys[2], (DIM, VOCAB)))
    targets = jax.random.randint(keys[3], (ROWS, SEQ), 0, VOCAB)
    weights = jax.random.uniform(keys[4], (ROWS, 1), minval=0.2) * jnp.ones((ROWS, SEQ))
    return h, scale, kernel, targets, weights / (ROWS * SEQ)


def _value_and_grads(form, *args, cotangent=1.0, **kw):
    h, scale, kernel, targets, weights = args

    def scalar(h, scale, kernel):
        total, per_seq = form(h, scale, kernel, targets, weights, **kw)
        return cotangent * total, per_seq

    (total, per_seq), grads = jax.value_and_grad(scalar, argnums=(0, 1, 2), has_aux=True)(
        h, scale, kernel)
    return total, per_seq, grads


def _close(got, want, tol):
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * max(float(np.abs(want).max()), 1e-30))


# a tile of one, tiles that do not divide the 24 tokens (a short last tile
# becomes padding of weight 0), one that does, a tile of everything, one
# larger than that, and the tile the shapes give
@pytest.mark.parametrize("tile", [1, 5, 7, 8, 16, 24, 64, None])
def test_float32_loss_and_gradients_match_the_plain_form(tile):
    args = _inputs()
    total, per_seq, grads = _value_and_grads(tiled, *args, tile=tile)
    want_total, want_per_seq, want_grads = _value_and_grads(plain, *args)
    _close(total, want_total, 1e-6)
    _close(per_seq, want_per_seq, 1e-6)
    for g, w in zip(grads, want_grads):  # hidden state, the norm's scale, the kernel
        assert g.shape == w.shape and g.dtype == w.dtype
        _close(g, w, 1e-6)


@pytest.mark.parametrize("tile", [1, 5, 8, 24])
def test_bfloat16_stays_within_the_plain_forms_own_rounding(tile):
    """Both forms in bfloat16 against the plain form in float32 on the same
    inputs: the tiled form's error is no more than the plain form's (its
    logits are not rounded to bfloat16 on their way to the log-sum-exp)."""
    args = _inputs()
    truth = _value_and_grads(plain, *args)
    rounded = _value_and_grads(plain, *args, dtype=jnp.bfloat16)
    got = _value_and_grads(tiled, *args, dtype=jnp.bfloat16, tile=tile)
    flat = lambda r: [r[0], r[1], *r[2]]
    for g, p, t in zip(flat(got), flat(rounded), flat(truth)):
        assert g.dtype == p.dtype
        scale = float(np.abs(t).max())
        err, plain_err = float(np.abs(g - t).max()), float(np.abs(p - t).max())
        assert err <= 1.5 * plain_err + 2.0**-9 * scale, (err, plain_err, scale)


@pytest.mark.parametrize("cotangent", [1.0, -2.5, 0.0])
@pytest.mark.parametrize("tile", [5, 24])
def test_any_scalar_cotangent_and_unequal_sequence_weights(tile, cotangent):
    """The weights differ a sequence (so the scalar is not the mean), and
    what multiplies the scalar downstream is not 1."""
    args = _inputs(seed=1)
    got = _value_and_grads(tiled, *args, tile=tile, cotangent=cotangent)
    want = _value_and_grads(plain, *args, cotangent=cotangent)
    _close(got[0], want[0], 1e-6)
    for g, w in zip(got[2], want[2]):
        _close(g, w, 1e-6) if cotangent else np.testing.assert_array_equal(g, 0.0)


def test_the_weights_gradient_is_each_tokens_loss_and_the_values_carry_none():
    h, scale, kernel, targets, weights = _inputs()
    x = _norm(h, scale, jnp.float32).reshape(-1, DIM)
    scalar = lambda w: head_loss(x, kernel, targets.reshape(-1), w, tile=5)[0]
    nll = head_loss(x, kernel, targets.reshape(-1), weights.reshape(-1), tile=5)[1]
    _close(jax.grad(scalar)(weights.reshape(-1)), nll, 1e-6)
    values = lambda x: head_loss(x, kernel, targets.reshape(-1), weights.reshape(-1))[1].sum()
    np.testing.assert_array_equal(jax.grad(values)(x), 0.0)


@pytest.mark.parametrize("tile", [7, None])
def test_two_heads_share_one_kernel_and_the_second_weighs_less(tile):
    """A trunk head and an MTP head at 0.3 of its weight over one kernel and
    one norm: the kernel's (and the scale's) gradient is the sum."""
    h, scale, kernel, targets, weights = _inputs(seed=2)
    h2 = jnp.roll(h, 1, axis=1) * 0.7
    targets2 = jnp.roll(targets, -1, axis=1)

    def both(form, **kw):
        def scalar(h, h2, scale, kernel):
            return (form(h, scale, kernel, targets, weights, **kw)[0]
                    + form(h2, scale, kernel, targets2, 0.3 * weights, **kw)[0])
        return jax.value_and_grad(scalar, argnums=(0, 1, 2, 3))(h, h2, scale, kernel)

    (total, grads), (want, want_grads) = both(tiled, tile=tile), both(plain)
    _close(total, want, 1e-6)
    for g, w in zip(grads, want_grads):
        _close(g, w, 1e-6)


def _products(jaxpr) -> int:
    return str(jaxpr).count("dot_general")


def test_no_gradient_asked_forms_no_gradient_products():
    """The primal path is the logits' product alone; asking for the gradient
    adds dX's and dW's in the same pass over the tiles and none after it."""
    h, scale, kernel, targets, weights = _inputs()
    x = _norm(h, scale, jnp.float32).reshape(-1, DIM)
    scalar = lambda x, k: head_loss(x, k, targets.reshape(-1), weights.reshape(-1), tile=8)[0]
    assert _products(jax.make_jaxpr(scalar)(x, kernel)) == 1
    assert _products(jax.make_jaxpr(jax.grad(scalar, argnums=(0, 1)))(x, kernel)) == 3


def test_the_kernels_gradient_takes_the_kernels_dtype():
    h, scale, kernel, targets, weights = _inputs()
    x = _norm(h, scale, jnp.bfloat16).reshape(-1, DIM)
    scalar = lambda x, k: head_loss(x, k, targets.reshape(-1), weights.reshape(-1), tile=8)[0]
    for dtype in (jnp.bfloat16, jnp.float32):
        dx, dw = jax.grad(scalar, argnums=(0, 1))(x, kernel.astype(dtype))
        assert dx.dtype == jnp.bfloat16 and dw.dtype == dtype and dw.shape == kernel.shape


# the five language recipes' heads at 16 384 tokens a step (JoyAI, Ling,
# Laguna, Solar, SmallThinker), then what does not divide
@pytest.mark.parametrize("tokens,rows,want", [
    (16384, 16160, 4096), (16384, 19648, 2048), (16384, 12544, 4096), (16384, 24576, 2048),
    (16384, 37984, 1024),
    (24, 40, 24),          # everything in one tile
    (20000, 37984, 1280),  # no whole divisor in reach: 16 tiles, 480 rows of padding
    (2 * 1000, 300000, 128),  # one TILE_ALIGN, the least a tile is
])
def test_the_tile_follows_the_shapes(tokens, rows, want):
    tile = head_tile(tokens, rows)
    assert tile == want
    assert tile == tokens or (tile % TILE_ALIGN == 0 and 4 * tile * rows <= TILE_BYTES)
    padding = -tokens % tile
    assert padding < tile and 40 * padding <= tokens + 40 * TILE_ALIGN  # a few percent at most


@functools.cache
def _model(seed: int = 11):
    driver = harness.load_module("drivers", "lm_steps")
    config = driver.tiny(harness.load_cell("joyai_flash_pretrain_2x8k"))["config"]
    cfg = MlaMoeConfig(**driver.lm_fields(config) | {"dtype": "float32"})
    params = ref_params.make_params(seed, lm_params.lm_shapes(config))
    biases = lm_params.make_biases(seed, config)
    first, rows = config["vocab_rows"]
    tokens = np.random.default_rng(seed).integers(first, first + rows, (3, 22), dtype=np.int32)
    return cfg, {"params": params, "batch_stats": biases}, jnp.asarray(tokens)


def test_the_models_logits_are_the_plain_product_to_the_bit():
    """``MlaMoeLM.logits()`` keeps the plain form: final norm, one einsum."""
    cfg, variables, tokens = _model()
    model = MlaMoeLM(cfg)
    got = jax.jit(lambda v: model.apply(v, tokens, method="logits"))(variables)

    @jax.jit
    def want(v):
        hidden = model.apply(v, tokens, True, method="_hidden")[0]
        scale, kernel = v["params"]["ln"]["scale"], v["params"]["head"]["kernel"]
        return [jnp.einsum("bsd,dv->bsv", _norm(h, scale, cfg.compute_dtype),
                           kernel.astype(cfg.compute_dtype)).astype(jnp.float32) for h in hidden]

    assert cfg.rms_eps == EPS and len(got) == 2
    for g, w in zip(got, want(variables)):
        np.testing.assert_array_equal(g, w)


def test_the_models_losses_are_the_plain_forms_from_its_own_logits():
    """``loss`` carries the gradient and is the mean of ``loss_per_sample``;
    the per-sequence values are the plain form's over ``logits()``."""
    cfg, variables, tokens = _model()
    model = MlaMoeLM(cfg)
    out = jax.jit(lambda v: model.apply(v, tokens))(variables)
    logits = jax.jit(lambda v: model.apply(v, tokens, method="logits"))(variables)
    seq, ids = tokens.shape[1] - 2, tokens - cfg.rows[0]
    per_head = []
    for i, lg in enumerate(logits):
        hit = jnp.take_along_axis(lg, ids[:, 1 + i : seq + 1 + i, None], axis=-1)[..., 0]
        per_head.append((jax.nn.logsumexp(lg, axis=-1) - hit).mean(axis=-1))
    per_sample = per_head[0] + cfg.mtp_loss_weight * per_head[1]
    _close(out["loss_per_sample"], per_sample, 1e-6)
    _close(out["loss_trunk"], per_head[0].mean(), 1e-6)
    _close(out["loss_mtp"], per_head[1].mean(), 1e-6)
    _close(out["loss"], per_sample.mean(), 1e-6)
