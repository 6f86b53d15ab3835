"""The shared jumbo MLP's kernel gradients, formed once a step
(``ops/shared_grad.py``, ``models/layers.JumboMlp``): against the per-call
form (the same model with no slots handed to its blocks, which is what the
pipeline runtime and every forward-only program run), against a float32
reference, through ``make_train_step``, and in the compiled text.
"""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jumbo_mae_tpu_tpu.models import DecoderConfig, MAEPretrainModel, layers, preset
from jumbo_mae_tpu_tpu.models.vit import JumboViT
from jumbo_mae_tpu_tpu.ops import shared_grad
from jumbo_mae_tpu_tpu.parallel import MeshConfig, create_mesh
from jumbo_mae_tpu_tpu.train import (
    OptimConfig,
    create_sharded_state,
    make_optimizer,
    make_train_step,
)

LAYERS, ROWS = 4, 4
SHARED = ("jumbo_mlp", "fc1", "kernel"), ("jumbo_mlp", "fc2", "kernel")


def tiny(dtype="float32", **kw):
    """3 CLS tokens of width 64 (a 192 -> 768 -> 192 shared MLP), 4 layers,
    MAE mode."""
    return preset("vit_t16", image_size=32, patch_size=8, mask_ratio=0.75,
                  labels=None, dtype=dtype, layers=LAYERS, **kw)


@contextlib.contextmanager
def per_call_form(on: bool = True):
    """Hand the blocks no slots: every call of the shared MLP differentiates
    as a plain dense does."""
    opened = layers.JumboMlp.open_slots
    if on:
        layers.JumboMlp.open_slots = lambda self, n, rows: (None,) * n
    try:
        yield
    finally:
        layers.JumboMlp.open_slots = opened


IMAGES = jnp.asarray(np.random.RandomState(0).randn(ROWS, 32, 32, 3), jnp.float32)


@functools.cache
def seeded_params():
    """One set of float32 parameters for every case: normal draws of 0.02
    on the model's own tree (LayerNorm scales about 1), so that no gradient
    is the zero an init's zero CLS tokens and biases would make it."""
    init = lambda: JumboViT(tiny()).init(
        {"params": jax.random.key(0), "noise": jax.random.key(1)}, IMAGES)["params"]
    rng = np.random.RandomState(7)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.asarray(
            (path[-1].key == "scale") + 0.02 * rng.standard_normal(a.shape), a.dtype),
        jax.eval_shape(init))


# (compute dtype, the encoder's options): rematerialisation off and on, its
# policy none and dots, dropout and droppath on with a fixed key
CASES = {
    "float32_grad_ckpt_dropout_droppath": (
        "float32", {"grad_ckpt": True, "dropout": 0.1, "droppath": 0.1}),
    "float32_grad_ckpt_dots": ("float32", {"grad_ckpt": True, "remat_policy": "dots"}),
    "bfloat16_plain": ("bfloat16", {}),
}


@functools.cache
def grad_program(case: str, per_call: bool):
    """The compiled gradient of a loss on the MAE-mode encoder's tokens, in
    the form with slots or (``per_call``) without."""
    dtype, options = CASES[case]
    model = JumboViT(tiny(dtype, **options))

    def loss(p):
        tokens, _, _ = model.apply(
            {"params": p}, IMAGES, False,
            rngs={"noise": jax.random.key(2), "dropout": jax.random.key(3)})
        return (tokens.astype(jnp.float32) ** 2).mean()

    with per_call_form(per_call):
        return jax.jit(jax.grad(loss)).lower(seeded_params()).compile()


def split(grads):
    """(the two shared kernels' gradients, every other leaf) by path."""
    flat = {tuple(k.key for k in path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(grads)}
    shared = {p: g for p, g in flat.items() if p[-3:] in SHARED}
    assert len(shared) == 2
    return shared, {p: g for p, g in flat.items() if p not in shared}


@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_are_the_per_call_forms(case):
    """Every leaf but the two shared kernels: equal to the bit. The two: in
    float32 to 1e-5 of their largest entry; in bfloat16 within bf16 rounding
    of the per-call form, and no further from float32 compute's gradient
    (rematerialisation changes no value) than the per-call form is: one
    float32 accumulation over all layers' rows where bf16-rounded partials
    were summed."""
    params = seeded_params()
    shared, rest = split(grad_program(case, False)(params))
    shared_pc, rest_pc = split(grad_program(case, True)(params))
    assert rest.keys() == rest_pc.keys()
    for path in rest:
        np.testing.assert_array_equal(rest[path], rest_pc[path], err_msg=str(path))
    bf16 = CASES[case][0] == "bfloat16"
    for path in shared:
        top = np.abs(shared_pc[path]).max()
        assert top > 1e-5, "a gradient of zeros proves nothing"
        gap = np.abs(shared[path] - shared_pc[path]).max() / top
        assert gap < (2**-7 if bf16 else 1e-5), (path, gap)
    if bf16:
        exact, _ = split(grad_program("float32_grad_ckpt_dots", False)(params))
        for path in shared:
            off = np.linalg.norm(shared[path] - exact[path])
            off_pc = np.linalg.norm(shared_pc[path] - exact[path])
            assert off <= off_pc * 1.02, (path, off, off_pc)


@functools.cache
def trainer(grad_accum: int):
    """(mesh, state, its sharding, a batch) for a two-layer encoder under a
    one-layer decoder and plain SGD, whose step is the gradient times the
    learning rate (large, so that the parameters' change is read to seven
    digits off parameters of 0.02)."""
    module = MAEPretrainModel(tiny(grad_ckpt=grad_accum > 1).replace(layers=2),
                              DecoderConfig(layers=1, dim=16, heads=2, dtype="float32"))
    images = np.random.RandomState(1).randint(0, 256, (2 * ROWS, 32, 32, 3)).astype(np.uint8)
    batch = {"images": jnp.asarray(images.reshape(grad_accum, -1, 32, 32, 3)
                                   if grad_accum > 1 else images)}
    example = jax.tree_util.tree_map(lambda x: x[0], batch) if grad_accum > 1 else batch
    mesh = create_mesh(MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1])
    tx = make_optimizer(OptimConfig(name="sgd", learning_rate=1e4, lr_scaling="none",
                                    warmup_steps=0, training_steps=4, weight_decay=0.0), 8)
    state, sharding = create_sharded_state(module, tx, example, mesh, mode="pretrain")
    return mesh, state, sharding, batch


def change_over_one_step(grad_accum: int):
    mesh, state, sharding, batch = trainer(grad_accum)
    before = jax.tree_util.tree_map(np.asarray, state.params)
    step = make_train_step(mesh, sharding, mode="pretrain", grad_accum=grad_accum)
    state, _ = step(jax.tree_util.tree_map(jnp.copy, state), batch)  # the step donates
    return jax.tree_util.tree_map(lambda new, old: np.asarray(new) - old, state.params, before)


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_the_trainers_step_moves_the_parameters_as_the_per_call_form_does(grad_accum):
    """Through ``make_train_step``: with ``grad_accum`` the slots are opened
    inside the micro-step, one product a kernel a micro-step."""
    shared, rest = split(change_over_one_step(grad_accum))
    with per_call_form():
        shared_pc, rest_pc = split(change_over_one_step(grad_accum))
    for path in rest:
        np.testing.assert_array_equal(rest[path], rest_pc[path], err_msg=str(path))
    for path in shared:
        top = np.abs(shared_pc[path]).max()
        assert top > 1e-3
        assert np.abs(shared[path] - shared_pc[path]).max() / top < 1e-5, path


# the parent's tree for the tiny preset, and entries of the parent's init
# with jax.random.key(0) (read off commit 55c5b7d)
TREE = {
    "block_N/attn/k/bias": (4, 16), "block_N/attn/k/kernel": (64, 4, 16),
    "block_N/attn/out/bias": (64,), "block_N/attn/out/kernel": (4, 16, 64),
    "block_N/attn/q/bias": (4, 16), "block_N/attn/q/kernel": (64, 4, 16),
    "block_N/attn/v/bias": (4, 16), "block_N/attn/v/kernel": (64, 4, 16),
    "block_N/ln1/bias": (64,), "block_N/ln1/scale": (64,),
    "block_N/ln2/bias": (64,), "block_N/ln2/scale": (64,),
    "block_N/ln3/bias": (192,), "block_N/ln3/scale": (192,),
    "block_N/mlp/fc1/bias": (256,), "block_N/mlp/fc1/kernel": (64, 256),
    "block_N/mlp/fc2/bias": (64,), "block_N/mlp/fc2/kernel": (256, 64),
    "cls_tokens": (1, 3, 64),
    "embed/pos_embed": (4, 4, 64),
    "embed/proj/bias": (64,), "embed/proj/kernel": (8, 8, 3, 64),
    "jumbo_mlp/fc1/bias": (768,), "jumbo_mlp/fc1/kernel": (192, 768),
    "jumbo_mlp/fc2/bias": (192,), "jumbo_mlp/fc2/kernel": (768, 192),
    "ln/bias": (64,), "ln/scale": (64,),
}
PARENTS_INIT = {  # path: (kernel[0, :3], the sum of |kernel|)
    "jumbo_mlp/fc1/kernel": (
        [0.009459956549108028, -0.006471619941294193, -0.034338731318712234],
        2136.2998761316016),
    "jumbo_mlp/fc2/kernel": (
        [-0.00013530239812098444, -0.01962260529398918, -0.004309756215661764],
        2133.0790215365505),
}


def test_parameter_tree_and_init_are_the_parents():
    """Same paths, shapes, dtypes, and the same values from the same seed: a
    checkpoint the parent wrote restores, and ``infer/quant.py`` and the
    converters find the kernels where they were."""
    params = jax.jit(lambda: JumboViT(tiny()).init(
        {"params": jax.random.key(0), "noise": jax.random.key(1)}, IMAGES)["params"])()
    flat = {"/".join(k.key for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(params)}
    assert all(leaf.dtype == jnp.float32 for leaf in flat.values())
    shapes = {re.sub(r"block_\d+", "block_N", p): leaf.shape for p, leaf in flat.items()}
    assert shapes == TREE
    assert sum(p.startswith("block_") for p in flat) == LAYERS * 18
    for path, (corner, abs_sum) in PARENTS_INIT.items():
        got = np.asarray(flat[path])
        np.testing.assert_array_equal(got[0, :3], np.asarray(corner, np.float32))
        assert float(np.abs(got).sum(dtype=np.float64)) == pytest.approx(abs_sum, rel=1e-7)
    for path in ("jumbo_mlp/fc1/bias", "jumbo_mlp/fc2/bias"):
        assert not np.asarray(flat[path]).any()


def kernel_shaped_dots(text: str) -> list[str]:
    """``op_name`` of every ``dot`` in a compiled CPU module whose result has
    a shared kernel's shape."""
    found = []
    for line in text.splitlines():
        head, _, _ = line.partition(" dot(")
        if head != line and re.search(r"\[(192,768|768,192)\]", head.split("=")[-1]):
            found.append(re.search(r'op_name="([^"]*)"', line).group(1))
    return found


def test_the_compiled_step_holds_two_products_not_two_a_layer():
    backward = grad_program("float32_grad_ckpt_dots", False).as_text()
    dots = kernel_shaped_dots(backward)
    assert len(dots) == 2, dots
    assert all("/jumbo_mlp/fc" in name and "transpose(" in name for name in dots), dots
    # the one product contracts all layers' rows
    assert f"[{LAYERS * ROWS},768]" in backward and f"[{LAYERS * ROWS},192]" in backward
    assert len(kernel_shaped_dots(
        grad_program("float32_grad_ckpt_dots", True).as_text())) == 2 * LAYERS

    # a program that never differentiates drops the slots: nothing stacked
    # over the layers, and not a byte more than the form that opens none
    model = JumboViT(tiny())
    forward = jax.jit(lambda p: model.apply({"params": p}, IMAGES,
                                            rngs={"noise": jax.random.key(2)}))
    compiled = forward.lower(seeded_params()).compile()
    with per_call_form():
        compiled_pc = forward.lower(seeded_params()).compile()
    text = compiled.as_text()
    assert f"[{LAYERS * ROWS},768]" not in text and f"[{LAYERS},{ROWS},768]" not in text
    assert (compiled.memory_analysis().temp_size_in_bytes
            == compiled_pc.memory_analysis().temp_size_in_bytes)


def test_the_two_rules_alone():
    """``open_slots`` + ``record`` on a bare kernel: dW is the sum of the
    per-layer products, dX is autodiff's own, and the slots' values are
    never read (zeros in, the same y out)."""
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(8, 5), jnp.float32)
    xs = jnp.asarray(rng.randn(3, 4, 8), jnp.float32)

    def deferred(w, xs):
        slots = shared_grad.open_slots(w, 3, 4, jnp.float32)
        ys = [shared_grad.record(s, x, x @ jax.lax.stop_gradient(w)) for s, x in zip(slots, xs)]
        return sum((y ** 2).sum() for y in ys)

    plain = lambda w, xs: sum(((x @ w) ** 2).sum() for x in xs)
    assert float(deferred(w, xs)) == float(plain(w, xs))
    for got, want in zip(jax.grad(deferred, (0, 1))(w, xs), jax.grad(plain, (0, 1))(w, xs)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
