import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jumbo_mae_tpu_tpu.models import (
    ClassificationModel,
    DecoderConfig,
    JumboViT,
    MAEPretrainModel,
    preset,
)

TINY = preset(
    "vit_t16", image_size=32, patch_size=8, dtype="float32", labels=None
)
TINY_DEC = DecoderConfig(layers=1, dim=32, heads=2, dtype="float32")


def _images(n=2, size=32, key=0):
    return jax.random.randint(
        jax.random.key(key), (n, size, size, 3), 0, 256, dtype=jnp.int32
    ).astype(jnp.uint8)


class TestJumboViT:
    def test_mae_mode_shapes(self):
        cfg = TINY.replace(mask_ratio=0.75)
        model = JumboViT(cfg)
        imgs = jnp.zeros((2, 32, 32, 3), jnp.float32)
        vars_ = model.init(
            {"params": jax.random.key(0), "noise": jax.random.key(1)}, imgs
        )
        tokens, mask, ids = model.apply(
            vars_, imgs, rngs={"noise": jax.random.key(2)}
        )
        # 16 patches, keep 4, +3 CLS
        assert tokens.shape == (2, 3 + 4, cfg.dim)
        assert mask.shape == (2, 16)
        assert float(mask.sum(-1)[0]) == 12.0

    def test_classify_mode_logits(self):
        cfg = TINY.replace(labels=10)
        model = JumboViT(cfg)
        imgs = jnp.zeros((2, 32, 32, 3), jnp.float32)
        vars_ = model.init({"params": jax.random.key(0)}, imgs)
        logits = model.apply(vars_, imgs)
        assert logits.shape == (2, 10)

    def test_jumbo_mlp_is_shared_across_blocks(self):
        cfg = TINY.replace(labels=10, layers=3)
        model = JumboViT(cfg)
        vars_ = model.init(
            {"params": jax.random.key(0)}, jnp.zeros((1, 32, 32, 3))
        )
        params = vars_["params"]
        # exactly one jumbo_mlp parameter set, at the encoder level
        assert "jumbo_mlp" in params
        assert params["jumbo_mlp"]["fc1"]["kernel"].shape == (
            3 * cfg.dim,
            12 * cfg.dim,
        )
        for i in range(3):
            assert "jumbo_mlp" not in params[f"block_{i}"]

    def test_linear_probe_stops_gradient(self):
        cfg = TINY.replace(labels=10, linear_probing=True, batch_norm=True)
        model = JumboViT(cfg)
        # distinct random images: with identical samples BatchNorm collapses
        # its output to the zero-init bias and every grad is exactly 0
        imgs = jax.random.normal(jax.random.key(9), (2, 32, 32, 3))
        vars_ = model.init({"params": jax.random.key(0)}, imgs)

        def loss_fn(params):
            logits, _ = model.apply(
                {"params": params, "batch_stats": vars_["batch_stats"]},
                imgs,
                deterministic=False,
                mutable=["batch_stats"],
            )
            return (logits**2).sum()

        grads = jax.grad(loss_fn)(vars_["params"])
        flat = jax.tree_util.tree_leaves_with_path(grads)
        for path, g in flat:
            name = jax.tree_util.keystr(path)
            gnorm = float(jnp.abs(g).sum())
            if "head" in name:
                assert gnorm > 0, f"head grad unexpectedly zero: {name}"
            else:
                assert gnorm == 0, f"trunk grad leaked: {name}"

    def test_gap_pooling(self):
        cfg = TINY.replace(labels=10, pooling="gap")
        model = JumboViT(cfg)
        imgs = jnp.zeros((2, 32, 32, 3), jnp.float32)
        vars_ = model.init({"params": jax.random.key(0)}, imgs)
        assert model.apply(vars_, imgs).shape == (2, 10)

    @pytest.mark.slow  # heavy compile; full suite covers it
    def test_remat_matches_no_remat(self):
        imgs = jax.random.normal(jax.random.key(3), (2, 32, 32, 3))
        cfg = TINY.replace(labels=10)
        vars_ = JumboViT(cfg).init({"params": jax.random.key(0)}, imgs)

        def loss(params, cfg):
            out = JumboViT(cfg).apply({"params": params}, imgs)
            return (out**2).mean()

        g1 = jax.grad(loss)(vars_["params"], cfg)
        g2 = jax.grad(loss)(vars_["params"], cfg.replace(grad_ckpt=True))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5), g1, g2
        )

    def test_remat_policies_match_no_remat(self):
        """A remat policy must only change WHAT is recomputed, never the
        gradient values (``dots`` is the ViT-H/14 recipe's)."""
        imgs = jax.random.normal(jax.random.key(3), (2, 32, 32, 3))
        cfg = TINY.replace(labels=10)
        vars_ = JumboViT(cfg).init({"params": jax.random.key(0)}, imgs)

        def loss(params, cfg):
            out = JumboViT(cfg).apply({"params": params}, imgs)
            return (out**2).mean()

        g1 = jax.grad(loss)(vars_["params"], cfg)
        g2 = jax.grad(loss)(
            vars_["params"], cfg.replace(grad_ckpt=True, remat_policy="dots")
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5), g1, g2
        )


@pytest.mark.parametrize("policy", ["none", "dots"])
@pytest.mark.parametrize("tower", ["encoder", "decoder"])
def test_a_rematted_vit_block_saves_what_it_saved_without_the_names(tower, policy, capsys):
    """``checkpoint_policy`` also keeps the arrays named by the causal core's
    forward rule. A ViT block has none: under both policies it saves exactly
    what jax's own policy object saves (nothing; the matmul outputs)."""
    import flax.linen as nn

    from jumbo_mae_tpu_tpu.models.config import maybe_remat
    from jumbo_mae_tpu_tpu.models.layers import JumboBlock, PlainBlock, make_jumbo_mlp

    if tower == "encoder":
        cfg = TINY.replace(grad_ckpt=True, remat_policy=policy)
        build = lambda cls: cls(cfg, make_jumbo_mlp(cfg, name=None))
        block_cls = JumboBlock
    else:
        cfg = TINY_DEC.replace(grad_ckpt=True, remat_policy=policy)
        build = lambda cls: cls(cfg)
        block_cls = PlainBlock
    x = jax.random.normal(jax.random.key(0), (2, 7, cfg.dim))
    params = build(block_cls).init(jax.random.key(1), x, True)["params"]

    def saved(cls):
        block = build(cls)
        loss = lambda p, h: (block.apply({"params": p}, h, True) ** 2).sum()
        jax.ad_checkpoint.print_saved_residuals(loss, params, x)
        out = capsys.readouterr().out
        assert " named '" not in out
        # by shape: how JAX words a residual's origin varies with its caches
        return sorted(ln.split(" ")[0] for ln in out.splitlines())

    unnamed = {"none": None, "dots": jax.checkpoint_policies.dots_saveable}[policy]
    ours = saved(maybe_remat(block_cls, cfg))
    assert ours == saved(nn.remat(block_cls, static_argnums=(2,), policy=unnamed))
    assert len(ours) < len(saved(block_cls))  # and fewer than with no remat


class TestMAEPretrainModel:
    def _build(self, **kw):
        cfg = TINY.replace(mask_ratio=0.75)
        model = MAEPretrainModel(cfg, TINY_DEC, **kw)
        imgs = _images()
        vars_ = model.init(
            {"params": jax.random.key(0), "noise": jax.random.key(1)}, imgs
        )
        return model, vars_, imgs

    def test_loss_finite_and_scalar(self):
        model, vars_, imgs = self._build()
        out = model.apply(vars_, imgs, rngs={"noise": jax.random.key(2)})
        assert out["loss"].shape == ()
        assert np.isfinite(float(out["loss"]))

    def test_norm_pix_loss(self):
        model, vars_, imgs = self._build(norm_pix_loss=True)
        out = model.apply(vars_, imgs, rngs={"noise": jax.random.key(2)})
        assert np.isfinite(float(out["loss"]))

    def test_reconstruction_shape(self):
        model, vars_, imgs = self._build()
        out = model.apply(
            vars_,
            imgs,
            rngs={"noise": jax.random.key(2)},
            return_reconstruction=True,
        )
        assert out["reconstruction"].shape == (2, 16, 8 * 8 * 3)

    def test_loss_only_depends_on_masked_patches(self):
        """Gradient of the loss w.r.t. predictions must be zero on visible
        patches — the loss contract of MAE."""
        model, vars_, imgs = self._build()

        out = model.apply(
            vars_,
            imgs,
            rngs={"noise": jax.random.key(5)},
            return_reconstruction=True,
        )
        mask = np.asarray(out["mask"])
        assert mask.sum() == 2 * 12  # 16 patches, keep 4


class TestClassificationModel:
    def test_metrics_shapes(self):
        cfg = TINY.replace(labels=10)
        model = ClassificationModel(cfg, label_smoothing=0.1)
        imgs, labels = _images(4), jnp.array([1, 2, 3, 4])
        vars_ = model.init({"params": jax.random.key(0)}, imgs, labels)
        out = model.apply(vars_, imgs, labels)
        assert out["loss"].shape == (4,)
        assert out["acc1"].shape == (4,)
        assert set(np.unique(np.asarray(out["acc5"]))) <= {0.0, 1.0}

    def test_train_path_with_mixup(self):
        cfg = TINY.replace(labels=10)
        model = ClassificationModel(
            cfg, mixup_alpha=0.8, cutmix_alpha=1.0, label_smoothing=0.1
        )
        imgs, labels = _images(4), jnp.array([1, 2, 3, 4])
        vars_ = model.init({"params": jax.random.key(0)}, imgs, labels)
        out = model.apply(
            vars_,
            imgs,
            labels,
            deterministic=False,
            rngs={"mixup": jax.random.key(1), "dropout": jax.random.key(2)},
        )
        assert np.isfinite(np.asarray(out["loss"])).all()

    def test_perfect_prediction_acc(self):
        cfg = TINY.replace(labels=10)
        model = ClassificationModel(cfg)
        imgs, labels = _images(2), jnp.array([0, 1])
        vars_ = model.init({"params": jax.random.key(0)}, imgs, labels)
        out = model.apply(vars_, imgs, labels)
        # with random init acc is whatever it is, but all values must be 0/1
        assert set(np.unique(np.asarray(out["acc1"]))) <= {0.0, 1.0}


class TestMixupOps:
    def test_identity_when_disabled(self):
        from jumbo_mae_tpu_tpu.ops.mixup import mixup_cutmix

        imgs = jax.random.normal(jax.random.key(0), (4, 8, 8, 3))
        labels = jax.nn.one_hot(jnp.array([0, 1, 2, 3]), 10)
        out_i, out_l = mixup_cutmix(jax.random.key(1), imgs, labels, 0.0, 0.0)
        np.testing.assert_array_equal(np.asarray(out_i), np.asarray(imgs))

    def test_label_mass_conserved(self):
        from jumbo_mae_tpu_tpu.ops.mixup import mixup_cutmix

        imgs = jax.random.normal(jax.random.key(0), (8, 16, 16, 3))
        labels = jax.nn.one_hot(jnp.arange(8) % 4, 10)
        for ma, ca in [(0.8, 0.0), (0.0, 1.0), (0.8, 1.0)]:
            _, out_l = mixup_cutmix(jax.random.key(2), imgs, labels, ma, ca)
            np.testing.assert_allclose(
                np.asarray(out_l.sum(-1)), np.ones(8), rtol=1e-5
            )


def test_config_rejects_indivisible_heads():
    """head_dim = dim // heads must not floor silently (advisor round-4):
    the recipe surface (--set model.dec_heads=...) lands on these configs."""
    from jumbo_mae_tpu_tpu.models.config import JumboViTConfig

    with pytest.raises(ValueError, match="divisible"):
        JumboViTConfig(dim=768, heads=7)
    with pytest.raises(ValueError, match="divisible"):
        DecoderConfig(dim=512, heads=7)
    with pytest.raises(ValueError, match="divisible"):
        DecoderConfig(dim=512, heads=16).replace(heads=3)
    # valid ones still construct
    assert JumboViTConfig(dim=768, heads=12).head_dim == 64
    assert DecoderConfig(dim=512, heads=2).head_dim == 256
