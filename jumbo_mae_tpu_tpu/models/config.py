"""Model configuration dataclasses and presets.

Replaces the reference's ``ViTBase``/``MAEDecoderBase`` dataclass-mixin
pattern (``/root/reference/src/modeling.py:35-104``) with plain frozen config
objects passed to modules as a single attribute — hashable, serializable, and
independent of module inheritance.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Literal

import jax.numpy as jnp

Posemb = Literal["learnable", "sincos2d"]
Pooling = Literal["cls", "gap"]
MaskModeT = Literal["shared", "per_sample"]
# rematerialization policy under grad_ckpt=True:
#   "none" — recompute the whole block; keep only the causal attention core's
#            output and log-sum-exp, where a block has that kernel
#   "dots" — also save every matmul output, recompute elementwise only
RematPolicy = Literal["none", "dots"]


def checkpoint_policy(name: str):
    """Map a RematPolicy name to the jax.checkpoint policy callable.

    Every policy keeps the arrays that carry the causal core's two checkpoint
    names (``ops/pallas/attention.py``): the forward kernel's output and its
    log-sum-exp, the only residuals of its backward kernels that recomputing
    the block's projections does not rebuild. A block that keeps them runs the
    forward kernel once. The cost is fixed by shapes: ``heads × v_head_dim``
    values in the compute dtype plus one float32 a head, for each token and
    layer (32 × 128 bf16 + 32 × 4 B = 8.3 KB against the 4 KB of block input a
    remat keeps anyway, at the JoyAI share's widths). An array carries a name
    only where that kernel's forward rule produced it: a block without it
    (the ViT's, the MAE decoder's, the einsum form of the causal core) saves
    under ``"none"`` nothing and under ``"dots"`` its matmul outputs."""
    import jax

    from jumbo_mae_tpu_tpu.ops.pallas.attention import CAUSAL_LSE_NAME, CAUSAL_OUT_NAME

    policies = jax.checkpoint_policies
    named = policies.save_only_these_names(CAUSAL_OUT_NAME, CAUSAL_LSE_NAME)
    if name == "none":
        return named
    if name == "dots":
        return policies.save_from_both_policies(policies.dots_saveable, named)
    raise ValueError(f"unknown remat policy {name!r}")


def maybe_remat(block_cls, cfg):
    """Wrap a transformer block class with ``nn.remat`` per the config's
    ``grad_ckpt``/``remat_policy`` knobs (the one place the remat wiring
    lives; used by both the encoder and the MAE decoder). The deterministic
    flag (arg 2) stays static."""
    import flax.linen as nn

    if not cfg.grad_ckpt:
        return block_cls
    return nn.remat(
        block_cls,
        static_argnums=(2,),
        policy=checkpoint_policy(cfg.remat_policy),
    )


@dataclass(frozen=True)
class JumboViTConfig:
    """Encoder configuration.

    Capability parity with ``ViTBase`` (``/root/reference/src/modeling.py:35``)
    plus TPU-first knobs: compute ``dtype`` (bfloat16 by default — MXU-native)
    and a per-sample masking mode option.
    """

    layers: int = 12
    dim: int = 768
    heads: int = 12
    num_cls_tokens: int = 3
    labels: int | None = 1000
    layerscale: bool = False

    patch_size: int = 16
    image_size: int = 224
    posemb: Posemb = "learnable"
    pooling: Pooling = "cls"

    dropout: float = 0.0
    droppath: float = 0.0
    grad_ckpt: bool = False
    remat_policy: RematPolicy = "none"

    # MAE
    mask_ratio: float | None = None
    mask_mode: MaskModeT = "shared"

    # classification-head behavior
    linear_probing: bool = False
    batch_norm: bool = False

    # TPU-first knobs
    dtype: str = "bfloat16"  # compute dtype; params always float32

    def __post_init__(self):
        if self.heads <= 0 or self.dim % self.heads:
            # head_dim floors silently otherwise: heads=7 at dim=768 would
            # train a 763-wide attention with no warning (the recipe/--set
            # surface lands here)
            raise ValueError(
                f"dim ({self.dim}) must be divisible by heads ({self.heads})"
            )

    @property
    def head_dim(self) -> int:
        return self.dim // self.heads

    @property
    def hidden_dim(self) -> int:
        return 4 * self.dim

    @property
    def grid(self) -> tuple[int, int]:
        return (self.image_size // self.patch_size,) * 2

    @property
    def num_patches(self) -> int:
        g = self.grid
        return g[0] * g[1]

    @property
    def keep_len(self) -> int:
        if self.mask_ratio is None:
            raise ValueError("keep_len undefined without mask_ratio")
        return int(self.num_patches * (1.0 - self.mask_ratio))

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def replace(self, **kw) -> "JumboViTConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class DecoderConfig:
    """MAE decoder configuration (parity:
    ``MAEDecoderBase``, ``/root/reference/src/modeling.py:73-104``).
    Decoder positional embeddings are always fixed sincos2d — the reference's
    ``dec_posemb`` flag was parsed but ignored (defect ledger #3), so it does
    not exist here."""

    layers: int = 8
    dim: int = 512
    heads: int = 16
    layerscale: bool = False

    dropout: float = 0.0
    droppath: float = 0.0
    grad_ckpt: bool = False
    remat_policy: RematPolicy = "none"

    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.heads <= 0 or self.dim % self.heads:
            raise ValueError(
                f"decoder dim ({self.dim}) must be divisible by heads "
                f"({self.heads})"
            )

    @property
    def head_dim(self) -> int:
        return self.dim // self.heads

    @property
    def hidden_dim(self) -> int:
        return 4 * self.dim

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def replace(self, **kw) -> "DecoderConfig":
        return dataclasses.replace(self, **kw)


# Named presets matching the reference recipe matrix (config/*.sh) plus the
# BASELINE.json north-star ViT-H/14.
PRESETS: dict[str, dict] = {
    "vit_t16": dict(layers=2, dim=64, heads=4),  # test-sized
    "vit_s16": dict(layers=12, dim=384, heads=6),
    "vit_b16": dict(layers=12, dim=768, heads=12),
    "vit_l16": dict(layers=24, dim=1024, heads=16),
    "vit_h14": dict(layers=32, dim=1280, heads=16, patch_size=14),
}


def preset(name: str, **overrides) -> JumboViTConfig:
    if name not in PRESETS:
        raise ValueError(f"unknown preset {name!r}; have {sorted(PRESETS)}")
    return JumboViTConfig(**{**PRESETS[name], **overrides})
