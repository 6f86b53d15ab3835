"""Int8 weight-only post-training quantization for the serving forward.

PERF_ARCHIVE.md's per-op accounting puts the serving-relevant shapes in the
weight-HBM-bandwidth-bound regime at small batch: every request streams the
full parameter set through the MXU once, so halving parameter bytes halves
the dominant term. This module converts a restored f32 params tree into
int8 matmul kernels with per-output-channel f32 scales:

- **What quantizes.** Leaves named ``kernel`` with ndim >= 2 — the patch
  embedding conv, q/k/v/out attention projections, MLP fc1/fc2, the head,
  and the decoder stack. Everything else (positional embeddings, CLS/mask
  tokens, LayerNorm scales, biases, BatchNorm statistics) stays f32: those
  are a rounding error of the byte budget and quantizing them buys nothing.
- **How.** Symmetric per-output-channel scaling: ``scale = max|w| / 127``
  over the reduction axes (the axes the matmul contracts away), so each
  output channel keeps its own dynamic range and a single outlier channel
  cannot crush the resolution of the rest. Zero-max channels get scale 1
  (they dequantize to exact zeros).
- **Dequant-on-use.** :class:`QuantizedTensor` is a registered pytree node,
  so the quantized tree is passed straight into the jitted forward as an
  argument — int8 weights are what lives in HBM and what the executable
  reads; the ``int8 -> f32 multiply`` runs on-chip where it fuses into the
  consumer. Dequantization reproduces ``q * scale`` exactly in f32, so the
  quantized forward is as deterministic (and as row-independent — the
  padding-inertness contract survives) as the f32 one.

Parity is measured, never assumed: :func:`parity_report` runs the same
images through a reference and a quantized engine and reports feature
cosine / logits top-1 agreement against the stated tolerances below —
``tools/bench_infer.py`` embeds the report in its JSON and CI gates on it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Stated parity tolerances (README "Quantized serving"): measured on the
# CPU smoke model (bench_infer --quant-leg) and asserted by CI; chip-side
# recipes re-measure with the same report before a quantized rollout.
FEATURE_COSINE_MIN = 0.999
TOP1_AGREEMENT_MIN = 0.98

_QKV = ("q", "k", "v")


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """An int8 weight plus its per-output-channel f32 scale.

    Registered as a pytree node so jit/AOT treat it as two leaves — the
    int8 payload is the device-resident form; nothing f32-sized survives
    quantization. ``scale`` keeps reduced axes as size-1 dims so
    ``q * scale`` broadcasts back to the weight's shape.
    """

    __slots__ = ("q", "scale")

    def __init__(self, q, scale):
        self.q = q
        self.scale = scale

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    def dequantize(self, dtype=jnp.float32):
        """Exact ``q * scale`` in f32, then cast — inside a jitted forward
        the multiply fuses into the consuming matmul's operand read."""
        w = self.q.astype(jnp.float32) * self.scale
        return w.astype(dtype)

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    def __repr__(self):
        return f"QuantizedTensor(shape={tuple(self.q.shape)}, int8+f32scale)"


def is_quantized(x) -> bool:
    return isinstance(x, QuantizedTensor)


def _key_name(entry) -> str:
    # DictKey(.key) for dicts, GetAttrKey(.name) for dataclasses/modules
    return str(getattr(entry, "key", getattr(entry, "name", entry)))


def _reduction_axes(names: list[str], ndim: int) -> tuple[int, ...]:
    """The axes a matmul contracts away, i.e. everything except the output
    channels. DenseGeneral q/k/v kernels are (dim, heads, head_dim) — the
    output is the trailing (heads, head_dim) pair; every other kernel
    (Dense 2-D, attention out 3-D, Conv 4-D) has output as the last axis."""
    if ndim >= 3 and len(names) >= 2 and names[-2] in _QKV:
        return tuple(range(ndim - 2))
    return tuple(range(ndim - 1))


def quantize_tensor(w, axes: tuple[int, ...]) -> QuantizedTensor:
    """Symmetric int8 quantization of one weight over ``axes``."""
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return QuantizedTensor(q, scale)


def quantize_params(params) -> tuple[dict, dict]:
    """Walk a params tree; return ``(quantized_tree, report)``.

    The tree keeps its structure — matmul kernels become
    :class:`QuantizedTensor` leaves, everything else passes through
    untouched. ``report`` accounts for what happened: leaf counts, byte
    totals before/after, and the compression ratio (the number the
    bandwidth model converts into step-time)."""
    report = {
        "n_quantized": 0,
        "n_kept": 0,
        "bytes_before": 0,
        "bytes_after": 0,
    }

    def visit(path, leaf):
        names = [_key_name(p) for p in path]
        if is_quantized(leaf):
            raise ValueError(
                f"{'/'.join(names)} is already quantized — quantize_params "
                "expects an f32 params tree, not its own output"
            )
        arr = np.asarray(leaf)
        nbytes = int(arr.size * arr.dtype.itemsize)
        report["bytes_before"] += nbytes
        if names and names[-1] == "kernel" and arr.ndim >= 2:
            qt = quantize_tensor(leaf, _reduction_axes(names, arr.ndim))
            report["n_quantized"] += 1
            report["bytes_after"] += int(
                qt.q.size * 1 + qt.scale.size * qt.scale.dtype.itemsize
            )
            return qt
        report["n_kept"] += 1
        report["bytes_after"] += nbytes
        return leaf

    qtree = jax.tree_util.tree_map_with_path(visit, params, is_leaf=is_quantized)
    report["compression"] = round(
        report["bytes_before"] / max(report["bytes_after"], 1), 3
    )
    return qtree, report


def dequantize_tree(tree, dtype=jnp.float32):
    """Map :meth:`QuantizedTensor.dequantize` over a (possibly mixed) tree.
    Called at the top of the jitted forward: the executable's *arguments*
    stay int8; the f32 view exists only as fused intermediates."""
    return jax.tree_util.tree_map(
        lambda x: x.dequantize(dtype) if is_quantized(x) else x,
        tree,
        is_leaf=is_quantized,
    )


# ------------------------------------------------------------------ parity


def feature_cosine(a, b) -> np.ndarray:
    """Per-row cosine similarity between two feature matrices."""
    a = np.asarray(a, np.float64).reshape(len(a), -1)
    b = np.asarray(b, np.float64).reshape(len(b), -1)
    num = (a * b).sum(axis=1)
    den = np.linalg.norm(a, axis=1) * np.linalg.norm(b, axis=1)
    return num / np.maximum(den, 1e-12)


def top1_agreement(logits_a, logits_b) -> float:
    a = np.asarray(logits_a)
    b = np.asarray(logits_b)
    return float((a.argmax(-1) == b.argmax(-1)).mean())


def parity_report(
    reference,
    quantized,
    images,
    *,
    task: str = "features",
    pool: str = "cls",
    registry=None,
) -> dict:
    """Measure quantization parity on real traffic: the same images through
    a reference engine and a quantized engine.

    ``features``: per-image cosine between pooled embeddings (min and mean)
    against :data:`FEATURE_COSINE_MIN`. ``logits``: top-1 agreement against
    :data:`TOP1_AGREEMENT_MIN`, plus the max absolute logit delta for
    context. The verdict lands in ``within_tolerance`` and, when a metrics
    registry is live, in the ``infer_quant_parity`` gauge family.
    """
    if task not in ("features", "logits"):
        raise ValueError(f"parity is defined for features/logits, got {task!r}")
    rep: dict = {"task": task, "images": int(np.asarray(images).shape[0])}
    if task == "features":
        ref = reference.features(images, pool=pool)
        q = quantized.features(images, pool=pool)
        cos = feature_cosine(ref, q)
        rep.update(
            cosine_min=round(float(cos.min()), 6),
            cosine_mean=round(float(cos.mean()), 6),
            tolerance={"cosine_min": FEATURE_COSINE_MIN},
        )
        rep["within_tolerance"] = rep["cosine_min"] >= FEATURE_COSINE_MIN
    else:
        ref = reference.logits(images)
        q = quantized.logits(images)
        rep.update(
            top1_agreement=round(top1_agreement(ref, q), 6),
            max_abs_logit_delta=round(float(np.abs(ref - q).max()), 6),
            tolerance={"top1_agreement": TOP1_AGREEMENT_MIN},
        )
        rep["within_tolerance"] = rep["top1_agreement"] >= TOP1_AGREEMENT_MIN
    if registry is None:
        from jumbo_mae_tpu_tpu.obs.metrics import get_registry

        registry = get_registry()
    gauge = registry.gauge(
        "infer_quant_parity",
        "quantized-vs-reference parity measurements",
        labels=("metric",),
    )
    for name in ("cosine_min", "cosine_mean", "top1_agreement"):
        if name in rep:
            gauge.labels(name).set(rep[name])
    gauge.labels("within_tolerance").set(1.0 if rep["within_tolerance"] else 0.0)
    return rep
