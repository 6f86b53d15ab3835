#!/usr/bin/env python3
"""Chip smoke: the trainer and the server on a TPU, through their own entry
points, at the full width of a model the repo ships.

    python3 chip_smoke.py [--out DIR]       # one chip: kernels, train,
                                            #   resume, serve, lm_kernels,
                                            #   lm_train
    python3 chip_smoke.py --phases lm_kernels,lm_train   # only those
    python3 chip_smoke.py --phases lm_train --lm-recipe recipes/pretrain_ling3_flash_ep64.yaml
                                            # lm_train on the other language family
    python3 chip_smoke.py --chips 4         # four-chip host: sharded training
                                            #   against its one-chip control,
                                            #   and no other phase

This process is the only one that touches JAX (a chip belongs to one
process): every phase calls ``cli.train.main`` / ``cli.predict.main`` here,
in-process, and no child is started. It fails at once when the platform is
not ``tpu`` — there is no option that lets it pass on the CPU. Each phase is
a plain function of (recipe, overrides, out dir), so
``tests/test_chip_compile.py`` can rehearse it at toy size without a chip.

stdout carries one JSON line per finished phase (name, wall and compile
seconds, what was checked) and, when every phase passed, a last line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The entry points' own chatter goes to ``<out>/<phase>.log``; the tail of a
failed phase's log is copied to stderr. Any failed phase means a non-zero
exit and no last line.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import logging
import math
import re
import shutil
import sys
import time
import traceback
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
L16_RECIPE = str(REPO / "recipes" / "pretrain_vit_l16_in1k_800ep.yaml")
H14_RECIPE = str(REPO / "recipes" / "pretrain_vit_h14_in1k_fsdp.yaml")
LM_RECIPE = str(REPO / "recipes" / "pretrain_joyai_flash_ep16.yaml")

# (batch, seq, heads, head_dim) attention inputs the long-context recipes
# reach (recipes/pretrain_vit_l16_448_longctx.yaml): the decoder at 448 px
# (787 tokens, head_dim 32) and, for the same model at 896 px, the encoder
# (787 tokens, head_dim 64) and the decoder (3139 tokens, head_dim 32).
KERNEL_SHAPES = ((8, 787, 16, 32), (8, 787, 16, 64), (2, 3139, 16, 32))

# bf16 carries 8 mantissa bits; the kernel and its reference round at
# different points, so they agree to a few bf16 ulps of the largest value.
# A wrong mask or block plan is off by the size of the values themselves.
KERNEL_REL_TOL = 0.05
# per-step loss of the sharded run against its one-chip control: same seed,
# same global batch, reductions in another order
LOSS_REL_TOL = 2e-2


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# --------------------------------------------------------------- compiles


class CompileWatch:
    """Counts this process's XLA compiles and persistent-cache traffic from
    JAX's own telemetry: ``jax.monitoring`` events for backend compiles
    (seconds included) and cache misses, and the compiler's log line for each
    hit, which is the only place the hit's program is named."""

    MISS_EVENT = "/jax/compilation_cache/cache_misses"
    HIT_PREFIX = "Persistent compilation cache hit for"

    def __init__(self):
        import jax.monitoring

        from jumbo_mae_tpu_tpu.obs.retrace import COMPILE_EVENT

        self._compile_event = COMPILE_EVENT
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_misses = 0
        self.hit_programs: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        handler = logging.Handler(level=logging.DEBUG)
        handler.emit = self._on_log
        compiler_log = logging.getLogger("jax._src.compiler")
        compiler_log.addHandler(handler)
        compiler_log.setLevel(logging.DEBUG)
        # the debug records stop here; warnings still reach stderr (_on_log)
        compiler_log.propagate = False

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == self._compile_event:
            self.compiles += 1
            self.compile_s += float(duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.MISS_EVENT:
            self.cache_misses += 1

    def _on_log(self, record: logging.LogRecord) -> None:
        if str(record.msg).startswith(self.HIT_PREFIX) and record.args:
            self.hit_programs.append(str(record.args[0]))
        elif record.levelno >= logging.WARNING:
            print(f"{record.name}: {record.getMessage()}", file=sys.stderr)

    def mark(self) -> dict:
        return {
            "compiles": self.compiles,
            "compile_s": self.compile_s,
            "cache_misses": self.cache_misses,
            "hits": len(self.hit_programs),
        }

    def since(self, mark: dict) -> dict:
        """Compile activity after ``mark``. A persistent-cache hit still
        counts as one (short) backend compile event."""
        hits = self.hit_programs[mark["hits"] :]
        return {
            "compiles": self.compiles - mark["compiles"],
            "compile_s": round(self.compile_s - mark["compile_s"], 2),
            "cache_misses": self.cache_misses - mark["cache_misses"],
            "cache_hits": len(hits),
            "hit_programs": hits,
        }


# ------------------------------------------------------------- utilities


def _delta(before: dict, after: dict, name: str, key: str) -> int:
    """How far one counter moved between two ``MetricsRegistry.snapshot()``s
    (absent counts as 0)."""
    return int(after.get(name, {}).get(key, 0) - before.get(name, {}).get(key, 0))


def _registry_snapshot() -> dict:
    from jumbo_mae_tpu_tpu.obs.metrics import get_registry

    return get_registry().snapshot()


def _read_metrics(run_dir: Path) -> list[dict]:
    """The trainer's own JSONL metrics log, every record in order."""
    (path,) = run_dir.glob("*-metrics.jsonl")
    return [json.loads(ln) for ln in path.read_text().splitlines() if ln.strip()]


def _logged_losses(
    records: list[dict], first: int, last: int, who: str
) -> dict[int, float]:
    """The loss the trainer logged at each of steps ``first..last``: every
    step present, every value finite."""
    losses = {
        int(r["step"]): float(r["train/loss"])
        for r in records
        if "train/loss" in r and first <= int(r.get("step", -1)) <= last
    }
    check(sorted(losses) == list(range(first, last + 1)),
          f"{who}: expected a logged loss for steps {first}..{last}, got {sorted(losses)}")
    check(all(np.isfinite(v) for v in losses.values()),
          f"{who}: non-finite loss {losses}")
    return losses


def _peak_hbm_bytes() -> int | None:
    import jax

    stats = jax.local_devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))


# ---------------------------------------------------------------- kernels


def _check_against_reference(label, key, fn, ref_fn, args, worst, *, interpret: bool):
    """``fn`` (kernels) and ``ref_fn`` (XLA) are ``(*args) -> (scalar, aux)``:
    compile ``fn``'s value-and-gradient over every argument, require the
    Mosaic custom call in it (unless interpreted), run both and record in
    ``worst[key]`` the largest relative error over ``aux`` and the gradients."""
    import jax
    import jax.numpy as jnp

    argnums = tuple(range(len(args)))
    compiled = jax.jit(jax.value_and_grad(fn, argnums=argnums, has_aux=True)).lower(*args).compile()
    if not interpret:
        check(
            "tpu_custom_call" in compiled.as_text(),
            f"{label}: no Mosaic custom call in the compiled program — the "
            "kernel is not what ran",
        )
    (_, aux), grads = compiled(*args)
    (_, ref_aux), ref_grads = jax.jit(
        jax.value_and_grad(ref_fn, argnums=argnums, has_aux=True)
    )(*args)
    got = jax.tree_util.tree_leaves((aux, grads))
    want = jax.tree_util.tree_leaves((ref_aux, ref_grads))
    for g, r in zip(got, want):
        check(bool(jnp.isfinite(g.astype(jnp.float32)).all()), f"{label}: non-finite output")
        err = _rel_err(g, r)
        check(
            err < KERNEL_REL_TOL,
            f"{label}: {err:.4f} off the XLA reference (tolerance {KERNEL_REL_TOL})",
        )
        worst[key] = round(max(worst.get(key, 0.0), err), 5)


def phase_kernels(shapes=KERNEL_SHAPES, *, interpret: bool = False) -> dict:
    """Pallas flash attention forward+backward, plain and ``_with_lse``,
    executed and compared with ``ops.attention.xla_attention``; plus
    the one-hot masking gather against the XLA gather, bit for bit.

    ``interpret`` exists for the CPU rehearsal in the tests; ``main`` never
    sets it, and without it the compiled program must hold the Mosaic
    custom call."""
    import jax
    import jax.numpy as jnp

    from jumbo_mae_tpu_tpu.ops.attention import xla_attention
    from jumbo_mae_tpu_tpu.ops.pallas.attention import (
        pallas_flash_attention,
        pallas_flash_attention_with_lse,
    )

    def ref_lse(q, k):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(s, axis=-1)  # (b, h, sq)
        return lse.reshape(-1, lse.shape[-1])

    worst: dict[str, float] = {}
    for b, s, h, d in shapes:
        keys = jax.random.split(jax.random.key(s * d), 5)
        q = (jax.random.normal(keys[0], (b, s, h, d)) * d**-0.5).astype(jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, s, h, d)).astype(jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, s, h, d)).astype(jnp.bfloat16)
        # fixed random cotangents, so no gradient is trivially small
        w_o = jax.random.normal(keys[3], (b, s, h, d))
        w_l = jax.random.normal(keys[4], (b * h, s))

        def flash(q, k, v):
            o = pallas_flash_attention(q, k, v, 1024, 1024, interpret)
            return (o.astype(jnp.float32) * w_o).sum(), o

        def flash_lse(q, k, v):
            o, lse = pallas_flash_attention_with_lse(q, k, v, 1024, 1024, interpret)
            return (o.astype(jnp.float32) * w_o).sum() + (lse * w_l).sum(), (o, lse)

        def ref(q, k, v):
            o = xla_attention(q, k, v)
            return (o.astype(jnp.float32) * w_o).sum(), o

        def ref_with_lse(q, k, v):
            o, lse = xla_attention(q, k, v), ref_lse(q, k)
            return (o.astype(jnp.float32) * w_o).sum() + (lse * w_l).sum(), (o, lse)

        for name, fn, ref_fn in (
            ("flash", flash, ref),
            ("flash_with_lse", flash_lse, ref_with_lse),
        ):
            _check_against_reference(
                f"{name} at {(b, s, h, d)}", f"{name}@{s}x{d}", fn, ref_fn, (q, k, v),
                worst, interpret=interpret,
            )

    return {
        "shapes": [list(s) for s in shapes],
        "mosaic_custom_call": not interpret,
        "max_rel_err_vs_xla": worst,
    }


# ------------------------------------------------------- language model


# (batch, heads, seq, qk width, shared rope width, v width[, key/value heads,
# window]), at a length that is no multiple of the kernel's block: latent
# attention's published head, and a grouped-query head of one score part
# whose 512-token window is walked as a band of blocks, and a full one whose
# heads are 64 wide (half a lane tile) at a group of 4
CAUSAL_SHAPES = ((1, 4, 2148, 128, 64, 128), (1, 16, 2148, 128, 0, 128, 2, 512),
                 (1, 8, 2148, 64, 0, 64, 2, None))
# (batch, heads, seq, width) of the rotate-half rope kernel's operand: 128 and
# 64 wide (ops/pallas/rope.py takes whole and half lane tiles)
ROPE_SHAPES = ((1, 4, 2048, 128), (1, 4, 2048, 64))
# (batch, heads, seq, width) of the linear-attention layers' short-convolution
# kernels' operand (ops/pallas/short_conv.py): two head blocks of four
# sequence blocks each
CONV_SHAPES = ((1, 16, 2048, 128),)
# (rows, k, n, group sizes): one expert takes most rows, one takes none
GROUPED_SHAPE = (4096, 2048, 1536, (2900, 0, 517, 200, 33, 8, 1, 300))
# (batch, heads, key/value heads, clean tokens a sequence, width, diffusion
# block) of the causal kernels under the block-diffusion pattern, every row
# against the einsum form: a short row that is no multiple of the kernel's
# block, and the SDAR cell's own shape (2 x 8192 rows a sequence, 32 heads
# over 4: all 80 block pairs of the 8-block tables, the 24 cut ones among them)
BLOCKDIFF_SHAPES = ((1, 8, 1, 2148, 128, 4), (1, 32, 4, 8192, 128, 4))


def phase_lm_kernels(causal=CAUSAL_SHAPES, grouped=GROUPED_SHAPE, rope=ROPE_SHAPES,
                     conv=CONV_SHAPES, blockdiff=BLOCKDIFF_SHAPES, *,
                     interpret: bool = False) -> dict:
    """The causal flash kernels (unequal qk and v widths, the shared rope
    key; grouped key/value heads under a window; 64-wide heads; a clean and a
    noisy copy under the block-diffusion pattern) forward+backward
    against the einsum form, the rotate-half rope kernel against its
    ``jax.numpy`` form, the short-convolution kernels (q's form, with the
    norm, and v's, without) forward+backward against ``short_conv_plain``,
    and the grouped product forward+backward
    against ``lax.ragged_dot``; worst relative errors."""
    import jax
    import jax.numpy as jnp

    from jumbo_mae_tpu_tpu.models.lm import Rope, rope_half
    from jumbo_mae_tpu_tpu.ops.attention import xla_causal_attention
    from jumbo_mae_tpu_tpu.ops.grouped_matmul import grouped_matmul
    from jumbo_mae_tpu_tpu.ops.kda import short_conv, short_conv_plain
    from jumbo_mae_tpu_tpu.ops.pallas.attention import pallas_causal_attention

    worst: dict[str, float] = {}

    def compare(name, fn, ref_fn, args):
        _check_against_reference(name, name, fn, ref_fn, args, worst, interpret=interpret)

    for b, h, s, dn, dr, dv, *grouped_window in causal:
        g, window = grouped_window or (h, None)
        keys = jax.random.split(jax.random.key(s), 6)
        bf = lambda k, shape, scale=1.0: (jax.random.normal(k, shape) * scale).astype(jnp.bfloat16)
        scale = (dn + dr) ** -0.5
        args = (bf(keys[0], (b, h, s, dn), scale), bf(keys[1], (b, h, s, dr), scale) if dr else None,
                bf(keys[2], (b, g, s, dn)), bf(keys[3], (b, s, dr)) if dr else None,
                bf(keys[4], (b, g, s, dv)))
        w = jax.random.normal(keys[5], (b, h, s, dv))

        def weigh(fn, w=w):
            def weighed(*xs):
                o = fn(*xs)
                return (o.astype(jnp.float32) * w).sum(), o
            return weighed

        block = None if not interpret else 16  # None: the shape's own (causal_block)
        name = f"causal@{s}x{dn}+{dr}/{dv}" + (f"g{h // g}w{window}" if grouped_window else "")
        compare(name,
                weigh(lambda *xs, window=window: pallas_causal_attention(
                    *xs, block, interpret, window)),
                weigh(lambda *xs, window=window: xla_causal_attention(*xs, window)), args)

    for b, h, g, s, d, unit in blockdiff:
        keys = jax.random.split(jax.random.key(s + unit), 4)
        bf = lambda k, shape, scale=1.0: (jax.random.normal(k, shape) * scale).astype(jnp.bfloat16)
        # scores of deviation 6: a row's output is then a few keys' values however many it
        # sees, and a key wrongly seen or hidden moves some late row's output by its size
        # (under scores of deviation 1 a late row is a mean of thousands that 4 keys cannot move)
        q, k, v = bf(keys[0], (b, h, 2 * s, d), 6 * d**-0.5), bf(keys[1], (b, g, 2 * s, d)), bf(
            keys[2], (b, g, 2 * s, d))
        w = jax.random.normal(keys[3], (b, h, 2 * s, d))
        block = None if not interpret else 16

        def kernels(q, k, v, unit=unit, w=w, block=block):
            o = pallas_causal_attention(q, None, k, None, v, block, interpret, None, unit)
            return (o.astype(jnp.float32) * w).sum(), o

        def einsum(q, k, v, unit=unit, w=w, group=h // g):
            # a query head at a time: one head's (2 s, 2 s) scores are 1 GB at the cell's shape
            one = jax.checkpoint(lambda x: xla_causal_attention(
                x[0][:, None], None, x[1][:, None], None, x[2][:, None], None, unit)[:, 0])
            heads = lambda x: jnp.moveaxis(x, 1, 0)
            o = heads(jax.lax.map(one, (heads(q), heads(jnp.repeat(k, group, axis=1)),
                                        heads(jnp.repeat(v, group, axis=1)))))
            return (o.astype(jnp.float32) * w).sum(), o

        compare(f"blockdiff@2x{s}x{d}g{h // g}b{unit}", kernels, einsum, (q, k, v))

    for b, h, s, d in rope:
        keys = jax.random.split(jax.random.key(d), 2)
        x = jax.random.normal(keys[0], (b, h, s, d)).astype(jnp.bfloat16)
        w = jax.random.normal(keys[1], (b, h, s, d))
        turn = Rope(rope_theta=1e6)
        # a 3-D operand takes the jax.numpy form on every backend
        compare(f"rope@{s}x{d}",
                lambda x, w=w: ((rope_half(x, turn, interpret=interpret).astype(jnp.float32)
                                 * w).sum(), None),
                lambda x, w=w: ((jnp.stack([rope_half(row, turn) for row in x])
                                 .astype(jnp.float32) * w).sum(), None), (x,))

    for b, h, s, d in conv:
        keys = jax.random.split(jax.random.key(h), 3)
        x = jax.random.normal(keys[0], (b, h, s, d)).astype(jnp.bfloat16)
        taps = jax.random.uniform(keys[1], (4, h, d), jnp.float32, -0.5, 0.5)
        w = jax.random.normal(keys[2], (b, h, s, d))
        for name, scale in (("q", d**-0.5), ("v", None)):
            # interpret or not, the op's own rule sends this shape to the kernels on the chip
            compare(f"short_conv_{name}@{s}x{d}",
                    lambda x, taps, scale=scale, w=w: (
                        (short_conv(x, taps, scale, interpret=interpret).astype(jnp.float32)
                         * w).sum(), None),
                    lambda x, taps, scale=scale, w=w: (
                        (short_conv_plain(x, taps, scale).astype(jnp.float32) * w).sum(), None),
                    (x, taps))

    m, k, n, sizes = grouped
    keys = jax.random.split(jax.random.key(m), 3)
    lhs = jax.random.normal(keys[0], (m, k)).astype(jnp.bfloat16)
    rhs = (jax.random.normal(keys[1], (len(sizes), k, n)) * k**-0.5).astype(jnp.bfloat16)
    w = jax.random.normal(keys[2], (m, n))
    group_sizes = jnp.asarray(sizes, jnp.int32)

    starts = np.concatenate([[0], np.cumsum(sizes)])
    group_of_row = np.full((m,), len(sizes), np.int32)  # rows past the last group: none
    for g in range(len(sizes)):
        group_of_row[starts[g]:starts[g + 1]] = g

    def kernels(a, b):
        out = grouped_matmul(a, b, group_sizes, impl="pallas", interpret=interpret)
        return (out.astype(jnp.float32) * w).sum(), out

    def dense(a, b):
        # every group's matrix meets every row, in float32; a mask keeps its own
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        out = sum(jnp.where((group_of_row == g)[:, None],
                            jnp.dot(a, b[g], precision="highest"), 0.0)
                  for g in range(len(sizes)))
        return (out * w).sum(), out

    compare(f"grouped@{m}x{k}x{n}", kernels, dense, (lhs, rhs))
    return {"mosaic_custom_call": not interpret, "max_rel_err_vs_xla": worst}


def causal_kernel_calls(text: str) -> dict:
    """How often a compiled program's text calls each of the causal core's
    two kernels: ``{"fwd": n, "bwd": n}``."""
    return {k: len(re.findall(rf'custom-call\([^\n]*/causal_attention_{k}/pallas_call"', text))
            for k in ("fwd", "bwd")}


def bd_kernel_calls(text: str) -> dict:
    """How often a compiled program's text calls each of the causal core's two
    kernels under the block-diffusion pattern, that is inside the ``bd_core``
    scope: ``{"fwd": n, "bwd": n}``; 0 in every causal family's step."""
    return {k: len(re.findall(
        rf'custom-call\([^\n]*/bd_core/[^\n"]*causal_attention_{k}/pallas_call"', text))
        for k in ("fwd", "bwd")}


def kda_kernel_calls(text: str) -> dict:
    """How often a compiled program's text runs the linear-attention core:
    ``{"fwd": calls of the forward chunk kernel, "bwd": of the backward one,
    "loops": ``while`` loops under the ``kda_core`` scope}``."""
    calls = {k: len(re.findall(rf'custom-call\([^\n]*/kda_chunk_{k}/pallas_call"', text))
             for k in ("fwd", "bwd")}
    return {**calls, "loops": len(re.findall(r' while\([^\n]*/attn/kda_core/[^"\n]*while"', text))}


def short_conv_kernel_calls(text: str) -> dict:
    """How often a compiled program's text runs the linear-attention layers'
    short-convolution kernels (``ops/pallas/short_conv.py``), by phase:
    ``{"fwd": the forward kernel in the forward pass, "recompute": under a
    block's remat, "bwd": the backward kernel}``."""
    names = re.findall(r'custom-call\([^\n]*op_name="([^"]*)/kda_short_conv_(fwd|bwd)/pallas_call"',
                       text)
    return {"fwd": sum(k == "fwd" and "rematted_computation" not in path for path, k in names),
            "recompute": sum(k == "fwd" and "rematted_computation" in path for path, k in names),
            "bwd": sum(k == "bwd" for _, k in names)}


def rope_kernel_calls(text: str) -> int:
    """How often a compiled program's text calls the rotate-half rope kernel
    (``ops/pallas/rope.py``)."""
    return len(re.findall(r'custom-call\([^\n]*[/(]rope_half\)*/pallas_call"', text))


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8}


def head_product_calls(text: str, rows: int) -> dict:
    """How often a compiled program's text runs the head's product, by phase,
    and the widest array it holds over the rows held: ``{"fwd": n,
    "recompute": n, "bwd": n, "widest_bytes": b}``. A head's product is a
    ``dot`` / ``convolution`` under the ``lm_head`` scope with ``rows`` among
    its operands' or its result's dimensions; the phase is its ``op_name``'s
    (a rematted computation, else a transpose, else the forward pass, as
    ``benchmarks/scope_reduce.py`` reads it). The widest array is over every
    shape with a ``rows`` axis: a tile's float32 logits or the float32 kernel
    where the loss walks the tokens in tiles (``ops/head_loss.py``), all
    tokens' logits where it does not."""
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])", text, re.M))
    dims = lambda shape: [int(d) for d in re.findall(r"\d+", shape.split("[", 1)[1])]
    calls = {"fwd": 0, "recompute": 0, "bwd": 0}
    product = re.compile(r"= (\w+\[[\d,]*\])\S* (?:dot|convolution)\(([^)]*)\)"
                         r'[^\n]*op_name="([^"]*/lm_head/[^"]*)"')
    for result, operands, op_name in product.findall(text):
        inline = re.findall(r"\w+\[[\d,]*\]", operands)  # some backends print operands' shapes
        named = [shape_of.get(name, "x[]") for name in re.findall(r"%([\w.\-]+)", operands)]
        if any(rows in dims(shape) for shape in [result, *inline, *named]):
            phase = ("recompute" if "rematted_computation" in op_name
                     else "bwd" if "transpose(" in op_name else "fwd")
            calls[phase] += 1
    arrays = {(dtype, tuple(map(int, inner.split(","))))
              for dtype, inner in re.findall(r"\b(\w+)\[([\d,]+)\]", text)}
    widest = max((_DTYPE_BYTES.get(dtype, 0) * math.prod(shape) for dtype, shape in arrays
                  if rows in shape), default=0)
    return {**calls, "widest_bytes": widest}


def check_step_runs_the_head_three_times(programs: dict, lm, tokens: int, seq: int) -> dict:
    """The step program among ``programs`` runs each head's product three
    times, all in the forward pass (logits, dX and dW a tile of tokens at a
    time: ``ops/head_loss.py``), never again under a remat or in the backward
    pass, on every backend: the tiles are a loop XLA compiles. On the chip,
    at the recipe's own sizes, it holds nothing over the rows held that is
    larger than one tile's float32 logits or the float32 kernel (at the
    rehearsal's toy widths other axes share the rows' length; so they do
    in a recipe that holds as many rows as a sequence has tokens, where an
    activation cannot be told from the logits by its shape and the size is
    not checked)."""
    import jax

    from jumbo_mae_tpu_tpu.ops.head_loss import head_tile

    rows = lm.rows[1]
    calls = head_product_calls(programs["train_step"].as_text(), rows)
    want = {"fwd": 3 * (1 + lm.mtp_layers), "recompute": 0, "bwd": 0}
    check({k: calls[k] for k in want} == want,
          f"the step runs the head's product {calls}, not {want}")
    most = 4 * rows * max(head_tile(tokens, rows), lm.dim)
    check(jax.default_backend() != "tpu" or rows == seq or 0 < calls["widest_bytes"] <= most,
          f"the step holds {calls['widest_bytes']} B over the rows held, over {most}")
    return calls


def check_step_runs_the_rope_kernel(programs: dict, lm) -> int:
    """The step program among ``programs`` turns each grouped-query block's
    ``q`` and ``k`` through the rope kernel three times: forward, under the
    block's rematerialisation, and transposed. Off the chip, and in a family
    whose rope is on adjacent pairs, the step holds no such call."""
    import jax

    calls = rope_kernel_calls(programs["train_step"].as_text())
    # a grouped-query block whose kind has a rotary embedding (a kind may have none)
    ropes = dict(lm.rope_parameters or ())
    blocks = sum(ropes.get(kind) is not None for kind in lm.kinds)
    want = 3 * 2 * blocks if jax.default_backend() == "tpu" else 0
    check(calls == want, f"the step calls the rope kernel {calls} times, not {want}")
    return calls


def check_step_runs_the_kda_kernels(programs: dict, lm) -> dict:
    """The step program among ``programs`` runs, for each of ``lm``'s
    linear-attention blocks, the forward chunk kernel twice (forward, and
    the rematted block's second forward, which keeps every chunk's starting
    state), the backward kernel once, and no loop. Off the chip the core is
    the scan: no kernel, three loops a block."""
    import jax

    calls = kda_kernel_calls(programs["train_step"].as_text())
    n = lm.kda_layers
    want = ({"fwd": 2 * n, "bwd": n, "loops": 0} if jax.default_backend() == "tpu"
            else {"fwd": 0, "bwd": 0, "loops": 3 * n})
    check(calls == want, f"the step runs the linear-attention core {calls}, not {want}")
    return calls


def check_step_runs_the_short_conv_kernels(programs: dict, lm) -> dict:
    """The step program among ``programs`` sends q, k and v of each of
    ``lm``'s linear-attention blocks through the short-convolution kernels:
    three forward calls a block, three under its remat, three backward. Off
    the chip the filter, SiLU and norm are the plain composition: no kernel."""
    import jax

    calls = short_conv_kernel_calls(programs["train_step"].as_text())
    want = dict.fromkeys(calls, 3 * lm.kda_layers if jax.default_backend() == "tpu" else 0)
    check(calls == want, f"the step runs the short-convolution kernels {calls}, not {want}")
    return calls


def check_step_runs_each_causal_kernel_once_a_block(programs: dict, lm) -> dict:
    """The step program among ``programs`` runs each of the causal core's
    kernels once for each of ``lm``'s latent-attention blocks: a rematted block keeps the
    forward kernel's output and log-sum-exp, so a second forward run says the
    remat policy lost the two names. Off the chip the core resolves to its
    einsum form and the step holds no kernel at all."""
    import jax

    check("train_step" in programs, "cli.train noted no step program")
    calls = causal_kernel_calls(programs["train_step"].as_text())
    # a linear-attention block and a short-convolution block have no causal core
    cores = sum(kind not in ("kda", "conv") for kind in lm.kinds) + lm.mtp_layers
    want = cores if jax.default_backend() == "tpu" else 0
    check(set(calls.values()) == {want},
          f"the step calls the causal kernels {calls}, not {want} times each")
    return calls


def check_step_runs_the_block_diffusion_core(programs: dict, lm) -> dict:
    """The step program's text calls the causal kernels under the ``bd_core``
    scope once each a block of a block-diffusion model on the TPU (the
    rematted forward dead, as the causal core's: PR 30), and never in a
    causal family's step or off the chip."""
    import jax

    calls = bd_kernel_calls(programs["train_step"].as_text())
    on_chip = bool(lm.diffusion_block) and jax.default_backend() == "tpu"
    want = lm.layers if on_chip else 0
    check(calls == {"fwd": want, "bwd": want},
          f"the step calls the causal kernels under bd_core {calls}, not {want} times each")
    return calls


@contextlib.contextmanager
def one_noise_draw():
    """Inside, every step of a block-diffusion model draws its noise from one
    key, whatever its ``noise`` stream hands it: a batch's second visit is then
    under the levels and masks of its first, and its loss has to fall as a
    causal model's does. The program has no option for this; the smoke puts
    itself between the model and ``ops/masking.block_noise``."""
    import jax
    from jumbo_mae_tpu_tpu.models import lm

    real = lm.block_noise
    lm.block_noise = lambda key, *sizes: real(jax.random.key(0), *sizes)
    try:
        yield
    finally:
        lm.block_noise = real


def phase_lm_train(recipe: str, overrides: list[str], out_dir: Path, *, steps: int) -> dict:
    """``cli.train`` on the language-model recipe for ``steps`` steps of
    seeded tokens, every step's metrics logged: every loss finite; each of
    the cycled batches' loss lower the second time it is seen; nothing
    dropped by an expert layer, whose held pairs fit one round of its chunk
    at the recipe's routing; no step skipped by the guard; the step program
    runs each of the causal core's kernels once a latent-attention block,
    the chunk kernels (forward twice, backward once) a linear-attention
    block, the rope kernel six times a grouped-query block and each head's
    product three times, all in the forward pass; where the
    recipe has linear-attention layers, their counters are logged on every
    step and their states stay bounded. A block-diffusion recipe's steps all
    draw one noise (``one_noise_draw``), so that its loss falls from visit to
    visit too; its first loss and masked share are held to the objective's,
    its core's kernels counted under ``bd_core``, and the one round is not
    asked of it (below). ``recipe`` is any language family's
    (``--lm-recipe``)."""
    from jumbo_mae_tpu_tpu.cli import train as cli_train
    from jumbo_mae_tpu_tpu.models.lm import MlaMoeConfig
    from jumbo_mae_tpu_tpu.obs.trace import format_setup_report, keeping_programs, setup_report
    from jumbo_mae_tpu_tpu.ops.masking import BLOCK_NOISE_EPS

    cfg = _load(recipe, overrides)
    lm = MlaMoeConfig(**cfg.model.lm)
    before, t0 = _registry_snapshot(), time.perf_counter()
    # the trainer's step dies with its loop; a block-diffusion recipe's steps all
    # draw one noise, so that a batch's second visit is under its first's masks
    with keeping_programs() as programs, (
            one_noise_draw() if lm.diffusion_block else contextlib.nullcontext()):
        cli_train.main(_train_argv(recipe, overrides, out_dir))
    after = _registry_snapshot()
    for line in format_setup_report(setup_report(t0), min_s=0.25):
        print(f"[lm_train] {line}", flush=True)  # this phase's records alone, set-up and steps
    calls = check_step_runs_each_causal_kernel_once_a_block(programs, lm)
    kda_calls = check_step_runs_the_kda_kernels(programs, lm)
    conv_calls = check_step_runs_the_short_conv_kernels(programs, lm)
    rope_calls = check_step_runs_the_rope_kernel(programs, lm)
    head_calls = check_step_runs_the_head_three_times(
        programs, lm, cfg.run.train_batch_size * cfg.data.seq_len, cfg.data.seq_len)
    bd_calls = check_step_runs_the_block_diffusion_core(programs, lm)
    programs.clear()  # or the step's executable outlives the phase
    records = _read_metrics(out_dir / cfg.run.name)
    losses = _logged_losses(records, 1, steps, "lm_train")
    cycle = 8  # data/synthetic.token_batches cycles 8 distinct batches
    check(all(losses[i + cycle] < losses[i] for i in range(1, steps - cycle + 1)),
          f"loss did not fall from one visit of a batch to the next: {losses}")
    by_step = {int(r["step"]): r for r in records if "train/moe_dropped" in r}
    check(sorted(by_step) == list(range(1, steps + 1)), "missing expert counters")
    check(all(r["train/moe_dropped"] == 0 for r in by_step.values()), "an expert layer dropped pairs")
    rounds = sorted({r["train/moe_rounds"] for r in by_step.values()})
    family = {}  # the family's own counters, where it has any
    if lm.diffusion_block:
        # the first loss is what seeded weights give whatever the noise, ln(rows) times a
        # mean of masked / t that is 1 in expectation, and the masked share a half, each
        # as near as the step's count of diffusion blocks allows (the weights' spread is
        # 1.2 / sqrt(blocks) of the loss, the levels' 0.3 / sqrt(blocks))
        blocks = cfg.run.train_batch_size * cfg.data.seq_len // lm.diffusion_block
        first = losses[1] / math.log(lm.rows[1])
        check(abs(first - 1.0) < max(0.1, 6.0 / math.sqrt(blocks)),
              f"the first loss is {first:.3f} x ln(rows held)")
        masked = [r.get("train/bd_masked_share") for r in by_step.values()]
        mean = (1 + BLOCK_NOISE_EPS) / 2  # of t ~ U[eps, 1]
        check(all(m is not None and abs(m - mean) < max(0.02, 2.0 / math.sqrt(blocks))
                  for m in masked),
              f"the masked share is missing or far from {mean:.3f}: {masked}")
        family = {"bd_masked_share_min_max": [round(min(masked), 4), round(max(masked), 4)],
                  "diffusion_block": lm.diffusion_block}
        # no check of one round: the recipe's q/k norm scales start at 1 (the program has
        # no option for them), and from seeded weights the masked rows, a quarter of all
        # and one embedding row, then route alike and can fill a chunk twice (PERF.md §6,
        # PR 47: 1 or 2 rounds on the chip); the benchmark's cell seeds its own weights
    else:
        check(rounds == [1], f"an expert layer's held pairs took other than one round: {rounds}")
    skipped = _delta(before, after, "train_steps_skipped_total", "")
    check(skipped == 0, f"{skipped} step(s) skipped by the divergence guard")
    if lm.kda_layers:
        states = [r.get("train/kda_state_absmax") for r in by_step.values()]
        check(all(s is not None and 0 < s < 100 for s in states),
              f"a linear-attention state is missing or unbounded: {states}")
        decay = [r["train/kda_decay_mean"] for r in by_step.values()]
        beta = [r["train/kda_beta_max"] for r in by_step.values()]
        negative = [r["train/kda_neg_eig_share"] for r in by_step.values()]
        check(all(0 < x < lm.kda_beta_scale for x in beta)
              and all((x > 0) == (lm.kda_beta_scale > 1) for x in negative),
              f"beta outside (0, {lm.kda_beta_scale}) or its share past 1 off: {beta} {negative}")
        family = {"kda_state_absmax_max": round(max(states), 4),
               "kda_decay_mean_min_max": [round(min(decay), 4), round(max(decay), 4)],
               "kda_beta_max": round(max(beta), 4),
               "kda_neg_eig_share_min_max": [round(min(negative), 4), round(max(negative), 4)]}
    if lm.expert_act == "relu":  # a ReLU gate's zeros are counted, step by step
        zeros = [r.get("train/moe_act_zero_share") for r in by_step.values()]
        check(all(z is not None and 0 < z < 1 for z in zeros),
              f"the experts' share of zero activations is missing or not a share: {zeros}")
        family |= {"moe_act_zero_share_min_max": [round(min(zeros), 4), round(max(zeros), 4)],
                "router_input": lm.router_input}
    retraces = _delta(before, after, "retrace_events_total", "train")
    check(retraces == 0, f"{retraces} unexpected recompile(s) after warmup")
    last = max((r for r in records if "perf/tokens_per_sec_per_chip" in r),
               key=lambda r: r["step"])
    share = [r["train/moe_held_share"] for r in by_step.values()]
    return {
        "steps": steps,
        "loss_first": round(losses[1], 4),
        "loss_after_one_cycle": round(losses[1 + cycle], 4),
        "loss_last": round(losses[steps], 4),
        "moe_dropped": 0,
        "moe_rounds": int(rounds[-1]),
        "causal_kernel_calls": calls,
        "bd_kernel_calls": bd_calls,
        "kda_kernel_calls": kda_calls,
        "short_conv_kernel_calls": conv_calls,
        "rope_kernel_calls": rope_calls,
        "head_product_calls": head_calls,
        "attn_pairs": {kind: {"visited": visited, "needed": needed} for kind, (visited, needed)
                       in lm.attn_pairs(cfg.data.seq_len).items()},
        "attn_heads": {kind: {"held": held, "published": published}
                       for kind, (held, published) in lm.attn_heads().items()},
        "layers_by_kind": lm.layers_by_kind,
        "qk_norm": lm.qk_norm,
        "tie_embeddings": lm.tie_embeddings,
        "moe_held_share_min_max": [round(min(share), 4), round(max(share), 4)],
        "moe_imbalance_max": round(max(r["train/moe_imbalance"] for r in by_step.values()), 3),
        **family,
        "skipped_steps": 0,
        "tokens_per_sec_per_chip": round(last["perf/tokens_per_sec_per_chip"], 1),
        "mfu_trainer_reported": last.get("perf/mfu"),
        "peak_hbm_bytes_this_process": _peak_hbm_bytes(),
    }


def _lm_overrides(steps: int) -> list[str]:
    # the schedule's horizon stays the recipe's (its warm-up is longer than a smoke)
    return ["run.use_wandb=false", "run.sanity_eval=false", f"run.training_steps={steps}",
            f"run.eval_interval={steps}", "run.log_interval=1", "optim.training_steps=100000"]


# ------------------------------------------------------------------ train


def _train_argv(recipe: str, overrides: list[str], out_dir: Path) -> list[str]:
    return ["--config", recipe, "--set", *overrides, f"run.output_dir={out_dir}"]


def _load(recipe: str, overrides: list[str]):
    from jumbo_mae_tpu_tpu.config import load_config

    return load_config(recipe, overrides)


def phase_train(
    recipe: str, overrides: list[str], out_dir: Path, *, steps: int
) -> dict:
    """``cli.train`` for ``steps`` steps ending in one eval and one
    checkpoint save. Every logged loss finite, the last below the first, no
    unexpected recompile; the rates the trainer logs ride along as
    information."""
    from jumbo_mae_tpu_tpu.cli import train as cli_train

    before = _registry_snapshot()
    cli_train.main(_train_argv(recipe, overrides, out_dir))
    after = _registry_snapshot()

    run = _load(recipe, overrides).run
    run_dir = out_dir / run.name
    records = _read_metrics(run_dir)
    losses = _logged_losses(records, 1, steps, "train")
    check(losses[steps] < losses[1],
          f"loss did not fall: {losses[1]:.4f} -> {losses[steps]:.4f}")
    evals = [r for r in records if "val/loss" in r]
    check(len(evals) == 1 and np.isfinite(evals[0]["val/loss"]),
          f"expected one finite eval, got {evals}")
    check((run_dir / "ckpt" / "last").is_dir(), "no checkpoint was saved")
    retraces = _delta(before, after, "retrace_events_total", "train")
    check(retraces == 0, f"{retraces} unexpected recompile(s) after warmup")

    # the rates of the last log window as the trainer logged them (every
    # step fetched, log_interval=1): information, not a benchmark
    last = max((r for r in records if "perf/images_per_sec" in r),
               key=lambda r: r["step"])
    return {
        "steps": steps,
        "loss_first": round(losses[1], 4),
        "loss_last": round(losses[steps], 4),
        "val_loss": round(float(evals[0]["val/loss"]), 4),
        "unexpected_recompiles": 0,
        "images_per_sec_per_chip": round(last["perf/images_per_sec_per_chip"], 1),
        "step_ms": round(1e3 * run.train_batch_size / last["perf/images_per_sec"], 1),
        "mfu_trainer_reported": last.get("perf/mfu"),
        "peak_hbm_bytes_this_process": _peak_hbm_bytes(),
    }


def phase_resume(
    recipe: str, overrides: list[str], out_dir: Path, *,
    start: int, steps: int, watch: CompileWatch,
) -> dict:
    """The same command with ``run.resume=true`` for ``steps`` more: the
    orbax checkpoint restores onto the device, and the second build of the
    step program is a persistent-compile-cache hit."""
    from jumbo_mae_tpu_tpu.cli import train as cli_train
    from jumbo_mae_tpu_tpu.obs.journal import read_merged_journal

    mark = watch.mark()
    cli_train.main(_train_argv(recipe, [*overrides, "run.resume=true"], out_dir))
    compiled = watch.since(mark)

    run_dir = out_dir / _load(recipe, overrides).run.name
    starts = [e for e in read_merged_journal(run_dir) if e.get("type") == "run_start"]
    check(starts[-1].get("resumed") is True and starts[-1].get("start_step") == start,
          f"the run did not resume from step {start}: {starts[-1]}")
    losses = _logged_losses(_read_metrics(run_dir), start + 1, start + steps, "resume")
    step_hits = [p for p in compiled["hit_programs"] if "train_step" in p]
    check(bool(step_hits),
          "the resumed run compiled its step program again instead of finding "
          f"it in the persistent cache (hits: {compiled['hit_programs']})")
    return {
        "resumed_from": start,
        "steps": steps,
        "loss_last": round(losses[start + steps], 4),
        "train_step_cache_hits": len(step_hits),
    }


# ------------------------------------------------------------------ serve


def phase_serve(
    recipe: str, overrides: list[str], out_dir: Path, *,
    requests: int = 48, max_batch: int = 8, replicas: int = 2,
) -> dict:
    """``cli.predict`` features over ``requests`` synthetic images: bf16,
    then int8, then a ``--serve --replicas`` pool (threads of this process).
    Every request answered, features finite, int8 within the tolerance
    ``quant.parity_report`` uses, nothing compiled after warmup."""
    from jumbo_mae_tpu_tpu.cli import predict as cli_predict
    from jumbo_mae_tpu_tpu.infer.bucketing import pow2_rungs
    from jumbo_mae_tpu_tpu.infer.quant import FEATURE_COSINE_MIN, feature_cosine

    base = [
        "--config", recipe, "--task", "features", "--synthetic", str(requests),
        "--max-batch", str(max_batch), "--warmup", "--set", *overrides,
    ]
    ladder = len(pow2_rungs(max_batch))
    dispatches = -(-requests // max_batch)
    task = "features:cls"
    feats: dict[str, np.ndarray] = {}
    result: dict = {"requests": requests, "ladder": ladder}

    for leg, extra in (("bf16", []), ("int8", ["--quant", "int8"])):
        before = _registry_snapshot()
        out = cli_predict.main([*base, *extra, "--out", str(out_dir / f"features_{leg}.npz")])
        after = _registry_snapshot()
        f = np.load(out)["features"]
        check(f.shape[0] == requests, f"{leg}: {f.shape[0]}/{requests} answered")
        check(bool(np.isfinite(f).all()), f"{leg}: non-finite features")
        feats[leg] = f

        compiled = _delta(before, after, "infer_bucket_cache_misses_total", task)
        loaded = _delta(before, after, "infer_warmcache_events_total", "hit")
        resident = _delta(before, after, "infer_bucket_cache_hits_total", task)
        # the warmup builds the ladder (compiled or loaded from the warm
        # cache); every dispatch after it must find its executable resident
        hot_path_compiles = compiled + loaded - ladder
        check(hot_path_compiles == 0 and resident == dispatches,
              f"{leg}: {hot_path_compiles} compile(s) on the request path "
              f"({resident}/{dispatches} dispatches found their executable)")
        result[leg] = {
            "answered": int(f.shape[0]),
            "warmup_compiled": compiled,
            "warmup_loaded": loaded,
            "hot_path_compiles": 0,
        }

    cos = feature_cosine(feats["bf16"], feats["int8"])
    check(float(cos.min()) >= FEATURE_COSINE_MIN,
          f"int8 features drifted: cosine min {cos.min():.5f} < {FEATURE_COSINE_MIN}")
    result["int8_cosine_min"] = round(float(cos.min()), 5)

    if replicas:
        before = _registry_snapshot()
        out = cli_predict.main([
            *base, "--serve", "--replicas", str(replicas),
            "--out", str(out_dir / "features_pool.npz"),
        ])
        after = _registry_snapshot()
        f = np.load(out)["features"]
        check(f.shape[0] == requests, f"pool: {f.shape[0]}/{requests} answered")
        check(bool(np.isfinite(f).all()), "pool: non-finite features")
        retraces = _delta(before, after, "retrace_events_total", "predict")
        check(retraces == 0, f"pool: {retraces} compile(s) after warmup")
        # both replicas serve the weights the direct path served
        cos = feature_cosine(feats["bf16"], f)
        check(float(cos.min()) >= FEATURE_COSINE_MIN,
              f"pool features differ from the direct path: cosine min {cos.min():.5f}")
        result["pool"] = {
            "replicas": replicas,
            "answered": int(f.shape[0]),
            "hot_path_compiles": 0,
        }
    return result


# ------------------------------------------------------------- four chips


def phase_fsdp(
    recipe: str, overrides: list[str], out_dir: Path, *,
    chips: int, steps: int,
) -> dict:
    """``cli.train`` with the state sharded over ``chips`` devices
    (``mesh.fsdp=chips``) against its control in this same process: the same
    seed and global batch on ``mesh.fsdp=1``, where ``create_mesh`` takes
    the first device as a sub-mesh. Losses must agree step for step, the
    state must really be spread, and the step must hold the collectives."""
    import jax

    from jumbo_mae_tpu_tpu.cli import train as cli_train

    losses: dict[int, dict[int, float]] = {}
    in_use: dict[int, dict[str, float]] = {}
    for n in (chips, 1):
        run = [*overrides, "mesh.data=1", f"mesh.fsdp={n}", f"run.name=fsdp{n}"]
        cli_train.main(_train_argv(recipe, run, out_dir))
        losses[n] = _logged_losses(
            _read_metrics(out_dir / f"fsdp{n}"), 1, steps, f"fsdp={n}"
        )
        # the trainer always saves at its last step; one state on disk at a
        # time is enough
        shutil.rmtree(out_dir / f"fsdp{n}" / "ckpt")
        # the finished run's state sits in reference cycles (engine <-> its
        # hooks); collect them so its device memory is free for the next run
        gc.collect()
        # memwatch sampled every device at the last log window, state live
        in_use[n] = dict(_registry_snapshot().get("mem_device_bytes", {}))
    for s in range(1, steps + 1):
        a, b = losses[chips][s], losses[1][s]
        check(abs(a - b) <= LOSS_REL_TOL * abs(b),
              f"step {s}: sharded loss {a:.5f} vs one-chip {b:.5f}")

    result = {
        "chips": chips,
        "steps": steps,
        "loss_sharded": [round(losses[chips][s], 5) for s in range(1, steps + 1)],
        "loss_one_chip": [round(losses[1][s], 5) for s in range(1, steps + 1)],
    }
    if in_use[1]:
        sharded_max = max(in_use[chips].values())
        control = max(in_use[1].values())
        check(sharded_max < control,
              f"per-device bytes in use {sharded_max:.3g} not below the "
              f"one-chip control's {control:.3g}")
        result["bytes_in_use_per_device_sharded"] = int(sharded_max)
        result["bytes_in_use_one_chip"] = int(control)
    else:
        # XLA:CPU reports no memory_stats(); a TPU always does
        check(jax.devices()[0].platform != "tpu", "no device memory stats on a TPU")
        result["bytes_in_use_per_device_sharded"] = "not reported by this backend"

    result.update(_check_fsdp_placement(
        recipe, [*overrides, "mesh.data=1", f"mesh.fsdp={chips}"], chips
    ))
    return result


def _check_fsdp_placement(recipe: str, overrides: list[str], chips: int) -> dict:
    """Build the state and the step program with the trainer's own factories
    and arguments (``cli.train.train`` keeps both to itself) and look at
    where they landed: code that never ran on more than one chip may put
    everything on the first."""
    import jax

    from jumbo_mae_tpu_tpu.cli.train import _example_batch, build_model
    from jumbo_mae_tpu_tpu.data import synthetic_batches
    from jumbo_mae_tpu_tpu.parallel import create_mesh
    from jumbo_mae_tpu_tpu.train import (
        create_sharded_state,
        make_optimizer,
        make_train_step,
    )

    cfg = _load(recipe, overrides)
    run = cfg.run
    mesh = create_mesh(cfg.mesh)
    check(mesh.devices.size == chips, f"mesh spans {mesh.devices.size} devices")
    model, enc_cfg, _ = build_model(cfg)
    tx = make_optimizer(cfg.optim, run.train_batch_size, num_layers=enc_cfg.layers)
    state, sharding = create_sharded_state(
        model, tx, _example_batch(cfg, run.train_batch_size), mesh,
        mode="pretrain", init_seed=run.init_seed, rng_seed=run.seed,
        param_dtype=cfg.optim.param_dtype,
    )

    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    path, largest = max(flat, key=lambda kv: kv[1].size)
    moments = [
        leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(state.opt_state)[0]
        if p[-len(path):] == path and leaf.shape == largest.shape
    ]
    check(len(moments) >= 2, f"found {len(moments)} Adam moments of the largest param")
    for arr in (largest, *moments):
        shards = arr.addressable_shards
        check(len({s.device for s in shards}) == chips,
              f"{jax.tree_util.keystr(path)}: shards on "
              f"{len({s.device for s in shards})} device(s), not {chips}")
        check(len({str(s.index) for s in shards}) == chips
              and all(s.data.nbytes * chips == arr.nbytes for s in shards),
              f"{jax.tree_util.keystr(path)}: shards do not hold 1/{chips} each")

    step = make_train_step(
        mesh, sharding, mode="pretrain", grad_accum=run.grad_accum,
        guard_nonfinite=run.sentinel,
    )
    batch = next(synthetic_batches(run.train_batch_size, cfg.data.image_size, seed=run.seed))
    step(state, {"images": batch["images"]})
    (compiled,) = step.executables.values()
    text = compiled.as_text()
    collectives = {
        op: text.count(f" {op}(") + text.count(f" {op}-start(")
        for op in ("all-gather", "reduce-scatter", "all-reduce")
    }
    check(collectives["all-gather"] > 0, "no all-gather in the sharded step")
    check(collectives["reduce-scatter"] + collectives["all-reduce"] > 0,
          "no gradient reduction collective in the sharded step")
    return {
        "largest_param": jax.tree_util.keystr(path),
        "largest_param_bytes_per_shard": int(largest.addressable_shards[0].data.nbytes),
        "moments_checked": len(moments),
        "collectives": collectives,
    }


# ------------------------------------------------------------------ driver


def run_phase(name: str, fn, out_dir: Path, watch: CompileWatch) -> bool:
    """Run one phase with the entry points' stdout in ``<out>/<name>.log``;
    print its JSON line; never raise."""
    log_path = out_dir / f"{name}.log"
    gc.collect()  # free the previous phase's device memory (see phase_fsdp)
    mark = watch.mark()
    t0 = time.perf_counter()
    line: dict = {"phase": name}
    try:
        with open(log_path, "w") as log, contextlib.redirect_stdout(log):
            checked = fn()
        line |= {"passed": True}
    except (Exception, SystemExit) as e:  # a CLI's SystemExit fails a phase too
        traceback.print_exc(file=sys.stderr)
        tail = log_path.read_text()[-4000:] if log_path.exists() else ""
        print(f"--- tail of {log_path} ---\n{tail}", file=sys.stderr)
        checked = None
        line |= {"passed": False, "error": f"{type(e).__name__}: {e}"[:600]}
    compiled = watch.since(mark)
    line |= {
        "wall_s": round(time.perf_counter() - t0, 1),
        "compile_s": compiled["compile_s"],
        "compiles": compiled["compiles"],
        "cache_hits": compiled["cache_hits"],
        "cache_misses": compiled["cache_misses"],
    }
    if checked is not None:
        line["checked"] = checked
    print(json.dumps(line), flush=True)
    return line["passed"]


# run-control overrides shared by every trainer phase. The recipes count in
# epochs; with dataset_size equal to the global batch an epoch is one step,
# so ``run.epochs`` is the step count. The schedule's horizon is pinned
# (optim.training_steps) so that a resumed run builds the same program.
def _trainer_overrides(batch: int, steps: int, horizon: int) -> list[str]:
    return [
        "run.synthetic_data=true",
        "run.use_wandb=false",
        "run.sanity_eval=false",
        f"run.train_batch_size={batch}",
        f"run.valid_batch_size={batch}",
        f"data.dataset_size={batch}",
        f"run.epochs={steps}",
        f"run.eval_interval={steps}",
        "run.log_interval=1",
        f"optim.training_steps={horizon}",
        "optim.warmup_epochs=1",
    ]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=str(REPO / "runs" / "chip_smoke"),
                    help="work directory (logs, checkpoints, features)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-training phase and its control")
    ap.add_argument("--phases", default="",
                    help="comma-separated names: run only these one-chip phases")
    ap.add_argument("--lm-recipe", default=LM_RECIPE,
                    help="the language-model recipe of the lm_train phase (either family's)")
    args = ap.parse_args(argv)

    from jumbo_mae_tpu_tpu.utils.procenv import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} on {len(devices)} device(s)",
              file=sys.stderr)
        return 2
    out = Path(args.out) / time.strftime("%Y%m%d-%H%M%S")
    out.mkdir(parents=True)
    print(f"chip_smoke: work dir {out}, compile cache {cache}", file=sys.stderr)
    watch = CompileWatch()

    if args.chips == 4:
        steps = 3
        phases = [(
            "fsdp",
            lambda: phase_fsdp(
                H14_RECIPE, _trainer_overrides(64, steps, steps), out,
                chips=4, steps=steps,
            ),
        )]
    else:
        steps, more = 8, 2
        train = _trainer_overrides(128, steps, steps + more)
        resume = _trainer_overrides(128, steps + more, steps + more)
        phases = [
            ("kernels", phase_kernels),
            ("train", lambda: phase_train(L16_RECIPE, train, out, steps=steps)),
            ("resume", lambda: phase_resume(
                L16_RECIPE, resume, out, start=steps, steps=more, watch=watch)),
            ("serve", lambda: phase_serve(L16_RECIPE, [], out)),
            ("lm_kernels", phase_lm_kernels),
            ("lm_train", lambda: phase_lm_train(args.lm_recipe, _lm_overrides(24), out, steps=24)),
        ]
        if args.phases:
            only = args.phases.split(",")
            unknown = set(only) - {name for name, _ in phases}
            if unknown:
                print(f"chip_smoke: unknown phase(s) {sorted(unknown)}", file=sys.stderr)
                return 2
            phases = [(name, fn) for name, fn in phases if name in only]

    passed = {}
    for name, fn in phases:
        if name == "resume" and not passed.get("train"):
            print(json.dumps({"phase": name, "passed": False,
                              "error": "skipped: the train phase failed"}), flush=True)
            passed[name] = False
            continue
        passed[name] = run_phase(name, fn, out, watch)
    if not all(passed.values()):
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
