#!/usr/bin/env python3
"""Time attention fwd+bwd on the real chip: Pallas flash vs XLA einsum.

One (shape, impl, knobs) cell per invocation — the Pallas kernel knobs
(JUMBO_PALLAS_MM_F32, JUMBO_PALLAS_PAD_TO_BLOCK, JUMBO_PALLAS_LANE) are
module-import constants, so each cell gets a fresh process. Use --matrix to
fan a sweep out over subprocesses and collect JSONL. A chip belongs to one
process: the --matrix parent runs its cells one at a time and never imports
jax itself.

    python tools/flash_microbench.py --shape 128,199,16,32 --impl flash
    python tools/flash_microbench.py --matrix --out /tmp/flash_ab.jsonl

Shapes are (batch, seq, heads, head_dim) of the attention input; timing is
value_and_grad of a sum over the output — forward AND both backward
kernels in one number, matching how the train step exercises them.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# (batch, seq, heads, head_dim) — the production attention shapes:
#   dec224: ViT-L/16 MAE decoder at 224px (seq 196+3), the B-scale hot spot
#   enc448 / dec448: 448px long-context legs (encoder keeps 25% + CLS)
SHAPES = {
    "dec224": (128, 199, 16, 32),
    "enc448": (32, 199, 16, 64),
    "dec448": (32, 787, 16, 32),
    "dec448w": (16, 787, 16, 64),
}


def run_cell(args) -> dict:
    sys.path.insert(0, str(REPO))

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit(
            "flash_microbench times Mosaic kernels against the v5e peak: it "
            "needs a TPU"
        )

    from jumbo_mae_tpu_tpu.ops.flash_attention import xla_attention
    from jumbo_mae_tpu_tpu.ops.pallas.attention import pallas_flash_attention

    b, s, h, d = (int(x) for x in args.shape.split(","))
    dtype = jnp.float32 if args.f32_inputs else jnp.bfloat16
    ks = jax.random.split(jax.random.key(0), 3)
    q = (jax.random.normal(ks[0], (b, s, h, d)) * d**-0.5).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, h, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, h, d)).astype(dtype)

    if args.impl == "flash":
        fn = lambda q, k, v: pallas_flash_attention(
            q, k, v, args.block_q, args.block_k
        ).astype(jnp.float32).sum()
    else:
        fn = lambda q, k, v: xla_attention(q, k, v).astype(jnp.float32).sum()

    # Chain the iterations through a lax.scan carry — one program whose N
    # inner attention steps are data-dependent and cannot overlap or be
    # elided — and fetch the outputs, so the timed region holds N kernel
    # executions and one dispatch, not N dispatches.
    grad_fn = jax.value_and_grad(fn, argnums=(0, 1, 2))

    @jax.jit
    def chained(q, k, v):
        def body(carry, _):
            val, grads = grad_fn(carry, k, v)
            return carry + (1e-6 * grads[0]).astype(carry.dtype), val
        _, vals = jax.lax.scan(body, q, None, length=args.iters)
        return vals

    vals = jax.device_get(chained(q, k, v))  # compile + warm, full fetch
    assert all(map(lambda x: x == x, vals)), "non-finite bench values"

    # 100%-MFU floor for the fwd+bwd attention matmuls (5 full score-shaped
    # matmuls' worth fwd+bwd: 2 fwd + ~5 bwd streams ≈ 7·2·b·h·s²·d, but be
    # conservative and floor on the forward pair only).
    floor_ms = (4 * b * h * s * s * d) / 197e12 * 1e3

    times = []
    for _ in range(args.rounds):
        t0 = time.perf_counter()
        vals = jax.device_get(chained(q, k, v))
        times.append((time.perf_counter() - t0) / args.iters * 1000)
    best = min(times)
    return {
        "impl": args.impl,
        "shape": [b, s, h, d],
        "block_q": args.block_q,
        "block_k": args.block_k,
        "mm_f32": os.environ.get("JUMBO_PALLAS_MM_F32") == "1",
        "pad_to_block": os.environ.get("JUMBO_PALLAS_PAD_TO_BLOCK") == "1",
        "ms_fwd_bwd": best,
        "ms_all_rounds": [round(t, 3) for t in times],
        "floor_ms": round(floor_ms, 4),
        "suspect": best < floor_ms,
    }


def run_matrix(args) -> int:
    cells = []
    for name, (b, s, h, d) in SHAPES.items():
        shape = f"{b},{s},{h},{d}"
        cells.append({"name": name, "shape": shape, "impl": "einsum"})
        for blocks in ((256, 256), (512, 512), (128, 128)):
            for mm_f32 in (False, True):
                for pad in (False, True):
                    cells.append(
                        {
                            "name": name,
                            "shape": shape,
                            "impl": "flash",
                            "block_q": blocks[0],
                            "block_k": blocks[1],
                            "mm_f32": mm_f32,
                            "pad": pad,
                        }
                    )
    out_path = Path(args.out) if args.out else None
    for cell in cells:
        env = dict(os.environ)
        env["JUMBO_PALLAS_MM_F32"] = "1" if cell.get("mm_f32") else "0"
        env["JUMBO_PALLAS_PAD_TO_BLOCK"] = "1" if cell.get("pad") else "0"
        cmd = [
            sys.executable, __file__,
            "--shape", cell["shape"],
            "--impl", cell["impl"],
            "--iters", str(args.iters),
            "--rounds", str(args.rounds),
        ]
        if cell["impl"] == "flash":
            cmd += [
                "--block-q", str(cell["block_q"]),
                "--block-k", str(cell["block_k"]),
            ]
        t0 = time.time()
        proc = subprocess.run(
            cmd, env=env, capture_output=True, text=True, timeout=900
        )
        line = None
        for out_line in reversed(proc.stdout.splitlines()):
            if out_line.startswith("{"):
                line = out_line
                break
        record = {
            "name": cell["name"],
            "wall_s": round(time.time() - t0, 1),
            **(json.loads(line) if line else {"error": proc.stderr[-800:]}),
        }
        print(json.dumps(record), flush=True)
        if out_path:
            with out_path.open("a") as f:
                f.write(json.dumps(record) + "\n")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shape", default="128,199,16,32", help="b,s,h,d")
    ap.add_argument("--impl", choices=("flash", "einsum"), default="flash")
    ap.add_argument("--block-q", type=int, default=1024)
    ap.add_argument("--block-k", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--f32-inputs", action="store_true")
    ap.add_argument("--matrix", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.matrix:
        return run_matrix(args)
    print(json.dumps(run_cell(args)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
