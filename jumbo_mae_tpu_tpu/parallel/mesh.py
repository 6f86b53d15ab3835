"""Device-mesh construction.

The reference's entire distributed story was ``jax.pmap(axis_name="batch")``
(``/root/reference/src/pretraining.py:125``) — pure data parallelism. Here the
runtime is an explicit ``jax.sharding.Mesh`` with up to four axes:

- ``data``  — batch sharding across slices/hosts (DCN-friendly outer axis);
- ``fsdp``  — batch sharding *and* parameter/optimizer sharding (ZeRO-3
  style), laid out on ICI;
- ``tensor`` — reserved for tensor-parallel experiments (size 1 by default);
- ``seq``   — sequence/context parallelism for ring attention (size 1 unless
  long-context is requested).

GSPMD inserts all-reduce / reduce-scatter / all-gather over the right fabric
from the sharding annotations; nothing in the framework issues collectives by
hand except the ``shard_map`` ring-attention path.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

from jumbo_mae_tpu_tpu.obs.trace import SPAN_MESH_BUILD, spanned

AXES = ("data", "fsdp", "tensor", "seq")


@dataclass(frozen=True)
class MeshConfig:
    """Axis sizes; -1 on ``fsdp`` means "all remaining devices".

    ``pipe > 1`` selects pipeline parallelism instead: the runtime builds a
    ``(data, pipe)`` mesh (``create_pipeline_mesh``) and streams
    ``pipe_microbatches`` microbatches through the GPipe schedule
    (``parallel/pipeline.py``). Mutually exclusive with fsdp/tensor/seq > 1.
    """

    data: int = 1
    fsdp: int = -1
    tensor: int = 1
    seq: int = 1
    pipe: int = 1
    pipe_microbatches: int = 0  # 0 → defaults to the pipe size
    # pretrain only: also depth-shard the MAE decoder stack over ``pipe``
    # (the pipe size must divide dec_layers)
    pipe_decoder: bool = False

    def validate_pipe(self) -> None:
        if self.pipe > 1 and any(
            s not in (1, -1) for s in (self.fsdp, self.tensor, self.seq)
        ):
            raise ValueError(
                "mesh.pipe composes with mesh.data only; set fsdp/tensor/seq "
                "to 1 (pipeline + FSDP/TP/SP composition is not wired)"
            )

    def resolve(self, n_devices: int) -> tuple[int, int, int, int]:
        if self.pipe > 1:
            # A flat (data, fsdp, tensor, seq) mesh cannot express pipeline
            # parallelism; silently dropping the knob would waste the pipe
            # axis. Callers must route through create_pipeline_mesh (the
            # CLI does: cli/train.py mesh.pipe branch).
            raise ValueError(
                "MeshConfig.pipe > 1 selects pipeline parallelism — build "
                "the mesh with create_pipeline_mesh, not create_mesh/resolve"
            )
        sizes = [self.data, self.fsdp, self.tensor, self.seq]
        if sizes.count(-1) > 1:
            raise ValueError("at most one mesh axis may be -1")
        known = int(np.prod([s for s in sizes if s != -1]))
        if -1 in sizes:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {known}"
                )
            sizes[sizes.index(-1)] = n_devices // known
        if int(np.prod(sizes)) > n_devices:
            raise ValueError(
                f"mesh {dict(zip(AXES, sizes))} needs more than the "
                f"{n_devices} available devices"
            )
        return tuple(sizes)  # type: ignore[return-value]


def plan_hybrid_mesh(
    sizes: tuple[int, int, int, int], n_slices: int
) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """Split the resolved axis sizes into (per-slice ICI shape, DCN shape)
    for a multislice deployment: only the ``data`` axis may span slices
    (the slow DCN fabric carries gradient all-reduce, which overlaps well),
    while fsdp/tensor/seq — whose collectives sit on the critical path —
    stay inside a slice on ICI."""
    data, fsdp, tensor, seq = sizes
    if data % n_slices:
        raise ValueError(
            f"data axis ({data}) must be divisible by the slice count "
            f"({n_slices}) — only the data axis spans DCN"
        )
    return (data // n_slices, fsdp, tensor, seq), (n_slices, 1, 1, 1)


def mesh_strategy(slice_ids: list[int], sizes: tuple[int, int, int, int]) -> str:
    """Decide how to lay devices out: ``"hybrid"`` (slice-aligned
    ICI×DCN mesh) only when every slice is fully used AND the data axis is
    divisible by the slice count; otherwise ``"flat"`` — which always works
    (it is the pre-multislice behavior), just with suboptimal fabric
    placement, so a default config never hard-fails on multislice hardware.
    """
    n_slices = len(set(slice_ids))
    if n_slices <= 1:
        return "flat"
    per_slice_counts = {s: slice_ids.count(s) for s in set(slice_ids)}
    if len(set(per_slice_counts.values())) != 1:
        return "flat"  # truncated sub-mesh straddles a slice boundary
    if sizes[0] % n_slices:
        return "flat"
    return "hybrid"


@spanned(SPAN_MESH_BUILD)
def create_mesh(
    config: MeshConfig | None = None, devices: list | None = None
) -> Mesh:
    """Build the global mesh. Axis order is (data, fsdp, tensor, seq) —
    outermost axis maps to the slowest fabric (DCN between slices), innermost
    to ICI neighbors, matching ``mesh_utils.create_device_mesh`` conventions.

    Multislice (DCN) is detected from the devices' ``slice_index``: with more
    than one slice the mesh is built with ``create_hybrid_device_mesh`` so
    slice boundaries land exactly on the data axis — a flat
    ``create_device_mesh`` would interleave slices and put fsdp/tensor
    collectives onto DCN.
    """
    devices = devices if devices is not None else jax.devices()
    config = config or MeshConfig()
    sizes = config.resolve(len(devices))
    n_used = int(np.prod(sizes))
    devices = devices[:n_used]  # explicit sub-mesh (tests, single-chip bench)
    from jax.experimental import mesh_utils

    slice_ids = [getattr(d, "slice_index", 0) for d in devices]
    strategy = mesh_strategy(slice_ids, sizes)
    n_slices = len(set(slice_ids))
    if strategy == "hybrid":
        per_slice, dcn = plan_hybrid_mesh(sizes, n_slices)
        dev_array = mesh_utils.create_hybrid_device_mesh(
            per_slice, dcn, devices=devices
        )
    else:
        if n_slices > 1:
            print(
                f"[mesh] WARNING: {n_slices} slices but mesh "
                f"{dict(zip(AXES, sizes))} is not slice-aligned (data axis "
                f"must be a multiple of {n_slices} and use every device); "
                "building a flat mesh — fsdp/tensor collectives may ride DCN"
            )
        if n_used == 1:
            dev_array = np.array(devices).reshape(sizes)
        else:
            dev_array = mesh_utils.create_device_mesh(sizes, devices=devices)
    return Mesh(dev_array, AXES)
