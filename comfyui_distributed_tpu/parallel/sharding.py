"""Parameter and activation sharding rules (tensor parallel / FSDP).

The reference has no model sharding at all ("does not combine VRAM",
reference README.md:186-194); on TPU it is table stakes: WAN-14B-class
models need FSDP across a v5p-16 (BASELINE.md config matrix). Rules
here are deliberately simple and compiler-friendly: pick one axis of
each parameter to shard along the model axis, let XLA insert the
all-gathers/reduce-scatters.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import MODEL_AXIS


def fsdp_spec_for(shape: tuple[int, ...], model_axis_size: int) -> P:
    """Shard the largest divisible axis; replicate scalars/vectors that
    don't divide. Deterministic given shape, so save/restore agree."""
    if model_axis_size <= 1 or not shape:
        return P()
    order = sorted(range(len(shape)), key=lambda i: shape[i], reverse=True)
    for axis in order:
        if shape[axis] % model_axis_size == 0 and shape[axis] >= model_axis_size:
            spec: list[Any] = [None] * len(shape)
            spec[axis] = MODEL_AXIS
            return P(*spec)
    return P()


def shard_params(params: Any, mesh: Mesh) -> Any:
    """Place a parameter pytree onto the mesh with FSDP sharding."""
    model_size = int(mesh.shape.get(MODEL_AXIS, 1))

    def place(leaf):
        arr = np.asarray(leaf) if not isinstance(leaf, jax.Array) else leaf
        spec = fsdp_spec_for(tuple(arr.shape), model_size)
        return jax.device_put(arr, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(place, params)


def param_specs(params: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching `shard_params` placement (for use
    as in_shardings of a jitted train/sample step)."""
    model_size = int(mesh.shape.get(MODEL_AXIS, 1))
    return jax.tree_util.tree_map(
        lambda leaf: fsdp_spec_for(tuple(np.shape(leaf)), model_size), params
    )


def replicate(tree: Any, mesh: Mesh) -> Any:
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda leaf: jax.device_put(leaf, sharding), tree)


def expert_range(n_experts: int, rank: int, size: int) -> range:
    """The routed experts that chip `rank` of the `size` chips sharing a
    layer holds: a contiguous run, so that with group-limited routing a
    chip holds whole groups (160 experts in 8 groups over 4 chips: rank
    0 holds experts 0-39, groups 0 and 1)."""
    if size < 1 or not 0 <= rank < size or n_experts % size:
        raise ValueError(
            f"{n_experts} experts do not divide over {size} chips (rank {rank})"
        )
    per_chip = n_experts // size
    return range(rank * per_chip, (rank + 1) * per_chip)


def params_byte_size(params: Any) -> int:
    """Total parameter bytes (as stored) — the numerator of the
    CDT_MESH_HBM_GB auto-TP budget rule."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        size = int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 4)
        total += size * itemsize
    return total


def maybe_shard_params(params: Any, mesh: Mesh | None) -> Any:
    """Shard a checkpoint's parameters along the mesh's model axis
    (tensor parallel) when the mesh has one; otherwise return params
    unchanged. This is how checkpoints exceeding one chip's HBM load
    at all: each chip holds a 1/TP slice and XLA inserts the gathers
    under the same jitted tile processor (docs/performance.md, mesh
    section — TP outputs are allclose, not bit-identical: sharded
    contractions change the reduction order)."""
    if mesh is None or int(mesh.shape.get(MODEL_AXIS, 1)) <= 1:
        return params
    return shard_params(params, mesh)
