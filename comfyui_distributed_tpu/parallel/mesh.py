"""Device mesh construction and TPU topology enumeration.

The TPU-native replacement for the reference's worker registry of
CUDA devices (reference workers/detection.py + api/worker_routes.py
`_get_cuda_info`): participants inside a slice are logical indices
along the mesh's "data" axis, and model sharding (tensor / FSDP) uses
the "model" axis. Multi-host pods extend the same mesh over DCN via
jax.distributed initialization.

Axis conventions used throughout the framework:
    data   — seed/batch replication axis (one "worker" per index)
    model  — tensor/FSDP sharding axis within a participant
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.exceptions import MeshError
from ..utils.logging import debug_log

DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape: axis name → size (-1 = infer remainder)."""

    axes: dict[str, int]

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = dict(self.axes)
        unknown = [name for name, size in sizes.items() if size == -1]
        if len(unknown) > 1:
            raise MeshError(f"at most one -1 axis allowed, got {unknown}")
        known = math.prod(s for s in sizes.values() if s != -1)
        if unknown:
            if known == 0 or n_devices % known != 0:
                raise MeshError(
                    f"cannot infer axis {unknown[0]}: {n_devices} devices not divisible by {known}"
                )
            sizes[unknown[0]] = n_devices // known
        if math.prod(sizes.values()) != n_devices:
            raise MeshError(
                f"mesh {sizes} does not cover {n_devices} devices"
            )
        return sizes


def local_device_count() -> int:
    return jax.local_device_count()


def build_mesh(
    spec: MeshSpec | dict[str, int] | None = None,
    devices: Sequence[Any] | None = None,
) -> Mesh:
    """Build a named mesh over the given (default: all) devices.

    Default layout is a pure data mesh — every chip is one participant,
    the TPU analog of the reference's one-worker-per-GPU auto-populate
    (reference web/masterDetection.js:36-104, done UI-side there;
    runtime-side here).
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if not devices:
        raise MeshError("no devices available")
    if spec is None:
        spec = MeshSpec({DATA_AXIS: -1, MODEL_AXIS: 1})
    elif isinstance(spec, dict):
        spec = MeshSpec(dict(spec))
    sizes = spec.resolve(len(devices))
    names = tuple(sizes.keys())
    shape = tuple(sizes[n] for n in names)
    dev_array = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(dev_array, names)


def shard_map_compat(fn, *, mesh: Mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with this codebase's argument names; every
    mesh-tier call site routes through here."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check,
    )


def data_axis_size(mesh: Mesh) -> int:
    return int(mesh.shape.get(DATA_AXIS, 1))


def model_axis_size(mesh: Mesh) -> int:
    return int(mesh.shape.get(MODEL_AXIS, 1))


def _parse_mesh_shape(raw: str | None) -> dict[str, int] | None:
    """``CDT_MESH_SHAPE`` grammar: ``"<data>,<model>"`` (e.g. ``"4,1"``,
    ``"-1,2"``; -1 infers the remainder) or a single ``"<data>"``.
    Unset is None (auto layout); a value that does not parse raises —
    an operator who set it did not ask for the auto layout."""
    if not raw:
        return None
    parts = [p.strip() for p in raw.split(",") if p.strip()]
    try:
        sizes = [int(p) for p in parts]
    except ValueError:
        sizes = []
    if not sizes or len(sizes) > 2:
        raise MeshError(
            f"CDT_MESH_SHAPE={raw!r}: expected '<data>' or '<data>,<model>'"
        )
    if len(sizes) == 1:
        sizes.append(1)
    return {DATA_AXIS: sizes[0], MODEL_AXIS: sizes[1]}


def worker_mesh(
    params_bytes: int | None = None,
    devices: Sequence[Any] | None = None,
) -> Mesh | None:
    """The production tile tier's local mesh, resolved from the
    CDT_MESH_SHAPE / CDT_TP_SIZE knob pair (plus the CDT_MESH_HBM_GB
    auto-TP budget rule when ``params_bytes`` is known).

    Default (no knobs set): a pure data mesh over all local chips on
    accelerator platforms — every chip services tile grants, so a
    4-chip worker advertises 4x grant capacity. On CPU the default is
    None (single-participant, the historical loop): forced host
    devices are a test construction, and auto-fanning the elastic tier
    across them would silently change the golden-exact K=1 path. CPU
    meshes are opt-in via the knobs (the mesh-parity suite does).

    Returns None when the resolved mesh would be a single participant
    with no model sharding — callers then take the unsharded path.
    A backend that cannot start, or knobs that do not fit the host,
    raise: one silent participant on a four-chip host is a quarter of
    the machine nobody knows is missing.
    """
    if devices is None:
        devices = jax.local_devices()
    devices = list(devices)
    n = len(devices)
    shape = _parse_mesh_shape(os.environ.get("CDT_MESH_SHAPE"))
    try:
        tp = int(os.environ.get("CDT_TP_SIZE", "0"))
    except ValueError:
        tp = 0
    if tp <= 0 and params_bytes:
        tp = auto_tp_size(params_bytes, n)
    if shape is None:
        if devices[0].platform == "cpu" and tp <= 1:
            return None  # opt-in only on CPU (see docstring)
        if n <= 1 and tp <= 1:
            return None
        shape = {DATA_AXIS: -1, MODEL_AXIS: max(1, tp)}
    elif tp > 1:
        # CDT_TP_SIZE overrides only the model entry — an explicit
        # data pin survives unless the combination exceeds the host,
        # in which case the data axis reverts to inferred
        shape = dict(shape, **{MODEL_AXIS: tp})
        if shape[DATA_AXIS] != -1 and shape[DATA_AXIS] * tp > n:
            shape[DATA_AXIS] = -1
    # an explicit shape smaller than the host uses the leading subset
    # of devices (chip pinning for shared hosts); -1 axes span them all
    explicit = math.prod(s for s in shape.values() if s != -1)
    if all(s != -1 for s in shape.values()) and 0 < explicit < n:
        devices = devices[:explicit]
    mesh = build_mesh(shape, devices)
    if data_axis_size(mesh) <= 1 and model_axis_size(mesh) <= 1:
        return None
    return mesh


def auto_tp_size(params_bytes: int, n_devices: int) -> int:
    """The HBM budget rule: the smallest power-of-two model-axis size
    (<= n_devices) whose per-chip parameter share fits CDT_MESH_HBM_GB
    GiB. 0/unset budget disables auto-TP (returns 1) — checkpoints
    that don't fit then fail to load exactly as before, loudly."""
    try:
        budget_gb = float(os.environ.get("CDT_MESH_HBM_GB", "0"))
    except ValueError:
        budget_gb = 0.0
    if budget_gb <= 0 or params_bytes <= 0:
        return 1
    budget = budget_gb * (1 << 30)
    # the data axis infers as n/tp, so tp must also divide n — on a
    # 6-chip host the ladder is 1, 2, never 4 or 8
    max_tp = 1
    while max_tp * 2 <= n_devices and n_devices % (max_tp * 2) == 0:
        max_tp *= 2
    tp = 1
    while tp < max_tp and params_bytes / tp > budget:
        tp *= 2
    if params_bytes / tp > budget:
        # even the widest divisible TP is over budget: proceed (the
        # load may still fit — the budget is a conservative rule) but
        # say so, or an OOM here looks like the rule never fired
        debug_log(
            f"auto_tp_size: {params_bytes / (1 << 30):.1f} GiB / tp={tp} "
            f"still exceeds CDT_MESH_HBM_GB={budget_gb:g} per-chip budget"
        )
    return tp


def mesh_summary(mesh: Mesh | None) -> dict[str, int]:
    """Compact mesh shape for telemetry/status surfaces."""
    if mesh is None:
        return {"data": 1, "model": 1, "devices": 1}
    return {
        "data": data_axis_size(mesh),
        "model": model_axis_size(mesh),
        "devices": int(mesh.size),
    }


_serving_mesh_summary: dict[str, int] | None = None
# knob-only fallback cache, keyed by the knob values so env changes
# (tests, operator retunes) invalidate it: (knobs, summary)
_fallback_mesh_summary: tuple[tuple, dict[str, int]] | None = None


def note_serving_mesh(mesh: Mesh | None) -> None:
    """Record the mesh actually constructed to serve tile grants (the
    elastic loops call this at startup). Status surfaces must report
    THIS shape, not a knob-only ``worker_mesh()`` re-derivation — the
    two differ exactly when the auto-TP budget rule needed
    ``params_bytes`` (a checkpoint over budget shrinks the data axis,
    and with it the advertised capacity)."""
    global _serving_mesh_summary
    _serving_mesh_summary = mesh_summary(mesh)


def serving_mesh_summary() -> dict[str, int]:
    """The recorded serving mesh, falling back to a knob-only
    ``worker_mesh()`` resolution when no elastic loop has run in this
    process yet. The fallback is cached per knob values — status
    surfaces poll continuously and must not construct a throwaway Mesh
    per request."""
    if _serving_mesh_summary is not None:
        return dict(_serving_mesh_summary)
    global _fallback_mesh_summary
    knobs = tuple(
        os.environ.get(k)
        for k in ("CDT_MESH_SHAPE", "CDT_TP_SIZE", "CDT_MESH_HBM_GB")
    )
    if _fallback_mesh_summary is None or _fallback_mesh_summary[0] != knobs:
        _fallback_mesh_summary = (knobs, mesh_summary(worker_mesh()))
    return dict(_fallback_mesh_summary[1])


def advertised_capacity(mesh: Mesh | None) -> int:
    """Grant capacity a worker reports to the master's placement
    policy: the data-axis width of its mesh (chips servicing tile
    fan-out; model-axis chips serve the same tiles, not more of them).
    1 without a mesh — the historical single-participant worker."""
    return data_axis_size(mesh) if mesh is not None else 1


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharded(mesh: Mesh, ndim: int = 4) -> NamedSharding:
    """Shard the leading (batch) axis across participants."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def describe_topology() -> dict[str, Any]:
    """Enumerate local accelerator topology for the control plane.

    The TPU replacement for the reference's `/distributed/system_info`
    CUDA enumeration (api/worker_routes.py:237-274): platform, chip
    ids, kind, coords, memory, process index, any chip-visibility
    pinning, and the versions of the packages that make up the
    backend.
    """
    import importlib.metadata as metadata

    devices = jax.devices()
    local = jax.local_devices()
    versions = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            versions[dist] = metadata.version(dist)
        except metadata.PackageNotFoundError:
            versions[dist] = None
    info: dict[str, Any] = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "local_device_count": len(local),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "versions": versions,
        "devices": [],
    }
    for dev in local:
        entry: dict[str, Any] = {
            "id": dev.id,
            "platform": dev.platform,
            "process_index": dev.process_index,
            "device_kind": dev.device_kind,
        }
        for attr in ("coords", "core_on_chip"):
            value = getattr(dev, attr, None)
            if value is not None:
                entry[attr] = value
        stats = dev.memory_stats()
        if stats is not None:
            entry["memory_stats"] = stats
        info["devices"].append(entry)
    return info
