"""Global device-mesh state — the TPU-native replacement for the
reference's communication-group machinery (SURVEY.md §2.3 TPU mapping).

Where the reference builds ProcessGroupNCCL rings per topology axis, here
``fleet.init`` (or auto-parallel) installs ONE ``jax.sharding.Mesh`` with
named axes (``dp``, ``sharding``, ``sep``, ``mp`` — pipeline stages get
per-stage sub-meshes) and layers place/constrain arrays with
``PartitionSpec``s; XLA GSPMD inserts the ICI collectives.
"""
from __future__ import annotations

import math

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_GLOBAL_MESH: Mesh | None = None


def set_mesh(mesh: Mesh | None):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_mesh() -> Mesh | None:
    return _GLOBAL_MESH


def has_mesh() -> bool:
    return _GLOBAL_MESH is not None


def mesh_axis_size(axis: str) -> int:
    if _GLOBAL_MESH is None or axis not in _GLOBAL_MESH.shape:
        return 1
    return int(_GLOBAL_MESH.shape[axis])


def data_axes(n: int):
    """The data-parallel mesh axes (``dp``, then ZeRO's ``sharding``) a
    batch dim of ``n`` rows divides over, as a PartitionSpec entry: what
    the per-shard kernels split their batch over and what the model
    constrains its hidden stream's batch to."""
    axes = [a for a in ("dp", "sharding") if mesh_axis_size(a) > 1]
    while axes and n % math.prod(mesh_axis_size(a) for a in axes):
        axes.pop()
    return tuple(axes) or None


def axis_groups(mesh: Mesh | None = None):
    """``{axis: groups of partition ids}`` of ``mesh`` (default: the
    installed one): a partition's id is its place in the mesh's flat
    device order, which is what a compiled collective's
    ``replica_groups`` count in; a group of ``axis`` holds the ids that
    differ along that axis alone. What the profiler's reader matches a
    collective against (``profiler.load_profiler_result(...,
    mesh_axes=)``); plain lists, so it can be written down beside a saved
    trace. Axes of size 1 are left out."""
    mesh = _GLOBAL_MESH if mesh is None else mesh
    if mesh is None:
        return {}
    ids = np.arange(mesh.devices.size).reshape(mesh.devices.shape)
    return {name: np.moveaxis(ids, k, -1).reshape(-1, ids.shape[k]).tolist()
            for k, name in enumerate(mesh.axis_names) if ids.shape[k] > 1}


class MeshScope:
    """Temporarily install a mesh (used by per-stage pipeline execution)."""

    def __init__(self, mesh):
        self._mesh = mesh

    def __enter__(self):
        global _GLOBAL_MESH
        self._saved = _GLOBAL_MESH
        _GLOBAL_MESH = self._mesh
        return self._mesh

    def __exit__(self, *exc):
        global _GLOBAL_MESH
        _GLOBAL_MESH = self._saved
        return False


# pass-through marker for constraint(): "leave this dim's sharding to the
# propagation pass" (valid only under a trace; eager constraint is identity)
UNCONSTRAINED = PartitionSpec.UNCONSTRAINED


def _named_sharding(spec):
    if _GLOBAL_MESH is None:
        return None
    if not isinstance(spec, PartitionSpec):
        spec = PartitionSpec(*spec)
    # drop axis names the mesh doesn't have (e.g. sep unused)
    cleaned = []
    for entry in spec:
        if entry is None:
            cleaned.append(None)
        elif entry is PartitionSpec.UNCONSTRAINED:
            cleaned.append(entry)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in _GLOBAL_MESH.shape)
            cleaned.append(kept if kept else None)
        else:
            cleaned.append(entry if entry in _GLOBAL_MESH.shape else None)
    return NamedSharding(_GLOBAL_MESH, PartitionSpec(*cleaned))


def _divisible(value, spec):
    """Check every sharded dim divides by the axis size product."""
    if _GLOBAL_MESH is None:
        return False
    shape = np.shape(value)
    for dim, entry in enumerate(spec):
        if entry is None or entry is PartitionSpec.UNCONSTRAINED:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        size = 1
        for a in axes:
            size *= int(_GLOBAL_MESH.shape.get(a, 1))
        if size > 1 and (dim >= len(shape) or shape[dim] % size != 0):
            return False
    return True


def spec_axes(spec):
    """Flatten a PartitionSpec (or spec tuple) into the mesh-axis names
    it uses, in order; UNCONSTRAINED and None entries contribute none."""
    out = []
    for entry in spec:
        if entry is None or entry is PartitionSpec.UNCONSTRAINED:
            continue
        out.extend((entry,) if isinstance(entry, str) else entry)
    return out


def merged_dim0_spec(shape, base_spec, mesh, axis):
    """Merge ``axis`` into dim 0 of ``base_spec``, MINOR (last in the
    dim-entry tuple): for a TP-sharded tensor this subdivides each ``mp``
    chunk so every device's ZeRO shard is a sub-slice of its own TP
    shard — ``(axis, 'mp')`` would interleave across mp chunks and force
    a cross-device reshard every step. Returns the base spec unchanged
    when dim 0 doesn't divide by the combined axis sizes or ``axis`` is
    already present. Shared by the ZeRO-1/2 optimizer-state placement
    (jit/train.py) and the stage-3 param placement (group_sharded.py)."""
    size = int(mesh.shape.get(axis, 1))
    ndim = len(shape)
    if size <= 1 or ndim == 0:
        return PartitionSpec(*base_spec)
    parts = list(base_spec) + [None] * (ndim - len(base_spec))
    d0 = parts[0]
    existing = () if d0 is None else (
        (d0,) if isinstance(d0, str) else tuple(d0))
    existing_size = 1
    for a in existing:
        existing_size *= int(mesh.shape.get(a, 1))
    if axis not in existing and shape[0] % (size * existing_size) == 0:
        parts[0] = (*existing, axis) if existing else axis
    return PartitionSpec(*parts)


def shard_value(value, *spec):
    """device_put a concrete array with the given PartitionSpec entries
    (falls back to replication for non-divisible dims)."""
    sharding = _named_sharding(spec)
    if sharding is None:
        return value
    if not _divisible(value, tuple(spec)):
        sharding = _named_sharding(())
    return jax.device_put(value, sharding)


def replicate_value(value):
    sharding = _named_sharding(())
    if sharding is None:
        return value
    return jax.device_put(value, sharding)


def constraint(value, *spec):
    """Sharding constraint usable both eagerly and inside traces; identity
    when no mesh is installed (single-device runs stay zero-cost)."""
    sharding = _named_sharding(spec)
    if sharding is None:
        return value
    if isinstance(value, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(value, sharding)
    if any(e is PartitionSpec.UNCONSTRAINED for e in spec):
        # UNCONSTRAINED is a propagation-pass concept; a concrete array
        # already carries its sharding — nothing to do eagerly
        return value
    if not _divisible(value, tuple(spec)):
        return value
    return jax.device_put(value, sharding)
