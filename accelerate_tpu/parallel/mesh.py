"""Device-mesh construction — the substrate for every parallelism strategy.

The reference selects among NCCL/gloo/xla process-group backends
(``state.py:708-760``) and then expresses parallelism as wrapper classes.  Here the
single substrate is a named ``jax.sharding.Mesh``: DP, FSDP/ZeRO, TP, SP, PP, EP are
*axes* of one mesh, and every strategy is a placement rule over those axes
(SURVEY.md §7 design stance).

Axis conventions (used across the whole framework):
  - ``dp``   data parallel (batch dim)
  - ``fsdp`` sharded-data-parallel (params/opt state sharded; batch also sharded)
  - ``tp``   tensor parallel (weight matrices sharded)
  - ``sp``   sequence/context parallel (activations sharded along sequence; ring attention)
  - ``pp``   pipeline stages
  - ``ep``   expert parallel (MoE)

Multi-host: axes listed in ``MeshConfig.dcn_axes`` are laid out across hosts (slow
DCN network); the remaining axes ride ICI.  This is the HYBRID_SHARD topology
(reference ``utils/constants.py:35``) and the standard multi-slice recipe.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MESH_AXES = ("dp", "fsdp", "tp", "sp", "pp", "ep")

# Batch (dim 0) is sharded over every data axis; this spec is reused by the data
# pipeline and the step compiler.
DATA_AXES = ("dp", "fsdp")


def _resolve_axis_sizes(axes: Dict[str, int], n_devices: int) -> Dict[str, int]:
    """Fill -1 axes with the remaining device count; validate the product."""
    sizes = dict(axes)
    fixed = 1
    wild = [k for k, v in sizes.items() if v in (-1, None)]
    for k, v in sizes.items():
        if v not in (-1, None):
            fixed *= v
    if n_devices % fixed != 0:
        raise ValueError(f"Mesh axes {axes} do not divide device count {n_devices}")
    if len(wild) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {wild}")
    if wild:
        sizes[wild[0]] = n_devices // fixed
    elif fixed > n_devices:
        raise ValueError(f"Mesh axes {axes} multiply to {fixed} > device count {n_devices}")
    # fixed < n_devices is allowed: the mesh covers a prefix of the devices
    # (useful for single-device runs and tests on a subset).
    return sizes


def build_mesh(
    axes: Optional[Dict[str, int]] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    dcn_axes: Optional[Dict[str, int]] = None,
    allow_split_physical_axes: bool = False,
) -> Mesh:
    """Build a named mesh.

    With no arguments: all devices on a single ``dp`` axis (plain data parallel —
    the reference's DDP default, ``accelerator.py:1439``).

    Axis order in ``axes`` matters: earlier axes change slowest across the physical
    device order, so put cross-host axes first and bandwidth-hungry axes (``tp``)
    last, adjacent on ICI.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not axes:
        axes = {"dp": n}
    axes = {k: v for k, v in axes.items() if v != 1 or k == "dp"} or {"dp": 1}
    axes = _resolve_axis_sizes(axes, n)
    names = tuple(axes.keys())
    shape = tuple(axes.values())
    used = math.prod(shape)
    if used < n:
        devices = devices[:used]
        n = used
    # CPU test meshes have no interconnect topology; reshape flat so the same
    # config validates on the test rig and lays out physically on real pods.
    is_cpu = all(d.platform == "cpu" for d in devices)

    if dcn_axes:
        # Hybrid mesh: dcn axes across slices/hosts, remaining within a slice.
        unknown = set(dcn_axes) - set(names)
        if unknown:
            raise ValueError(f"dcn_axes {sorted(unknown)} not present in mesh axes {names}")
        for k, dcn in dcn_axes.items():
            if dcn <= 0 or axes[k] % dcn != 0:
                raise ValueError(
                    f"dcn size {dcn} for axis {k!r} must divide its total size {axes[k]}"
                )
        ici_shape = [axes[k] // dcn_axes.get(k, 1) for k in names]
        dcn_shape = [dcn_axes.get(k, 1) for k in names]
        if is_cpu:
            dev_array = np.array(devices).reshape(shape)
        else:
            # On real pods, let genuine slice/config mismatches surface.
            dev_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape,
                dcn_shape,
                devices=devices,
                allow_split_physical_axes=allow_split_physical_axes,
            )
        return Mesh(dev_array, names)

    if is_cpu:
        dev_array = np.array(devices).reshape(shape)
    else:
        try:
            dev_array = mesh_utils.create_device_mesh(
                shape, devices=devices, allow_split_physical_axes=allow_split_physical_axes
            )
        except (ValueError, NotImplementedError, AssertionError):
            dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, names)


def replica_meshes(
    n_replicas: int,
    axes: Optional[Dict[str, int]] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
) -> List[Mesh]:
    """Split the device list into ``n_replicas`` disjoint groups and build one
    mesh per group — the substrate for data-parallel engine replicas behind a
    :class:`~accelerate_tpu.serving.router.ReplicaRouter`.  Each replica mesh
    carries the same ``axes`` (e.g. ``{"tp": 2}``); with ``axes=None`` each
    replica owns a single device."""
    devices = list(devices if devices is not None else jax.devices())
    if n_replicas <= 0:
        raise ValueError(f"n_replicas must be positive, got {n_replicas}")
    per = math.prod((axes or {"dp": 1}).values())
    if per * n_replicas > len(devices):
        raise ValueError(
            f"{n_replicas} replicas x {per} devices/replica exceeds "
            f"{len(devices)} available devices"
        )
    return [
        build_mesh(dict(axes) if axes else {"dp": 1},
                   devices=devices[i * per:(i + 1) * per])
        for i in range(n_replicas)
    ]


def shard_map(f, *, mesh: Mesh, in_specs, out_specs, check_vma: bool = True,
              axis_names=None):
    """``jax.shard_map`` with ``axis_names`` passed only when given (``None``
    means fully manual, which ``jax.shard_map`` spells by omission)."""
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, **kw)


def present_data_axes(mesh: Mesh) -> tuple:
    """The data axes this mesh actually has (size > 1)."""
    return tuple(a for a in DATA_AXES if a in mesh.axis_names and mesh.shape[a] > 1)


def data_partition_spec(mesh: Mesh) -> PartitionSpec:
    """PartitionSpec sharding batch dim 0 over every data axis present in the mesh."""
    present = present_data_axes(mesh)
    if not present:
        return PartitionSpec()
    return PartitionSpec(present)


def data_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, data_partition_spec(mesh))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def num_data_shards(mesh: Mesh) -> int:
    spec = data_partition_spec(mesh)
    if not spec:
        return 1
    axes = spec[0]
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.axis_names else 1


def sp_shardable(mesh: Mesh, batch: int, seq: int) -> bool:
    """Whether a [batch, seq, ...] activation can shard batch-over-data-axes and
    seq-over-sp on this mesh.  Shared gate for the model's sp activation
    constraint and the ring-attention dispatch — shape probes (``model.init``
    with batch 1) and ragged tails fall back to the unsharded computation."""
    if mesh_axis_size(mesh, "sp") <= 1:
        return False
    data_size = math.prod(mesh.shape[a] for a in present_data_axes(mesh)) or 1
    return batch % data_size == 0 and seq % mesh.shape["sp"] == 0
