"""Parameter/state sharding rules — FSDP/ZeRO as placement functions.

The reference implements FSDP via torch's flat-param wrapper (``accelerator.py:
1444-1553``) and ZeRO via DeepSpeed config surgery (``:1578-1800``).  Here both are
one mechanism: a rule mapping each array (by shape) to a ``PartitionSpec`` over the
mesh, applied at state-creation time with ``jax.jit(..., out_shardings=...)``.
XLA then emits exactly the FSDP comm pattern (all-gather params on use,
reduce-scatter grads) from the sharding alone.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils.dataclasses import FullyShardedDataParallelPlugin, ShardingStrategy
from ..utils.imports import is_tpu_platform
from . import mesh as mesh_lib


def fsdp_partition_spec(
    shape: Sequence[int],
    fsdp_size: int,
    min_weight_size: int = 2**12,
    axis_name: str = "fsdp",
) -> PartitionSpec:
    """Shard the largest divisible dim over the fsdp axis; small params stay replicated.

    The min-size cutoff is the analog of the reference's size-based auto-wrap policy
    (``utils/constants.py:36``): tiny params cost more to gather than to replicate.
    """
    if fsdp_size <= 1 or not shape or math.prod(shape) < min_weight_size:
        return PartitionSpec()
    order = sorted(range(len(shape)), key=lambda d: shape[d], reverse=True)
    for d in order:
        if shape[d] % fsdp_size == 0:
            spec: list = [None] * len(shape)
            spec[d] = axis_name
            return PartitionSpec(*spec)
    return PartitionSpec()


def expert_partition_spec(
    shape: Sequence[int],
    ep_size: int,
    fsdp_size: int = 1,
    min_weight_size: int = 2**12,
) -> PartitionSpec:
    """Spec for stacked-expert kernels: expert dim over ``ep``, largest matmul
    dim over ``fsdp`` when large enough — expert parallelism composed with
    ZeRO-style intra-expert sharding.

    Expert leaves are vmapped Dense kernels ``[E, in, out]``; under
    ``nn.scan`` an extra layer axis stacks in front (``[L, E, in, out]``), so
    like the TP rules the expert dim is anchored from the *trailing* matmul
    dims: ``ndim - 3``.
    """
    if not shape or ep_size <= 1:
        return fsdp_partition_spec(shape, fsdp_size, min_weight_size)
    expert_dim = max(0, len(shape) - 3)
    if shape[expert_dim] % ep_size != 0:
        return fsdp_partition_spec(shape, fsdp_size, min_weight_size)
    spec: list = [None] * len(shape)
    spec[expert_dim] = "ep"
    if fsdp_size > 1 and math.prod(shape) >= min_weight_size:
        rest = sorted(range(expert_dim + 1, len(shape)), key=lambda d: shape[d], reverse=True)
        for d in rest:
            if shape[d] % fsdp_size == 0:
                spec[d] = "fsdp"
                break
    return PartitionSpec(*spec)


def make_param_sharding_fn(
    mesh: Mesh,
    plugin: Optional[FullyShardedDataParallelPlugin] = None,
) -> Callable[[Any], NamedSharding]:
    """Build shape -> NamedSharding for parameters.

    With ``plugin.cpu_offload`` the sharded params live in ``pinned_host`` memory
    (the ZeRO param-offload analog, reference ``DeepSpeedPlugin.offload_param_device``);
    XLA streams them to HBM on use.
    """
    fsdp_size = mesh_lib.mesh_axis_size(mesh, "fsdp")
    shards_params = plugin is not None and plugin.shards_params and fsdp_size > 1
    memory_kind = "pinned_host" if (plugin is not None and plugin.cpu_offload) else None
    if memory_kind is not None and not supports_host_offload(mesh):
        memory_kind = None

    def rule(x) -> NamedSharding:
        shape = getattr(x, "shape", ())
        spec = (
            fsdp_partition_spec(shape, fsdp_size, plugin.min_weight_size)
            if shards_params
            else PartitionSpec()
        )
        return _named_sharding(mesh, spec, memory_kind)

    return rule


def make_opt_sharding_fn(
    mesh: Mesh,
    plugin: Optional[FullyShardedDataParallelPlugin] = None,
) -> Callable[[Any], NamedSharding]:
    """Optimizer-state rule: sharded whenever the strategy shards opt state (ZeRO>=1).

    Applied by shape, so Adam's ``mu``/``nu`` (param-shaped) shard exactly like the
    matching param would under FULL_SHARD, while scalars stay replicated.  With
    ``plugin.offload_optimizer`` the state lives in ``pinned_host`` memory
    (DeepSpeedCPUAdam analog — XLA fuses the host<->HBM streaming into the step).
    """
    fsdp_size = mesh_lib.mesh_axis_size(mesh, "fsdp")
    shards_opt = plugin is not None and plugin.shards_opt_state and fsdp_size > 1
    min_size = plugin.min_weight_size if plugin is not None else 2**12
    # the nvme tier keeps opt state on DISK (utils/chunked_update.DiskChunkStore),
    # not pinned host memory — chunk programs get plain device placements
    on_disk = plugin is not None and getattr(plugin, "offload_optimizer_nvme_path", None)
    memory_kind = (
        "pinned_host"
        if (plugin is not None and plugin.offload_optimizer and not on_disk)
        else None
    )
    if memory_kind is not None and not supports_host_offload(mesh):
        memory_kind = None

    def rule(x) -> NamedSharding:
        shape = getattr(x, "shape", ())
        spec = fsdp_partition_spec(shape, fsdp_size, min_size) if shards_opt else PartitionSpec()
        return _named_sharding(mesh, spec, memory_kind)

    return rule


def supports_host_offload(mesh: Mesh) -> bool:
    """Host-memory state offload needs the TPU runtime (XLA's CPU SPMD partitioner
    rejects host-placed jit outputs; verified empirically)."""
    try:
        dev = next(iter(np.asarray(mesh.devices).flat))
    except StopIteration:
        return False
    return is_tpu_platform(dev.platform)


def _named_sharding(mesh: Mesh, spec: PartitionSpec, memory_kind: Optional[str]) -> NamedSharding:
    # Trailing Nones dropped: jit reports its outputs' shardings that way, and
    # ``P("fsdp", None) != P("fsdp")`` — a state placed with the long spelling
    # comes back from its first step with the short one, and the second step
    # compiles again for the "new" input sharding.
    dims = list(spec)
    while dims and dims[-1] is None:
        dims.pop()
    spec = PartitionSpec(*dims)
    if memory_kind is None:
        return NamedSharding(mesh, spec)
    return NamedSharding(mesh, spec, memory_kind=memory_kind)


def shard_pytree(tree, rule: Callable[[Any], NamedSharding]):
    """Place a host pytree onto the mesh according to ``rule`` (jitted identity).

    Using a jitted identity with ``out_shardings`` (instead of ``device_put`` per
    leaf) lets XLA batch the transfers and works for abstract init too.
    """
    shardings = jax.tree_util.tree_map(rule, tree)
    return jax.jit(lambda t: t, out_shardings=shardings)(tree), shardings


def shard_pytree_with_path(tree, rule):
    """Like :func:`shard_pytree` but for *path-aware* rules ``(path, leaf) ->
    NamedSharding`` (e.g. :func:`..tensor_parallel.make_tp_sharding_fn`), which
    need the param name to pick the sharded dim."""
    shardings = jax.tree_util.tree_map_with_path(rule, tree)
    return jax.jit(lambda t: t, out_shardings=shardings)(tree), shardings


def sharding_of(tree):
    return jax.tree_util.tree_map(lambda x: x.sharding if isinstance(x, jax.Array) else None, tree)
