"""Tensor parallelism as path-based sharding rules over the ``tp`` mesh axis.

The reference only reaches TP through Megatron-LM's CUDA column/row-parallel
linears (``utils/dataclasses.py:1317``, ``utils/launch.py:258``).  Here TP is a
*placement rule*: project weight matrices onto the ``tp`` axis by parameter path
(Megatron convention — attention qkv and MLP up projections column-parallel,
output projections row-parallel, vocab-parallel embedding) and let XLA insert
the all-gathers/reduce-scatters.  Composes freely with the ``fsdp`` axis: the
dimension not taken by ``tp`` shards over ``fsdp``, covering Megatron+ZeRO-style
2D layouts with zero wrapper code.

Rules are regexes over the ``/``-joined parameter path, so they apply equally to
per-layer params (``layers_3/attn/q_proj/kernel``), scan-stacked params
(``layers/layer/attn/q_proj/kernel`` with a leading layer dim) and the matching
optimizer-state leaves (``opt_state/.../q_proj/kernel``).
"""

from __future__ import annotations

import math
import re
from typing import Any, Callable, Optional, Sequence, Tuple

from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils.dataclasses import FullyShardedDataParallelPlugin
from . import mesh as mesh_lib
from .sharding import _named_sharding, make_opt_sharding_fn, make_param_sharding_fn, supports_host_offload

# (pattern, which of the last two dims takes the tp axis): "out" = column-parallel
# (shard the output features), "in" = row-parallel (shard the reduction dim),
# "vocab" = vocab-parallel embedding (tp AND fsdp stack on the vocab dim; the
# hidden dim stays replicated — fsdp-sharding it forces the embedding-gradient
# scatter to reshard the batch-sharded input cotangent onto the hidden dim,
# which XLA's SPMD partitioner can only do by full rematerialization).
DEFAULT_TP_RULES: Tuple[Tuple[str, str], ...] = (
    (r"(q_proj|k_proj|v_proj|gate_proj|up_proj|lm_head)/kernel$", "out"),
    (r"(o_proj|down_proj)/kernel$", "in"),
    # embedding [vocab, hidden]: vocab-parallel (Megatron VocabParallelEmbedding)
    (r"embed_tokens/embedding$", "vocab"),
)

# Serving variant: column-parallel projections ONLY.  Row-parallel layers
# (``o_proj``/``down_proj`` sharded on the *contracting* dim) finish with a
# psum whose cross-device reduction order differs from the single-device
# matmul — a few-ulp drift that compounds over autoregressive decode steps
# until a greedy argmax flips.  Serving promises token-identical output at
# every tp degree (``tests/test_serving_mesh.py`` holds it bitwise), so those
# layers and the embedding gather stay replicated: every reduction a sharded
# serve executes runs over the same unsharded operands, in the same order,
# as its tp=1 twin.  Column-parallel q/k/v is also what keeps the paged KV
# pool head-sharded end to end — the cache writes land on the shard that
# computed them, no resharding collective in the decode loop.
SERVING_TP_RULES: Tuple[Tuple[str, str], ...] = (
    (r"(q_proj|k_proj|v_proj|gate_proj|up_proj|lm_head)/kernel$", "out"),
)


def path_to_str(path) -> str:
    parts = []
    for p in path:
        name = getattr(p, "name", None)
        if name is None:
            name = getattr(p, "key", None)
        if name is None:
            name = getattr(p, "idx", None)
        parts.append(str(name))
    return "/".join(parts)


def make_tp_sharding_fn(
    mesh: Mesh,
    plugin: Optional[FullyShardedDataParallelPlugin] = None,
    *,
    for_opt_state: bool = False,
    rules: Optional[Sequence[Tuple[str, str]]] = None,
    axis_name: str = "tp",
) -> Callable[[Any, Any], NamedSharding]:
    """Build a ``(path, leaf) -> NamedSharding`` rule with TP + FSDP composition.

    Matrices matching a TP rule shard their tp dimension over ``axis_name`` and
    (when the plugin shards this kind of state) the complementary dimension over
    ``fsdp``.  Everything else falls back to the shape-based FSDP rule.
    """
    tp = mesh_lib.mesh_axis_size(mesh, axis_name)
    fsdp = mesh_lib.mesh_axis_size(mesh, "fsdp")
    compiled = [(re.compile(pat), kind) for pat, kind in (rules or DEFAULT_TP_RULES)]
    if for_opt_state:
        base = make_opt_sharding_fn(mesh, plugin)
        shards_other = plugin is not None and plugin.shards_opt_state and fsdp > 1
        wants_offload = plugin is not None and plugin.offload_optimizer
    else:
        base = make_param_sharding_fn(mesh, plugin)
        shards_other = plugin is not None and plugin.shards_params and fsdp > 1
        wants_offload = plugin is not None and plugin.cpu_offload
    memory_kind = (
        "pinned_host" if (wants_offload and supports_host_offload(mesh)) else None
    )
    min_size = plugin.min_weight_size if plugin is not None else 2**12

    def rule(path, x) -> NamedSharding:
        shape = getattr(x, "shape", ())
        if tp > 1 and len(shape) >= 2:  # noqa: SIM102 (kept flat for readability)
            p = path_to_str(path)
            for pat, kind in compiled:
                if pat.search(p):
                    tp_dim = len(shape) - 1 if kind == "out" else len(shape) - 2
                    if shape[tp_dim] % tp == 0:
                        spec: list = [None] * len(shape)
                        if kind == "vocab":
                            # tp (and fsdp, when it also divides) stack on the
                            # vocab dim; hidden stays replicated (see rule docs)
                            if (
                                shards_other
                                and shape[tp_dim] % (tp * fsdp) == 0
                                and math.prod(shape) >= min_size
                            ):
                                spec[tp_dim] = (axis_name, "fsdp")
                            else:
                                spec[tp_dim] = axis_name
                            return _named_sharding(mesh, PartitionSpec(*spec), memory_kind)
                        other_dim = len(shape) - 2 if kind == "out" else len(shape) - 1
                        spec[tp_dim] = axis_name
                        if (
                            shards_other
                            and shape[other_dim] % fsdp == 0
                            and math.prod(shape) >= min_size
                        ):
                            spec[other_dim] = "fsdp"
                        return _named_sharding(mesh, PartitionSpec(*spec), memory_kind)
                    break  # matched but indivisible: fall through to base rule
        return base(x)

    return rule


def wrap_with_pp_rule(
    rule: Callable[[Any, Any], NamedSharding],
    mesh: Mesh,
    axis_name: str = "pp",
) -> Callable[[Any, Any], NamedSharding]:
    """Compose a pipeline-stage rule over an existing ``(path, leaf)`` rule.

    Scan-stacked layer params (paths under ``layers/``, leading dim = depth)
    shard their depth axis over ``pp`` so each pipeline stage *owns* its layer
    slice at rest — without this, ``pipeline_apply``'s shard_map reshards the
    fsdp-sharded stack onto the pp axis every step (an SPMD full-remat).
    Trailing-dim assignments (tp/fsdp) from the inner rule are kept; in the
    rare case the inner rule claimed dim 0, pp wins (stage locality beats
    intra-stack fsdp for that leaf).
    """
    pp = mesh_lib.mesh_axis_size(mesh, axis_name)
    if pp <= 1:
        return rule

    def pp_rule(path, x) -> NamedSharding:
        inner = rule(path, x)
        shape = getattr(x, "shape", ())
        p = path_to_str(path)
        if "layers/" not in p or not shape or shape[0] % pp != 0:
            return inner
        spec = list(inner.spec) + [None] * (len(shape) - len(inner.spec))
        spec[0] = axis_name
        kwargs = {}
        if getattr(inner, "memory_kind", None) is not None:
            kwargs["memory_kind"] = inner.memory_kind
        return NamedSharding(mesh, PartitionSpec(*spec), **kwargs)

    return pp_rule
