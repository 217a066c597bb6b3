"""Chunked host-offloaded optimizer updates — the DeepSpeedCPUAdam-parity piece.

Reference ZeRO-Offload (DeepSpeed `offload_optimizer_device="cpu"`,
`accelerator.py:1578-1800` config surgery) exists because accelerator memory
cannot hold params + grads + Adam moments at once; DeepSpeed solves it by
running the update *on the host*.  The TPU-native translation keeps the
update on the VPU but bounds its HBM footprint: the optimizer state lives in
pinned host memory and streams through HBM **one chunk at a time** on sync
steps.

Two mechanisms compose:

1. **Sliced view** (``build_slice_spec`` / ``with_sliced_view``): parameter
   leaves bigger than the chunk budget are split along their leading axis
   into slice sub-leaves — essential for ``scan_layers=True`` models, whose
   whole decoder stack is a handful of depth-stacked leaves (a 1.5B model's
   MLP stack alone carries ~6 GB of moments; leaf granularity cannot bound
   that).  The optimizer state is built over the view, so each slice's
   masters/moments are independent arrays.
2. **Per-chunk masking** (``build_chunked_tx``): the (view-level) transform
   is rebuilt as ``optax.chain(masked(tx, m_0), ..., masked(tx, m_{K-1}))``
   with each mask covering ~``chunk_bytes`` of view leaves.  The chain is
   mathematically identical to the plain tx — every view leaf is updated by
   exactly one member, every member's ``count`` advances on every sync step —
   but its state is a tuple of independent subtrees that can round-trip
   host↔HBM alone.

The trainer applies chunk ``i`` with a jitted program whose extra HBM is
O(chunk): full leaves enter as (alias) arguments, the program slices out just
this chunk's view, streams the chunk's optimizer subtree in from host,
updates, writes the slices back into the leaves, and streams the subtree
out.  ``with_master_weights`` composes underneath, giving the full
ZeRO-Offload memory story: device peak = bf16 params + bf16 grads + O(chunk).
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

# Per parameter element the streamed chunk holds master + two fp32 moments
# plus the transient update — budget 12 bytes/element when sizing groups.
_BYTES_PER_ELEMENT = 12

# Measured per-chunk HBM budget relative to the chunk's 12 B/element state:
# in + out stream copies plus the adam temps run ~4x the chunk footprint, and
# the allocator needs slack on top to avoid thrashing near the limit.  Swept
# on the 2.13B zero3 config on a 16 GB v5e in an earlier round: with an
# ~8.5 GB resident set, 1 GB chunks run 17.2 s/step, 1.47 GB chunks (a
# factor-4 budget) collapse to 42 s/step, 2 GB OOM intermittently.  Factor 6
# lands the adaptive size at the measured optimum.
_CHUNK_TRANSIENT_FACTOR = 6

# The CPU test rig has no HBM and reports no memory_stats(); chunk sizing
# there is exercised against one v5e's worth, labelled as the stand-in it is.
_CPU_STANDIN_HBM_BYTES = 16 << 30


def detect_hbm_bytes(device=None) -> int:
    """Per-device memory capacity: ``memory_stats()['bytes_limit']``, which
    the TPU runtime reports.  A CPU device (the test rig) gets a labelled
    16 GB stand-in; any other device without the statistic raises — chunks
    are never sized against an assumed capacity."""
    device = device if device is not None else jax.devices()[0]
    stats = device.memory_stats()
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    if device.platform == "cpu":
        return _CPU_STANDIN_HBM_BYTES
    raise ValueError(
        f"device {device.device_kind!r} reports no memory_stats()"
        f"['bytes_limit']; pass hbm_bytes= explicitly"
    )


def auto_chunk_bytes(
    params: Any,
    *,
    working_bytes_per_element: int,
    grad_bytes_per_element: int,
    accum_buffer_bytes_per_element: int = 0,
    shard_degree: int = 1,
    overlap: int = 2,
    hbm_bytes: Optional[int] = None,
) -> int:
    """Pick the streamed-chunk size from measured free HBM.

    Per device the resident set is the working params + grad buffer (+ the
    separate accumulation buffer when used), each divided by ``shard_degree``
    (the fsdp axis shards all three).  What remains after a margin for
    activations/executables is split across ``overlap`` in-flight chunks, each
    costing ~``_CHUNK_TRANSIENT_FACTOR`` x its state footprint.  Returns
    GLOBAL chunk bytes (the 12 B/element grouping unit of
    :func:`build_chunked_tx` — sharded leaves stream only their local shard,
    so the per-device cost is chunk/shard_degree).
    """
    hbm = hbm_bytes if hbm_bytes is not None else detect_hbm_bytes()
    n_elements = sum(
        int(math.prod(getattr(l, "shape", ()) or (1,)))
        for l in jax.tree_util.tree_leaves(params)
    )
    per_el = working_bytes_per_element + grad_bytes_per_element + accum_buffer_bytes_per_element
    resident = n_elements * per_el // max(shard_degree, 1)
    margin = max(1 << 30, int(hbm * 0.10))  # activations + executables + fragmentation
    free = hbm - resident - margin
    per_dev_chunk = free // (_CHUNK_TRANSIENT_FACTOR * max(overlap, 1))
    chunk = per_dev_chunk * max(shard_degree, 1)
    return int(min(max(chunk, 64 << 20), 4 << 30))


def with_master_weights(
    tx: optax.GradientTransformation, master_dtype=jnp.float32
) -> optax.GradientTransformation:
    """Keep fp32 master weights *inside* the optimizer state (ZeRO-Offload's
    layout: DeepSpeed stores fp32 master params + moments on host while the
    device holds fp16/bf16 working weights).

    ``TrainState.params`` can then live in the compute dtype — no fp32 copy
    and no cast copy in HBM — while the inner tx updates the fp32 masters;
    the emitted update is the low-precision delta ``cast(new_master) - params``.
    """

    def _cast(x, dtype):
        return x.astype(dtype) if hasattr(x, "astype") else x

    def init(params):
        master = jax.tree_util.tree_map(lambda p: _cast(p, master_dtype), params)
        return {"master": master, "inner": tx.init(master)}

    def update(updates, state, params=None):
        master = state["master"]
        inner_updates, inner_state = tx.update(
            jax.tree_util.tree_map(lambda u: _cast(u, master_dtype), updates),
            state["inner"],
            master,
        )
        new_master = optax.apply_updates(master, inner_updates)
        if params is None:
            delta = jax.tree_util.tree_map(
                lambda nm, m, u: nm.astype(u.dtype) - m.astype(u.dtype),
                new_master, master, updates,
            )
        else:
            # anchor on the actual working copy so low-precision rounding
            # cannot accumulate: params + delta ≈ cast(new_master) each step
            delta = jax.tree_util.tree_map(
                lambda nm, p: nm.astype(p.dtype) - p, new_master, params
            )
        return delta, {"master": new_master, "inner": inner_state}

    return optax.GradientTransformation(init, update)


# ----------------------------------------------------------------- slicing
def build_slice_spec(params: Any, chunk_bytes: int) -> List[List[Tuple[int, int]]]:
    """Per flattened leaf: ``[(start, end), ...]`` ranges along axis 0 whose
    per-slice footprint (12 B/element) stays within ``chunk_bytes``.  Leaves
    that fit whole (or cannot be sliced: scalars, axis 0 of size 1) get one
    range covering the full leaf ((0, dim0) — (0, 1) for scalars)."""
    spec: List[List[Tuple[int, int]]] = []
    for leaf in jax.tree_util.tree_leaves(params):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        n = int(math.prod(shape)) if shape else 1
        dim0 = shape[0] if shape else 1
        if n * _BYTES_PER_ELEMENT <= chunk_bytes or dim0 <= 1:
            spec.append([(0, max(dim0, 1))])
            continue
        per_row = (n // dim0) * _BYTES_PER_ELEMENT
        rows = max(1, chunk_bytes // max(per_row, 1))
        ranges = [(s, min(s + rows, dim0)) for s in range(0, dim0, rows)]
        spec.append(ranges)
    return spec


def view_tree(tree: Any, spec: List[List[Tuple[int, int]]]) -> Any:
    """Replace each leaf by a tuple of its axis-0 slices per ``spec``.
    Single-range leaves stay unwrapped (slice == whole leaf, no copies)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)

    def one(leaf, ranges):
        if len(ranges) == 1:
            return leaf
        return tuple(
            jax.lax.slice_in_dim(leaf, s, e, axis=0) for (s, e) in ranges
        )

    return jax.tree_util.tree_unflatten(
        treedef, [one(l, r) for l, r in zip(leaves, spec)]
    )


def unview_tree(view: Any, spec: List[List[Tuple[int, int]]], like: Any) -> Any:
    """Inverse of :func:`view_tree`: concatenate slice tuples back to leaves."""
    leaves, treedef = jax.tree_util.tree_flatten(like)
    vparts = treedef.flatten_up_to(view)

    def one(part, ranges):
        if len(ranges) == 1:
            return part
        return jnp.concatenate(list(part), axis=0)

    return jax.tree_util.tree_unflatten(
        treedef, [one(p, r) for p, r in zip(vparts, spec)]
    )


def with_sliced_view(
    tx: optax.GradientTransformation, spec: List[List[Tuple[int, int]]], like: Any
) -> optax.GradientTransformation:
    """Adapt a view-structured transform to the model's param structure."""

    def init(params):
        return tx.init(view_tree(params, spec))

    def update(updates, state, params=None):
        v_updates, new_state = tx.update(
            view_tree(updates, spec),
            state,
            None if params is None else view_tree(params, spec),
        )
        return unview_tree(v_updates, spec, updates), new_state

    return optax.GradientTransformation(init, update)


# ------------------------------------------------------------- view meta
def flatten_view_meta(params: Any, spec) -> Tuple[Any, List[Tuple[int, int, int]], int]:
    """(view_treedef, meta, n_view_leaves): ``meta[v] = (orig_leaf_idx, start,
    end)`` in view flatten order."""
    view = view_tree(params, spec)
    v_leaves, v_treedef = jax.tree_util.tree_flatten(view)
    meta: List[Tuple[int, int, int]] = []
    for leaf_idx, ranges in enumerate(spec):
        for (s, e) in ranges:
            meta.append((leaf_idx, s, e))
    assert len(meta) == len(v_leaves), (len(meta), len(v_leaves))
    return v_treedef, meta, len(v_leaves)


def partition_view(sizes: Sequence[int], chunk_bytes: int) -> List[List[int]]:
    """Greedily group view-leaf indices (flatten order, so slices of one leaf
    stay contiguous) to ~``chunk_bytes`` of moment footprint each."""
    groups: List[List[int]] = []
    current: List[int] = []
    current_bytes = 0
    for v, size in enumerate(sizes):
        b = size * _BYTES_PER_ELEMENT
        if current and current_bytes + b > chunk_bytes:
            groups.append(current)
            current, current_bytes = [], 0
        current.append(v)
        current_bytes += b
    if current:
        groups.append(current)
    return groups


def _group_mask(treedef, n_leaves: int, group: Sequence[int]):
    member = set(group)
    return jax.tree_util.tree_unflatten(
        treedef, [i in member for i in range(n_leaves)]
    )


def build_chunked_tx(
    tx: optax.GradientTransformation, params: Any, chunk_bytes: int
) -> Tuple[optax.GradientTransformation, Optional[Dict[str, Any]]]:
    """Rebuild ``tx`` as slice-view + chain-of-masked chunks.

    Returns ``(wrapped_tx, info)`` where ``info`` carries everything the
    trainer's chunked apply needs (``None`` when one chunk suffices — the
    original tx is returned unchanged then).  ``info`` keys: ``spec``,
    ``view_treedef``, ``meta``, ``groups``, ``masked``, ``n_view_leaves``.
    """
    spec = build_slice_spec(params, chunk_bytes)
    view_treedef, meta, n_view = flatten_view_meta(params, spec)
    leaves = jax.tree_util.tree_leaves(params)
    sizes = []
    for (leaf_idx, s, e) in meta:
        shape = tuple(getattr(leaves[leaf_idx], "shape", ()) or ())
        if not shape:
            sizes.append(1)
        else:
            per_row = int(math.prod(shape)) // shape[0] if shape[0] else 1
            sizes.append(per_row * (e - s))
    groups = partition_view(sizes, chunk_bytes)
    if len(groups) <= 1:
        return tx, None
    masked = [optax.masked(tx, _group_mask(view_treedef, n_view, g)) for g in groups]
    chained = optax.chain(*masked)
    return with_sliced_view(chained, spec, params), {
        "spec": spec,
        "view_treedef": view_treedef,
        "meta": meta,
        "groups": groups,
        "masked": masked,
        "n_view_leaves": n_view,
    }


# ---------------------------------------------------------- chunk programs
def fill_view(
    group: Sequence[int],
    meta: Sequence[Tuple[int, int, int]],
    orig_pos: Dict[int, int],
    sources: Sequence[Any],
    n_view: int,
) -> List[Any]:
    """Flat view-leaf list for one chunk: this chunk's positions hold slices
    of ``sources`` (the chunk's original leaves, in ``orig_pos`` order), all
    others hold shape-() dummies that ``optax.masked`` turns into MaskedNode.
    Shared by the chunk init and apply programs so their view layouts cannot
    diverge."""
    dummy = jnp.zeros(())
    full = [dummy] * n_view
    for v in group:
        leaf_idx, s, e = meta[v]
        src = sources[orig_pos[leaf_idx]]
        if getattr(src, "ndim", 0) == 0:
            full[v] = src
        else:
            full[v] = jax.lax.slice_in_dim(src, s, e, axis=0)
    return full


def make_chunk_apply(
    group: Sequence[int],
    masked_tx: optax.GradientTransformation,
    info: Dict[str, Any],
    *,
    opt_on_host: bool,
    params_on_host: bool = False,
    donate: bool = True,
    opt_on_disk: bool = False,
):
    """Jitted per-chunk apply over FULL leaves: ``(chunk_leaves, chunk_grads,
    chunk_opt_state) -> (new_chunk_leaves, new_chunk_opt_state)``.

    ``chunk_leaves`` are the distinct original param leaves this chunk's view
    slices come from — passed whole (jit args alias live buffers; no copy);
    the program slices out the chunk's ranges, updates them against the
    streamed optimizer subtree, and writes them back into the leaves.  Leaves
    outside the chunk's view positions are fed to ``optax.masked`` as
    shape-() dummies (it replaces them with ``MaskedNode`` pre-update, so
    only this chunk's tensors materialize).  Host-resident arguments are NOT
    donated (XLA rejects host-buffer donation); disk-resident opt state
    (``opt_on_disk``, the nvme tier) arrives as numpy mmaps — uploaded H2D at
    dispatch, not donatable — and the updated subtree is returned on device
    for the caller to persist (``DiskChunkStore.write_chunk``).
    """
    meta = info["meta"]
    view_treedef = info["view_treedef"]
    n_view = info["n_view_leaves"]
    orig_ids = sorted({meta[v][0] for v in group})
    orig_pos = {j: i for i, j in enumerate(orig_ids)}

    def fn(chunk_leaves, chunk_grads, chunk_opt_state):
        from jax.memory import Space

        if opt_on_host:
            chunk_opt_state = jax.device_put(chunk_opt_state, Space.Device)
        if params_on_host:
            chunk_leaves = jax.device_put(chunk_leaves, Space.Device)
        full_vp = fill_view(group, meta, orig_pos, chunk_leaves, n_view)
        full_vg = fill_view(group, meta, orig_pos, chunk_grads, n_view)
        vp_tree = jax.tree_util.tree_unflatten(view_treedef, full_vp)
        vg_tree = jax.tree_util.tree_unflatten(view_treedef, full_vg)
        v_updates, new_state = masked_tx.update(vg_tree, chunk_opt_state, vp_tree)
        vu = jax.tree_util.tree_flatten(v_updates)[0]

        new_leaves = list(chunk_leaves)
        for v in group:
            leaf_idx, s, e = meta[v]
            pos = orig_pos[leaf_idx]
            upd = vu[v].astype(new_leaves[pos].dtype)
            if getattr(new_leaves[pos], "ndim", 0) == 0:
                new_leaves[pos] = new_leaves[pos] + upd
            else:
                new_slice = full_vp[v] + upd
                new_leaves[pos] = jax.lax.dynamic_update_slice_in_dim(
                    new_leaves[pos], new_slice, s, axis=0
                )
        if opt_on_host:
            new_state = jax.device_put(new_state, Space.Host)
        if params_on_host:
            new_leaves = jax.device_put(new_leaves, Space.Host)
        return new_leaves, new_state

    donate_argnums = tuple(
        i for i, off_device in ((0, params_on_host), (2, opt_on_host or opt_on_disk))
        if donate and not off_device
    )
    return jax.jit(fn, donate_argnums=donate_argnums), orig_ids


# ------------------------------------------------------------ NVMe tier
class DiskChunkStore:
    """Disk ("nvme") tier for the chunked optimizer update — the reference's
    ``offload_optimizer_device="nvme"`` + ``nvme_path``
    (``/root/reference/src/accelerate/utils/dataclasses.py:806-834``,
    DeepSpeed ZeRO-Infinity's optimizer tier).

    Each chunk's optimizer subtree lives in raw ``.dat`` files under
    ``path/chunk_<i>/`` (the :mod:`accelerate_tpu.utils.offload` format,
    bf16 stored as int16), memory-mapped read-only between sync steps.  The
    chunk apply consumes the mmaps directly — the H2D upload reads straight
    from page cache/disk, and on rigs with the native runtime the same files
    are eligible for ``atpu_runtime.read_blocks`` threaded preads — and the
    updated subtree is written back through a fresh ``w+`` map after the
    program completes.  RAM and HBM stay bounded at O(chunk); the full state
    lives only on disk.
    """

    def __init__(self, path: str):
        # write_chunk serializes leaves via np.asarray: fine on one process
        # (sharded leaves gather across local devices), but on a multi-host
        # mesh the remote shards are non-addressable and np.asarray raises
        # mid-training.  Fail at construction with the actual limitation
        # instead; multi-host wants per-process shard-local stores (each rank
        # persisting only its addressable window), which the sharded-window
        # chunk layout does not implement yet.
        if jax.process_count() > 1:
            raise NotImplementedError(
                "The nvme optimizer tier (DiskChunkStore) is single-host only: "
                "chunk persistence gathers leaves with np.asarray, which cannot "
                "see non-addressable shards on a multi-process mesh. Use "
                'offload_optimizer_device="cpu" (pinned host) on pods, or shard '
                "the optimizer state with fsdp so each host's share fits in RAM."
            )
        os.makedirs(path, exist_ok=True)
        self.path = path
        self._meta: Dict[int, Any] = {}  # chunk -> (treedef, [leaf infos])

    def _chunk_dir(self, i: int) -> str:
        d = os.path.join(self.path, f"chunk_{i}")
        os.makedirs(d, exist_ok=True)
        return d

    def write_chunk(self, i: int, subtree: Any) -> Any:
        """Persist a (device/host) chunk subtree; return it re-mapped from disk.

        Writes go to a temp file and ``os.replace`` over the final name: the
        previous generation's read-mmaps (possibly still referenced by the
        just-consumed optimizer arrays — CPU backends can zero-copy numpy
        inputs) keep their old inode alive, where truncating in place
        (``mode="w+"`` on the existing file) would invalidate their pages and
        SIGBUS any late access.
        """
        from .offload import offload_weight

        leaves, treedef = jax.tree_util.tree_flatten(subtree)
        d = self._chunk_dir(i)
        index: Dict[str, Dict] = {}
        for j, leaf in enumerate(leaves):
            # sync=False: scratch state rewritten every sync step — page-cache
            # writeback only (an msync per leaf measured 3x+ slower cycles);
            # durability is the checkpoint engine's job, as with pinned host
            offload_weight(np.asarray(leaf), f"leaf_{j}__tmp", d, index=index, sync=False)
            os.replace(
                os.path.join(d, f"leaf_{j}__tmp.dat"), os.path.join(d, f"leaf_{j}.dat")
            )
            index[f"leaf_{j}"] = index.pop(f"leaf_{j}__tmp")  # keys match files on disk
        self._meta[i] = (treedef, [index[f"leaf_{j}"] for j in range(len(leaves))])
        return self.read_chunk(i)

    def read_chunk(self, i: int) -> Any:
        from .offload import load_offloaded_weight

        treedef, infos = self._meta[i]
        d = self._chunk_dir(i)
        leaves = [
            load_offloaded_weight(os.path.join(d, f"leaf_{j}.dat"), info)
            for j, info in enumerate(infos)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)


# Back-compat helpers used by tests
def partition_leaves(params: Any, chunk_bytes: int) -> List[List[int]]:
    """Leaf-granularity grouping (view-free); kept for the degenerate case and
    tests — :func:`build_chunked_tx` now partitions the sliced view instead."""
    leaves = jax.tree_util.tree_leaves(params)
    groups: List[List[int]] = []
    current: List[int] = []
    current_bytes = 0
    for i, leaf in enumerate(leaves):
        size = int(math.prod(getattr(leaf, "shape", ()) or (1,))) * _BYTES_PER_ELEMENT
        if current and current_bytes + size > chunk_bytes:
            groups.append(current)
            current, current_bytes = [], 0
        current.append(i)
        current_bytes += size
    if current:
        groups.append(current)
    return groups
