"""Big-model inference: shape-only init, device maps, offload, streaming forward
(reference ``big_modeling.py`` L6 + ``hooks.py`` offload engine).

Reference mechanism: meta-device init (``init_empty_weights``,
``big_modeling.py:56``), greedy device-map packing (``infer_auto_device_map``),
checkpoint dispatch (``load_checkpoint_and_dispatch``, ``:499``) and per-forward
weight streaming via ``AlignDevicesHook`` (``hooks.py:322-389``).

TPU-native re-design:

* meta init ≡ ``jax.eval_shape`` — abstract trees with zero allocation;
* when the model fits in pooled HBM, ``device_map="sharded"`` places every
  weight with a ``NamedSharding`` over the mesh and one jitted apply runs it —
  GSPMD inserts the collectives; no hooks, no python in the hot loop;
* for the overflow case, :class:`StreamingTransformer` is the AlignDevicesHook
  analog: per-layer jitted compute (ONE executable reused by every layer — all
  decoder layers share shapes) with double-buffered host→HBM transfers: layer
  ``i+1``'s weights stream while layer ``i`` computes.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .utils.modeling import (
    DeviceId,
    SEP,
    compute_module_sizes,
    flatten_tree,
    get_balanced_memory,
    get_max_layer_size,
    infer_auto_device_map,
    top_level_modules,
    unflatten_tree,
)
from .utils.offload import OffloadedWeightsLoader, offload_state_dict


# --------------------------------------------------------------------- init
def init_empty_weights(model, *args, method: str = "init", rng=None, **kwargs):
    """Abstract (shape-only) parameter tree — the ``init_empty_weights`` analog
    (reference ``big_modeling.py:56-166``; here no monkey-patching: JAX's
    abstract interpretation is first-class via ``jax.eval_shape``)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    fn = getattr(model, method)
    shapes = jax.eval_shape(lambda: fn(rng, *args, **kwargs))
    return shapes["params"] if isinstance(shapes, dict) and "params" in shapes else shapes


def init_params_on_host(model, *args, method: str = "init", rng=None, **kwargs):
    """Materialize freshly initialized parameters directly into pinned host
    memory — the creation path for bigger-than-HBM training states.

    Random init on-device would leave a full-precision parameter tree resident
    in HBM while ``create_train_state`` builds the working copy and the
    (host-offloaded) optimizer chunks; emitting the init program's outputs to
    host memory keeps the HBM peak at transients only.  Falls back to plain
    device init on backends without host memory support (CPU test rigs).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from .parallel.sharding import supports_host_offload
    from .state import PartialState

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    fn = getattr(model, method)

    def run():
        out = fn(rng, *args, **kwargs)
        return out["params"] if isinstance(out, dict) and "params" in out else out

    mesh = PartialState().mesh
    if not supports_host_offload(mesh):
        return jax.jit(run)()
    host = NamedSharding(mesh, PartitionSpec(), memory_kind="pinned_host")
    shapes = jax.eval_shape(run)
    jitted = jax.jit(run, out_shardings=jax.tree_util.tree_map(lambda _: host, shapes))
    placed = jitted()
    jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if isinstance(x, jax.Array) else x, placed
    )
    # drop the init executable's HBM plan before training compiles — scoped to
    # this program only (a global clear_caches would invalidate any steps the
    # caller already compiled)
    jitted.clear_cache()
    return placed


def checkpoint_shapes(
    checkpoint: str, files: Optional[Dict[str, str]] = None
) -> Dict[str, jax.ShapeDtypeStruct]:
    """Flat {path: ShapeDtypeStruct} read from safetensors headers — no
    tensor bytes are touched (the on-disk analog of meta init)."""
    from safetensors import safe_open

    flat: Dict[str, jax.ShapeDtypeStruct] = {}
    by_file: Dict[str, list] = {}
    for key, fname in (files if files is not None else _checkpoint_files(checkpoint)).items():
        by_file.setdefault(fname, []).append(key)
    for fname, keys in by_file.items():  # one open + header parse per file
        if fname.endswith(".bin"):
            entries = _bin_entries(fname)
            for key in keys:
                t = entries[key]
                flat[key] = jax.ShapeDtypeStruct(tuple(t.shape), _torch_np_dtype(t.dtype))
            continue
        with safe_open(fname, framework="np") as f:
            for key in keys:
                sl = f.get_slice(key)
                flat[key] = jax.ShapeDtypeStruct(
                    tuple(sl.get_shape()), _SAFETENSORS_DTYPES[sl.get_dtype()]
                )
    return flat


_SAFETENSORS_DTYPES = {
    "BOOL": np.dtype(np.bool_),
    "U8": np.dtype(np.uint8), "I8": np.dtype(np.int8),
    "U16": np.dtype(np.uint16), "I16": np.dtype(np.int16),
    "U32": np.dtype(np.uint32), "I32": np.dtype(np.int32),
    "U64": np.dtype(np.uint64), "I64": np.dtype(np.int64),
    "F16": np.dtype(np.float16), "F32": np.dtype(np.float32), "F64": np.dtype(np.float64),
    "BF16": jnp.bfloat16,
}


def _checkpoint_files(checkpoint: str) -> Dict[str, str]:
    """{tensor_name: file path} for a single-file or sharded checkpoint.

    Safetensors is the native format; torch-pickle ``.bin`` checkpoints
    (``pytorch_model.bin`` / ``pytorch_model.bin.index.json``) are read as a
    fallback via torch-cpu (reference ``load_checkpoint_in_model`` handles
    both, ``utils/modeling.py:1608-1830``).
    """
    import json

    if os.path.isfile(checkpoint):
        files = [checkpoint]
    else:
        for index_name in ("model.safetensors.index.json", "pytorch_model.bin.index.json"):
            index_path = os.path.join(checkpoint, index_name)
            if os.path.isfile(index_path):
                with open(index_path) as f:
                    index = json.load(f)
                return {
                    key: os.path.join(checkpoint, fname)
                    for key, fname in index["weight_map"].items()
                }
        for single_name in ("model.safetensors", "pytorch_model.bin"):
            single = os.path.join(checkpoint, single_name)
            if os.path.isfile(single):
                files = [single]
                break
        else:
            raise FileNotFoundError(
                f"No checkpoint found at {checkpoint} (looked for model.safetensors[.index.json] "
                "and pytorch_model.bin[.index.json])"
            )
    mapping: Dict[str, str] = {}
    for fname in files:
        if fname.endswith(".bin"):
            for key in _bin_entries(fname):
                mapping[key] = fname
        else:
            from safetensors import safe_open

            with safe_open(fname, framework="np") as f:
                for key in f.keys():
                    mapping[key] = fname
    return mapping


_BIN_CACHE: Dict[Any, Dict[str, Any]] = {}
_BIN_CACHE_MAX = 16  # bounds pinned shards; keyed on (path, mtime, size) so a
                     # rewritten checkpoint is never served stale


def _bin_entries(fname: str) -> Dict[str, Any]:
    """Lazily torch.load a ``.bin`` shard (mmap'd, cpu) -> {key: torch tensor}.

    Cached because torch-pickle has no header-only read: the one load serves
    both shape inspection and tensor reads (mmap keeps RSS bounded where the
    format allows).  LRU-capped, invalidated by file mtime/size.
    """
    stat = os.stat(fname)
    key = (fname, stat.st_mtime_ns, stat.st_size)
    cached = _BIN_CACHE.get(key)
    if cached is None:
        import torch

        try:
            cached = torch.load(fname, map_location="cpu", mmap=True, weights_only=True)
        except (TypeError, RuntimeError):  # older formats: no mmap / zipfile
            cached = torch.load(fname, map_location="cpu", weights_only=True)
        # drop superseded versions of this file, then cap total entries
        for k in [k for k in _BIN_CACHE if k[0] == fname]:
            del _BIN_CACHE[k]
        while len(_BIN_CACHE) >= _BIN_CACHE_MAX:
            del _BIN_CACHE[next(iter(_BIN_CACHE))]
        _BIN_CACHE[key] = cached
    return cached


def _torch_to_numpy(t) -> np.ndarray:
    import torch

    if t.dtype == torch.bfloat16:
        return t.view(torch.uint16).numpy().view(jnp.bfloat16)
    return t.numpy()


def _torch_np_dtype(td):
    import torch

    if td == torch.bfloat16:
        return jnp.bfloat16
    return np.dtype(str(td).replace("torch.", ""))


# ----------------------------------------------------------------- dispatch
def _validate_device_map(device_map: Dict[str, DeviceId], modules, what: str = "model") -> None:
    """An explicit device_map must cover exactly the top-level modules —
    silently defaulting uncovered layers to device 0 would defeat the offload
    the caller asked for (or OOM)."""
    known = set(modules)
    unknown = [k for k in device_map if k not in known]
    missing = [m for m in known if m not in device_map]
    if unknown:
        raise ValueError(
            f"device_map keys {unknown} are not modules of this {what} "
            f"(modules: {sorted(known)}). To pass per-device byte budgets use "
            "max_memory=... with device_map='auto'."
        )
    if missing:
        raise ValueError(
            f"device_map does not cover modules {sorted(missing)}; every top-level "
            "module needs a placement (device index, 'cpu', or 'disk')."
        )


def dispatch_params(
    params,
    device_map: Dict[str, DeviceId],
    offload_folder: Optional[str] = None,
) -> Tuple[Any, Optional[OffloadedWeightsLoader]]:
    """Place each top-level module's weights per ``device_map`` (reference
    ``dispatch_model``, ``big_modeling.py:305-496``).

    Device-mapped modules go to HBM (``jax.device_put``); ``"cpu"`` modules
    stay as host numpy arrays; ``"disk"`` modules are written to
    ``offload_folder`` memory-maps and dropped from RAM.  Returns the placed
    tree (disk leaves become ``None``) plus the weights loader covering
    cpu+disk entries for streaming.
    """
    _validate_device_map(device_map, top_level_modules(params))
    devices = jax.devices()
    placed: Dict[str, Any] = {}
    host_entries: Dict[str, Any] = {}
    disk_flat: Dict[str, Any] = {}
    for mod in top_level_modules(params):
        target = device_map[mod]
        sub = params[mod]
        if target == "disk":
            if offload_folder is None:
                raise ValueError("device_map places modules on 'disk' but no offload_folder was given.")
            disk_flat.update(flatten_tree(sub, mod))
            placed[mod] = None
        elif target == "cpu":
            sub = jax.tree_util.tree_map(np.asarray, sub)
            host_entries.update(flatten_tree(sub, mod))
            placed[mod] = sub
        else:
            placed[mod] = jax.device_put(sub, devices[int(target)])
    loader = None
    if disk_flat:
        offload_state_dict(offload_folder, {k: np.asarray(v) for k, v in disk_flat.items()})
        loader = OffloadedWeightsLoader(state_dict=host_entries, save_folder=offload_folder)
    elif host_entries:
        loader = OffloadedWeightsLoader(state_dict=host_entries)
    return placed, loader


def shard_params_for_inference(params, mesh=None, axis: Optional[str] = None):
    """Pooled-HBM placement: shard every weight's largest divisible dim over the
    mesh and let GSPMD handle the rest — the TPU answer to ``device_map`` when
    the model fits in aggregate HBM (SURVEY §7.10)."""
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        from .state import PartialState

        mesh = PartialState().mesh
    axes = list(mesh.shape.keys()) if axis is None else [axis]
    sizes = {a: mesh.shape[a] for a in axes}
    total = int(np.prod(list(sizes.values())))

    def place(x):
        x = jnp.asarray(x)
        best_dim, best_axes = None, ()
        for d, dim_size in enumerate(x.shape):
            if dim_size % total == 0:
                best_dim, best_axes = d, tuple(axes)
                break
        if best_dim is None:
            for d, dim_size in enumerate(x.shape):
                for a in axes:
                    if dim_size % sizes[a] == 0:
                        best_dim, best_axes = d, (a,)
                        break
                if best_dim is not None:
                    break
        spec = [None] * jnp.ndim(x)
        if best_dim is not None:
            spec[best_dim] = best_axes if len(best_axes) > 1 else best_axes[0]
        return jax.device_put(x, NamedSharding(mesh, PartitionSpec(*spec)))

    return jax.tree_util.tree_map(place, params)


def cpu_offload(params, exec_device_map: Optional[Dict[str, DeviceId]] = None):
    """Everything on host, streamed per-forward (reference ``cpu_offload``,
    ``big_modeling.py:169-211``)."""
    device_map = {mod: "cpu" for mod in top_level_modules(params)}
    if exec_device_map:
        device_map.update(exec_device_map)
    return dispatch_params(params, device_map)


def disk_offload(params, offload_folder: str):
    """Everything on disk memory-maps (reference ``disk_offload``,
    ``big_modeling.py:214-260``)."""
    device_map = {mod: "disk" for mod in top_level_modules(params)}
    return dispatch_params(params, device_map, offload_folder=offload_folder)


# ------------------------------------------------- checkpoint → dispatched
def load_checkpoint_and_dispatch(
    model,
    checkpoint: str,
    device_map: Union[str, Dict[str, DeviceId]] = "auto",
    max_memory: Optional[Dict[DeviceId, int]] = None,
    offload_folder: Optional[str] = None,
    dtype=None,
    mesh=None,
    quantization=None,
):
    """Load a safetensors checkpoint with placement decided *before* any tensor
    is read (reference ``load_checkpoint_and_dispatch``, ``big_modeling.py:499-628``).

    ``device_map``:
      * ``"sharded"`` — shard into pooled HBM via NamedSharding (TPU-preferred);
      * ``"auto"``/``"balanced"`` — greedy packing over device budgets, spilling
        to cpu/disk;
      * explicit dict — your placement.

    ``quantization`` (a :class:`~accelerate_tpu.ops.quantization.QuantizationConfig`,
    e.g. ``Int8Config()``) quantizes eligible kernels as they are read — the
    ``load_and_quantize_model`` analog (reference ``utils/bnb.py:44-467``):
    placement budgets see the quantized (4x/8x smaller) sizes, and the returned
    tree matches a model built with ``TransformerConfig(quantization=bits)``.

    Returns ``(params, device_map, weights_loader)``; disk-mapped tensors are
    NOT copied — the loader reads them zero-copy from the checkpoint itself.

    A raw HF model directory (config.json with a mapped ``model_type``, HF key
    naming) is auto-converted into ``<dir>/_atpu_native`` first — see
    :mod:`accelerate_tpu.models.hf_compat` — so a downloaded ``gpt2``/Llama
    snapshot loads directly.
    """
    from .models.hf_compat import convert_hf_checkpoint, is_hf_checkpoint

    if os.path.isdir(checkpoint) and is_hf_checkpoint(checkpoint):
        checkpoint = convert_hf_checkpoint(checkpoint, dtype=dtype)
    files = _checkpoint_files(checkpoint)
    flat_shapes = checkpoint_shapes(checkpoint, files=files)
    quantize_flat = None
    if quantization is not None:
        from .ops.quantization import quantize_flat_tree as quantize_flat

        flat_shapes = quantize_flat(flat_shapes, quantization, sep=SEP)
    abstract = unflatten_tree(flat_shapes)

    def read(keys, host: bool = False):
        flat = _read_tensors(files, keys, dtype)
        if quantize_flat is not None:
            if host:
                # cpu-targeted modules must quantize on the host: the jnp ops in
                # quantize() otherwise commit qweight/scales to the default
                # accelerator device, putting the whole "bigger than HBM" model
                # in HBM during load — and jax.Array leaves would also disable
                # the StreamingExecutor's packed host-transfer path.
                import contextlib

                try:
                    cpu = jax.local_devices(backend="cpu")[0]
                    ctx = jax.default_device(cpu)
                except RuntimeError:
                    ctx = contextlib.nullcontext()
                with ctx:
                    flat = quantize_flat(flat, quantization, sep=SEP)
                flat = {k: np.asarray(v) for k, v in flat.items()}
            else:
                flat = quantize_flat(flat, quantization, sep=SEP)
        return flat

    if device_map == "sharded":
        flat = read(list(files.keys()))
        params = shard_params_for_inference(unflatten_tree(flat), mesh=mesh)
        return params, "sharded", None

    if isinstance(device_map, str):
        if device_map not in ("auto", "balanced", "balanced_low_0"):
            raise ValueError(f"Unknown device_map {device_map!r}")
        budgets = get_balanced_memory(
            abstract, max_memory, dtype=dtype, low_zero=device_map == "balanced_low_0"
        )
        device_map = infer_auto_device_map(abstract, budgets, dtype=dtype)

    _validate_device_map(device_map, top_level_modules(abstract), what="checkpoint")
    devices = jax.devices()
    placed: Dict[str, Any] = {}
    host_entries: Dict[str, Any] = {}
    safetensors_refs: Dict[str, str] = {}
    for mod in top_level_modules(abstract):
        target = device_map[mod]
        keys = [k for k in files if k == mod or k.startswith(mod + SEP)]
        if target == "disk":
            if quantization is not None:
                raise ValueError(
                    "quantization with disk-mapped modules is not supported: disk "
                    "entries are zero-copy references into the fp checkpoint. Raise "
                    "max_memory (quantized weights are 4-8x smaller) or use 'cpu'."
                )
            # zero-copy: leave bytes in the checkpoint, remember the file
            for k in keys:
                safetensors_refs[k] = files[k]
            placed[mod] = None
        elif target == "cpu":
            flat = read(keys, host=True)
            host_entries.update(flat)
            placed[mod] = _strip_prefix(flat, mod)
        else:
            flat = read(keys)
            placed[mod] = jax.device_put(_strip_prefix(flat, mod), devices[int(target)])
    loader = None
    if host_entries or safetensors_refs:
        loader = OffloadedWeightsLoader(state_dict=host_entries, safetensors_files=safetensors_refs)
    return placed, device_map, loader


def _strip_prefix(flat: Dict[str, Any], mod: str):
    """Subtree under ``mod`` — a root-level leaf (key == mod) IS the value."""
    if set(flat) == {mod}:
        return flat[mod]
    return unflatten_tree({k[len(mod) + 1:]: v for k, v in flat.items()})


def _read_tensors(files: Dict[str, str], keys, dtype=None) -> Dict[str, np.ndarray]:
    from safetensors import safe_open

    by_file: Dict[str, list] = {}
    for k in keys:
        by_file.setdefault(files[k], []).append(k)
    out: Dict[str, np.ndarray] = {}
    for fname, ks in by_file.items():
        if fname.endswith(".bin"):
            entries = _bin_entries(fname)
            for k in ks:
                t = _torch_to_numpy(entries[k])
                out[k] = t.astype(jnp.dtype(dtype)) if dtype is not None else t
            continue
        with safe_open(fname, framework="np") as f:
            for k in ks:
                t = f.get_tensor(k)
                if dtype is not None:
                    t = t.astype(jnp.dtype(dtype))
                out[k] = t
    return out


# ------------------------------------------------------- streaming executor
class StageHook:
    """Public extension protocol for :class:`StreamingExecutor` — the
    TPU-native analog of the reference's ``ModelHook`` / ``add_hook_to_module``
    (``/root/reference/src/accelerate/hooks.py:36-217``).

    The reference patches ``nn.Module.forward`` per submodule; here the
    natural interception point is the **stage boundary** of the streaming
    plan (everything inside a stage is one fused XLA executable).  Subclass
    and override any of:

    * :meth:`fetch_weights` — replace where a stage's weights come from (a
      bespoke offload tier, a pinned-in-HBM cache, decryption, ...).  Return
      ``None`` to fall through to the executor's params/loader resolution.
    * :meth:`pre_stage` / :meth:`post_stage` — observe or transform the
      carry at stage entry/exit (timing, logging, activation edits).  Return
      ``None`` to keep the carry unchanged; these run at the host-level
      stage boundary, outside jit, so any python is allowed.

    Attach with ``StreamingExecutor(..., hooks=[...])`` or
    :meth:`StreamingExecutor.add_hook`.  Hooks run in attach order;
    ``fetch_weights`` uses the first non-``None`` result.

    See ``examples/by_feature/streaming_hooks.py`` for a worked custom
    offload policy + stage profiler.
    """

    def fetch_weights(self, executor: "StreamingExecutor", stage_index: int, source):
        """Return the stage's host/device param tree, or ``None`` for default."""
        return None

    def pre_stage(self, executor: "StreamingExecutor", stage_index: int, carry: tuple):
        """Return a replacement carry tuple, or ``None`` to keep ``carry``."""
        return None

    def post_stage(self, executor: "StreamingExecutor", stage_index: int, carry: tuple):
        """Return a replacement carry tuple, or ``None`` to keep ``carry``."""
        return None


class StreamingExecutor:
    """Generic layer-plan streaming forward — the model-agnostic
    ``AlignDevicesHook`` engine (reference ``hooks.py:219-396``) redesigned TPU-first.

    The reference hooks *any* ``nn.Module`` tree by patching each submodule's
    forward to fault its weights in from a weights map.  Here the same
    capability is a **plan**: an ordered list of ``(params_source, fn)`` stages,
    where ``fn(stage_params, *carry) -> carry`` is any jittable function and
    ``params_source`` is a module name resolved against ``params`` /
    ``weights_loader`` (or a callable returning the stage's host params).  The
    executor then runs the classic streaming schedule:

    * ONE jitted executable per distinct ``fn`` (all decoder layers share
      shapes, so N layers compile once);
    * double buffering: stage ``i+1``'s ``jax.device_put`` (async DMA) is
      issued before stage ``i``'s compute, overlapping transfer with the MXU;
    * stages already resident on the exec device skip the transfer.

    Works for any stacked-layer architecture — build a plan with
    :func:`make_layer_plan` or hand-roll one; :class:`StreamingTransformer`
    is the flagship-model adapter.
    """

    def __init__(
        self,
        plan,
        params=None,
        weights_loader=None,
        exec_device=None,
        pack_transfers: bool = True,
        hooks=None,
    ):
        self.plan = list(plan)
        if not self.plan:
            raise ValueError("StreamingExecutor needs a non-empty plan")
        self.params = params
        self.loader = weights_loader
        self.hooks = list(hooks) if hooks else []
        self.device = exec_device if exec_device is not None else jax.devices()[0]
        # Pack each host-resident stage into ONE contiguous buffer per dtype
        # before transfer: a decoder layer is ~10 leaves, and 10 small
        # device_puts pay 10x the DMA-issue latency of one big one
        # (measured 12x effective-bandwidth loss unpacked).  The stage fn then
        # slices the buffer back apart on-device (HBM-to-HBM, fused by XLA).
        self.pack_transfers = pack_transfers
        self._jit_cache: Dict[Any, Callable] = {}
        self._packed_cache: Dict[int, Any] = {}
        # (dtype, leaf-ids) -> (pinned leaf refs, packed host buffer); deduped
        # across stages so shared modules (tied embeddings) snapshot once
        self._buffer_registry: Dict[Any, Any] = {}

    # -- hooks -------------------------------------------------------------
    def add_hook(self, hook: StageHook) -> None:
        """Append a :class:`StageHook`.  Weights-affecting hooks compose with
        the packed-transfer cache via leaf identity: returning NEW arrays is
        picked up automatically; mutating host arrays in place still requires
        :meth:`invalidate_cache` (same contract as ``params``)."""
        self.hooks.append(hook)

    def remove_hook(self, hook: StageHook) -> None:
        self.hooks.remove(hook)

    def _hook_carry(self, method: str, i: int, carry: tuple) -> tuple:
        for h in self.hooks:
            out = getattr(h, method)(self, i, carry)
            if out is not None:
                carry = out if isinstance(out, tuple) else (out,)
        return carry

    # -- module weight access ---------------------------------------------
    def _stage_params(self, source, stage_index: Optional[int] = None):
        if stage_index is not None:
            for h in self.hooks:
                tree = h.fetch_weights(self, stage_index, source)
                if tree is not None:
                    return tree
        if callable(source):
            return source()
        return self._module_params(source)

    def _module_params(self, name: str):
        sub = self.params.get(name) if isinstance(self.params, dict) else None
        if sub is not None:
            return sub
        if self.loader is None:
            raise KeyError(f"No weights for module {name!r}")
        flat = {
            k[len(name) + 1:]: self.loader[k]
            for k in self.loader
            if k.startswith(name + SEP)
        }
        if not flat:
            raise KeyError(f"No weights for module {name!r}")
        return unflatten_tree(flat)

    def _to_device(self, tree):
        def put(x):
            if isinstance(x, jax.Array) and x.committed and x.devices() == {self.device}:
                return x
            return jax.device_put(x, self.device)

        return jax.tree_util.tree_map(put, tree)

    def _jitted(self, fn):
        cached = self._jit_cache.get(fn)
        if cached is None:
            cached = self._jit_cache[fn] = jax.jit(fn)
        return cached

    # -- packed transfer ----------------------------------------------------
    def invalidate_cache(self) -> None:
        """Drop cached packed host buffers.  Call after mutating host weights
        in place — packed stages are *snapshots* taken at first transfer.
        (Rebinding ``params`` to NEW arrays is detected automatically: cache
        validity is leaf *identity*, and cached entries pin their source
        leaves so ids cannot be recycled.)"""
        self._packed_cache.clear()
        self._buffer_registry.clear()

    def _packed_buffer(self, dtype, group_leaves):
        """Snapshot one dtype-group into a contiguous host buffer, deduped
        across stages: modules shared between stages (e.g. a tied embedding
        table used by both the embed and head stages) pack ONCE.

        The registry entry pins the source leaf objects, which both keeps the
        id-based key sound (no id recycling while cached) and makes a params
        rebind an automatic cache miss.
        """
        gkey = (np.dtype(dtype), tuple(id(x) for x in group_leaves))
        entry = self._buffer_registry.get(gkey)
        if entry is not None and all(a is b for a, b in zip(entry[0], group_leaves)):
            return entry[1]
        arrs = [np.asarray(x).reshape(-1) for x in group_leaves]
        # pack_buffers = multithreaded native gather when libatpu_runtime is
        # built, np.concatenate otherwise; either way the result is a snapshot
        # copy, never a live view of caller memory
        from .utils import _native

        buffer = _native.pack_buffers(arrs)
        self._buffer_registry[gkey] = (tuple(group_leaves), buffer)
        return buffer

    def _prepare_stage(self, i: int, transfer_cache: Optional[Dict[int, Any]] = None):
        """Resolve stage ``i``'s params and issue its (async) transfer.

        Returns ``(device_operand, spec_key, treedef)`` where ``spec_key`` is
        None for the unpacked path, else the static unpack layout.

        Packing applies only to stages whose every leaf is true host data
        (numpy etc., as produced by loaders/checkpoint reads) — jax Arrays are
        already device-resident (or one cheap device_put away) and take the
        unpacked path.  Packed buffers are consistent SNAPSHOTS keyed on leaf
        identity (sources pinned, so identity is sound); in-place host
        mutations require :meth:`invalidate_cache`.  ``transfer_cache`` dedupes
        H2D transfers of the same buffer within one forward (tied modules).
        """
        tree = self._stage_params(self.plan[i][0], stage_index=i)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        host = self.pack_transfers and leaves and not any(
            isinstance(x, jax.Array) for x in leaves
        )
        if not host:
            return self._to_device(tree), None, None

        cached = self._packed_cache.get(i)
        if cached is None or len(cached[0]) != len(leaves) or not all(
            a is b for a, b in zip(cached[0], leaves)
        ):
            # group leaves by dtype; one deduped contiguous buffer per group
            groups: Dict[Any, list] = {}
            placements = []
            for leaf in leaves:
                arr = np.asarray(leaf)
                g = groups.setdefault(arr.dtype, [])
                offset = sum(a.size for _, a in g)
                g.append((leaf, arr))
                placements.append((arr.dtype, offset, arr.size, arr.shape))
            dtypes = list(groups)
            buffers = [
                self._packed_buffer(d, [leaf for leaf, _ in groups[d]]) for d in dtypes
            ]
            spec = tuple(
                (dtypes.index(d), off, size, shape) for (d, off, size, shape) in placements
            )
            replaced = cached is not None
            self._packed_cache[i] = cached = (tuple(leaves), buffers, spec)
            if replaced:
                # a rebind superseded the old snapshot: drop registry entries no
                # stage references anymore, or every swap leaks a model copy
                live = {
                    id(b) for (_, bufs, _) in self._packed_cache.values() for b in bufs
                }
                self._buffer_registry = {
                    k: v for k, v in self._buffer_registry.items() if id(v[1]) in live
                }
        _, buffers, spec = cached
        dev_buffers = []
        for b in buffers:
            dev = transfer_cache.get(id(b)) if transfer_cache is not None else None
            if dev is None:
                dev = jax.device_put(b, self.device)
                if transfer_cache is not None:
                    transfer_cache[id(b)] = dev
            dev_buffers.append(dev)
        return dev_buffers, spec, treedef

    def _run_stage(self, fn, operand, spec, treedef, carry):
        if spec is None:
            return self._jitted(fn)(operand, *carry)
        cache_key = (fn, spec, treedef)
        wrapped = self._jit_cache.get(cache_key)
        if wrapped is None:
            def unpacked(buffers, *args):
                leaves = [
                    jax.lax.slice(buffers[g], (off,), (off + size,)).reshape(shape)
                    for (g, off, size, shape) in spec
                ]
                return fn(jax.tree_util.tree_unflatten(treedef, leaves), *args)

            wrapped = self._jit_cache[cache_key] = jax.jit(unpacked)
        return wrapped(operand, *carry)

    # -- forward -----------------------------------------------------------
    def __call__(self, *inputs):
        carry: Tuple[Any, ...] = inputs
        transfer_cache: Dict[int, Any] = {}  # per-call H2D dedupe (tied modules)
        current = self._prepare_stage(0, transfer_cache)
        for i, (source, fn) in enumerate(self.plan):
            nxt = None
            if i + 1 < len(self.plan):
                # async transfer of stage i+1 issued before stage i computes
                nxt = self._prepare_stage(i + 1, transfer_cache)
            operand, spec, treedef = current
            carry = self._hook_carry("pre_stage", i, carry)
            out = self._run_stage(fn, operand, spec, treedef, carry)
            carry = out if isinstance(out, tuple) else (out,)
            carry = self._hook_carry("post_stage", i, carry)
            current = nxt
        return carry[0] if len(carry) == 1 else carry


def make_layer_plan(embed, layers, head):
    """Convenience plan builder for the embed → N x layer → head shape that
    covers every decoder-only/encoder stack.

    ``embed``/``head`` are ``(params_source, fn)``; ``layers`` is an iterable of
    them (typically the SAME fn object for every layer so they share one
    compiled executable).
    """
    return [embed, *layers, head]


class StreamingTransformer(StreamingExecutor):
    """Flagship-Transformer adapter over :class:`StreamingExecutor`.

    Handles both parameter layouts (``layers_{i}`` modules, or the single
    stacked ``layers`` module of ``scan_layers=True`` — streamed by slicing),
    tied embeddings, and quantized weights (the stage fns run whatever the
    config dictates, including :class:`~accelerate_tpu.ops.quantization.QuantizedDense`).
    """

    def __init__(
        self,
        config,
        params,
        device_map: Optional[Dict[str, DeviceId]] = None,
        weights_loader=None,
        exec_device=None,
        layers_per_stage: int = 1,
        hooks=None,
    ):
        from .models.transformer import DecoderLayer, make_norm

        cfg = config
        self.config = config
        self.device_map = device_map or {}
        # scan_layers=True models store ONE stacked "layers" module (axis 0 =
        # depth, models/transformer.py) instead of layers_{i}; stream by
        # slicing the stack per layer.
        self._scan_layout = bool(getattr(cfg, "scan_layers", False)) or (
            isinstance(params, dict) and "layers" in params and "layers_0" not in params
        )
        self._stack_cache = None  # cached scanned-layer stack (invalidate_cache resets)
        self._stack_src = None    # identity of the params["layers"] subtree the cache came from
        self._slice_cache: Dict[int, Any] = {}  # per-layer slice trees of the stack
        # layers_per_stage > 1 amortizes per-dispatch/per-transfer fixed costs
        # (dominant on high-latency transports) over bigger chunks; choose so
        # ~2 chunks fit in free HBM alongside activations.
        k = max(1, int(layers_per_stage))

        def layer_fn(chunk_params, x, positions):
            for lp in chunk_params:  # static K iterations, one executable per chunk SIZE
                x = DecoderLayer(cfg).apply({"params": lp}, x, positions)
            return x, positions

        def cached_layer_fn(chunk_params, x, positions, ks, vs, index):
            # decode-mode stage: each layer reads/writes its own (k, v) cache
            # at the shared position index; caches stay in HBM across tokens —
            # only the weights stream.
            new_ks, new_vs = [], []
            for lp, k_c, v_c in zip(chunk_params, ks, vs):
                x, (nk, nv) = DecoderLayer(cfg).apply(
                    {"params": lp}, x, positions, cache=(k_c, v_c, index)
                )
                new_ks.append(nk)
                new_vs.append(nv)
            return x, tuple(new_ks), tuple(new_vs)

        has_embed_norm = getattr(cfg, "embed_norm", False)
        has_learned_pos = getattr(cfg, "positional", "rope") == "learned"

        def embed_fn(stage_params, ids, positions):
            import flax.linen as nn

            from .models.transformer import scale_embed

            embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
            parts = list(stage_params) if isinstance(stage_params, tuple) else [stage_params]
            x = scale_embed(cfg, embed.apply({"params": parts.pop(0)}, ids))
            if has_embed_norm:  # BLOOM: LayerNorm right after the embedding
                x = make_norm(cfg, None).apply({"params": parts.pop(0)}, x)
            if has_learned_pos:
                offset = getattr(cfg, "pos_offset", 0)
                pos = nn.Embed(
                    cfg.max_seq_len + offset, cfg.hidden_size,
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                )
                x = x + pos.apply({"params": parts.pop(0)}, positions + offset)
            return x, positions

        def head_fn(stage_params, x, positions):
            import flax.linen as nn

            norm_params, head_params = stage_params
            # same norm module the monolithic model uses (rmsnorm or layernorm)
            x = make_norm(cfg, None).apply({"params": norm_params}, x)
            if cfg.tie_word_embeddings:
                # exact monolithic semantics: embed.attend promotes to cfg.dtype
                embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
                logits = embed.apply({"params": head_params}, x.astype(cfg.param_dtype), method="attend")
                return logits.astype(jnp.float32)
            logits = x @ head_params["kernel"].astype(cfg.dtype)
            if getattr(cfg, "lm_head_bias", False):
                logits = logits + head_params["bias"].astype(cfg.dtype)
            return logits.astype(jnp.float32)

        head_source = "embed_tokens" if cfg.tie_word_embeddings else "lm_head"
        chunks = [
            tuple(range(start, min(start + k, cfg.num_layers)))
            for start in range(0, cfg.num_layers, k)
        ]
        self._chunks = chunks
        self._embed_fn = embed_fn
        self._head_fn = head_fn
        self._cached_layer_fn = cached_layer_fn
        embed_modules = ["embed_tokens"]
        if has_embed_norm:
            embed_modules.append("embed_norm")
        if has_learned_pos:
            embed_modules.append("pos_embed")
        embed_source = (
            "embed_tokens" if embed_modules == ["embed_tokens"]
            else (lambda: tuple(self._module_params(m) for m in embed_modules))
        )
        plan = make_layer_plan(
            embed=(embed_source, embed_fn),
            layers=[
                # bind per-chunk via default arg (a bare lambda would late-bind
                # every stage to the last chunk)
                (lambda c=chunk: tuple(self._layer_params(i) for i in c), layer_fn)
                for chunk in chunks
            ],
            head=(
                lambda: (self._module_params("final_norm"), self._module_params(head_source)),
                head_fn,
            ),
        )
        super().__init__(
            plan, params=params, weights_loader=weights_loader, exec_device=exec_device,
            hooks=hooks,
        )

    def invalidate_cache(self) -> None:
        self._stack_cache = None
        self._stack_src = None
        self._slice_cache = {}
        super().invalidate_cache()

    def _layer_params(self, i: int):
        if not self._scan_layout:
            return self._module_params(f"layers_{i}")
        # fetch the stacked module once (a loader read is a full eager
        # deserialize — O(layers) re-reads would defeat the streaming), and
        # keep the per-layer slice trees across calls: stable slice identity
        # is what lets the executor's packed cache hit instead of re-packing
        # the whole model every forward.  Swapping self.params requires
        # invalidate_cache(), same as every packed-cache path.
        stack_src = self.params.get("layers") if isinstance(self.params, dict) else None
        if self._stack_cache is None or self._stack_src is not stack_src:
            self._stack_cache = self._module_params("layers")["layer"]
            self._stack_src = stack_src
            self._slice_cache = {}
        cached = self._slice_cache.get(i)
        if cached is None:
            cached = self._slice_cache[i] = jax.tree_util.tree_map(
                lambda x: x[i], self._stack_cache
            )
        return cached

    def __call__(self, input_ids, positions=None):
        input_ids = jnp.asarray(input_ids)
        if self._scan_layout and not (isinstance(self.params, dict) and "layers" in self.params):
            # loader-backed stacks have no identity to validate against — the
            # loader may serve different bytes each call, so refetch per forward
            self._stack_cache = None
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1])[None, :], input_ids.shape)
        return super().__call__(input_ids, positions)

    # -- autoregressive decode (weights stream per token, cache stays in HBM) --
    def init_cache(self, batch_size: int, max_len: int, dtype=None,
                   per_lane_index: bool = False):
        """Per-chunk KV caches on the exec device: ``{"chunks": [(ks, vs), ...],
        "index": scalar}`` where ks/vs are per-layer ``[B, Hkv * D, max_len]``
        (rows flat, positions minor: one layer of a per-head
        :class:`~accelerate_tpu.models.transformer.KVCache`).

        Unlike the monolithic :class:`~accelerate_tpu.models.transformer.KVCache`
        (stacked over depth), chunk-grained caches keep ONE decode executable
        per chunk size and let each stage carry only its own slice.

        ``per_lane_index=True`` makes ``index`` a ``[B]`` vector — each lane
        decodes at its own position, the same masked-step contract the
        continuous-batching slot pool (:mod:`accelerate_tpu.serving`) drives,
        so a host scheduler can run in-flight admission over streaming weights.
        """
        cfg = self.config
        dtype = dtype if dtype is not None else getattr(cfg, "dtype", jnp.bfloat16)
        hd = cfg.resolved_head_dim
        shape = (batch_size, cfg.num_kv_heads * hd, max_len)
        chunks = []
        for c in self._chunks:
            ks = tuple(jax.device_put(jnp.zeros(shape, dtype), self.device) for _ in c)
            vs = tuple(jax.device_put(jnp.zeros(shape, dtype), self.device) for _ in c)
            chunks.append((ks, vs))
        index_shape = (batch_size,) if per_lane_index else ()
        return {
            "chunks": chunks,
            "index": jax.device_put(jnp.zeros(index_shape, jnp.int32), self.device),
        }

    def forward_with_cache(self, input_ids, cache):
        """Incremental forward (prefill S>1 or decode S=1) with the streaming
        schedule: stage ``i+1``'s weights transfer while stage ``i`` computes.
        Returns ``(logits [B,S,V], new_cache)``."""
        input_ids = jnp.asarray(input_ids)
        if self._scan_layout and not (isinstance(self.params, dict) and "layers" in self.params):
            self._stack_cache = None
        index = cache["index"]
        s = input_ids.shape[1]
        # scalar index: lockstep decode; [B] per-lane index: each lane at its
        # own position (the serving masked-step contract — Attention writes
        # per-lane and cached_attention masks per-lane)
        offset = index[:, None] if jnp.ndim(index) else index
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], input_ids.shape) + offset
        transfer_cache: Dict[int, Any] = {}
        n = len(self.plan)
        current = self._prepare_stage(0, transfer_cache)
        x = pos = logits = None
        new_chunks = []
        for i in range(n):
            nxt = self._prepare_stage(i + 1, transfer_cache) if i + 1 < n else None
            operand, spec, treedef = current
            if i == 0:
                carry = self._hook_carry("pre_stage", i, (input_ids, positions))
                x, pos = self._hook_carry(
                    "post_stage", i, self._run_stage(self._embed_fn, operand, spec, treedef, carry)
                )
            elif i == n - 1:
                carry = self._hook_carry("pre_stage", i, (x, pos))
                logits = self._run_stage(self._head_fn, operand, spec, treedef, carry)
                (logits,) = self._hook_carry("post_stage", i, (logits,))
            else:
                ks, vs = cache["chunks"][i - 1]
                carry = self._hook_carry("pre_stage", i, (x, pos, ks, vs, index))
                x, nks, nvs = self._hook_carry(
                    "post_stage", i,
                    self._run_stage(self._cached_layer_fn, operand, spec, treedef, carry),
                )
                new_chunks.append((nks, nvs))
            current = nxt
        return logits, {"chunks": new_chunks, "index": index + s}

    def generate(
        self,
        input_ids,
        max_new_tokens: int = 128,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_token_id: Optional[int] = None,
        pad_token_id: int = 0,
        rng=None,
        cache=None,
    ) -> np.ndarray:
        """Host-driven token loop over :meth:`forward_with_cache` — the
        reference's published benchmark workload (generation under CPU/disk
        offload, ``benchmarks/big_model_inference.py:108-139``): every token
        streams the weights once, double-buffered against compute.

        Returns ``[B, S + max_new_tokens]`` numpy token ids (EOS lanes padded).
        """
        from .models.generation import make_sampler

        input_ids = jnp.asarray(input_ids)
        b, s = input_ids.shape
        if cache is None:
            cache = self.init_cache(b, s + max_new_tokens)
        else:
            idx = jax.device_get(cache["index"])
            used = int(idx.max()) if getattr(idx, "ndim", 0) else int(idx)
            max_len = cache["chunks"][0][0][0].shape[-1]
            if used + s + max_new_tokens > max_len:
                raise ValueError(
                    f"cache max_len {max_len} < {used} already written + prompt {s} + "
                    f"max_new_tokens {max_new_tokens}; init_cache with max_len >= "
                    f"{used + s + max_new_tokens} (dynamic_update_slice would clamp "
                    "out-of-range writes and silently corrupt the cache)"
                )
        if rng is None:
            rng = jax.random.PRNGKey(0)
        sample = make_sampler(
            do_sample=do_sample, temperature=temperature, top_k=top_k, top_p=top_p
        )
        logits, cache = self.forward_with_cache(input_ids, cache)
        rng, sub = jax.random.split(rng)
        tok = np.asarray(sample(logits[:, -1], sub))
        done = np.zeros(b, dtype=bool)
        if eos_token_id is not None:
            done |= tok == eos_token_id
        toks = [tok]
        for _ in range(max_new_tokens - 1):
            if done.all():
                toks.append(np.full((b,), pad_token_id, dtype=tok.dtype))
                continue
            logits, cache = self.forward_with_cache(jnp.asarray(toks[-1])[:, None], cache)
            rng, sub = jax.random.split(rng)
            nxt = np.asarray(sample(logits[:, -1], sub))
            nxt = np.where(done, pad_token_id, nxt)
            if eos_token_id is not None:
                done |= nxt == eos_token_id
            toks.append(nxt)
        return np.concatenate([np.asarray(input_ids), np.stack(toks, axis=1)], axis=1)


