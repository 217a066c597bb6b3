"""The `Accelerator` — user-facing orchestrator.

TPU-native re-design of reference ``src/accelerate/accelerator.py`` (3439 LoC).
The reference wraps mutable torch objects (DDP/FSDP/DeepSpeed engines, patched
``forward``, GradScaler).  Here the orchestration is *compiled*: ``prepare()``
shards state over the device mesh, and the training step — forward, backward,
gradient accumulation, clipping, mixed precision, optimizer update, loss scaling —
is one ``jit``-compiled function whose collectives XLA derives from shardings.

Two usage styles are supported:

**Compiled step** (the TPU-fast path)::

    accelerator = Accelerator(mixed_precision="bf16", gradient_accumulation_steps=4)
    state = accelerator.create_train_state(params=params, tx=optax.adamw(1e-4))
    train_dl = accelerator.prepare(train_dl)
    step = accelerator.compile_train_step(loss_fn)      # loss_fn(params, batch[, rng])
    for batch in train_dl:
        state, metrics = step(state, batch)

**Imperative mirror** (reference loop shape; each call is still a jitted program)::

    for batch in train_dl:
        with accelerator.accumulate():
            grads, metrics = accelerator.compute_gradients(loss_fn, state, batch)
            state = accelerator.apply_gradients(state, grads)

Reference-parity surface implemented here: ``prepare`` (``accelerator.py:1191``),
``accumulate``/``no_sync`` (``:912-1069``), ``backward``-equivalents,
``clip_grad_norm_`` (``:2277-2289``), ``gather``/``gather_for_metrics``/``reduce``/
``pad_across_processes`` (``:2320-2494``), ``set_trigger``/``check_trigger``
(``:2148-2205``), ``join_uneven_inputs`` (``:1072``), ``autocast`` (``:3323``),
``free_memory`` (``:3158``), process-control helpers, ``save_state``/``load_state``
and ``save_model`` (see ``checkpointing.py``), trackers (``:2554-2680``).
"""

from __future__ import annotations

import contextlib
import functools
import gc
import inspect
import math
import os
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from .data_loader import DataLoaderDispatcher, DataLoaderShard, prepare_data_loader, skip_first_batches
from .logging import get_logger
from .optimizer import AcceleratedOptimizer
from .parallel import mesh as mesh_lib
from .parallel.sharding import make_opt_sharding_fn, make_param_sharding_fn
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, GradientState, PartialState
from .telemetry import get_registry as _get_telemetry_registry
from .telemetry import get_tracer as _get_tracer
from .telemetry import metrics as _telemetry_metrics
from .telemetry.cost import CostTable, detect_device_peaks
from .telemetry.flight_recorder import get_flight_recorder
from .telemetry.server import start_debug_server
from .telemetry.tracer import set_device_trace_active
from .telemetry.watchdog import RecompileWatchdog
from .train_state import DynamicLossScale, TrainState, global_norm, tree_finite
from .utils import operations as ops
from .utils.dataclasses import (
    CollectiveKwargs,
    CompilationConfig,
    DataLoaderConfiguration,
    DistributedType,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    GradScalerKwargs,
    InitProcessGroupKwargs,
    MeshConfig,
    ModelParallelPlugin,
    PrecisionPolicy,
    ProjectConfiguration,
    RNGType,
    ZeroPlugin,
    parse_flag_from_env,
)

logger = get_logger(__name__)


def _strip_memory_kind(s):
    if isinstance(s, NamedSharding) and s.memory_kind not in (None, "device"):
        return NamedSharding(s.mesh, s.spec)
    return s


def _is_dataloader_like(obj) -> bool:
    if isinstance(obj, (DataLoaderShard, DataLoaderDispatcher)):
        return True
    try:
        import torch.utils.data as tud

        if isinstance(obj, tud.DataLoader):
            return True
    except ImportError:
        pass
    from .data_loader import SimpleDataLoader

    return isinstance(obj, SimpleDataLoader)


def _is_optimizer_like(obj) -> bool:
    return isinstance(obj, (optax.GradientTransformation, AcceleratedOptimizer))


def _batch_token_count(batch) -> int:
    """Token count of a batch for throughput accounting: the largest 2-D
    integer leaf ([B, S] token ids) wins; batches without one (e.g. CV
    images) fall back to the largest leading dim, i.e. samples."""
    tokens = 0
    samples = 0
    for leaf in jax.tree_util.tree_leaves(batch):
        shape = getattr(leaf, "shape", None)
        if not shape:
            continue
        samples = max(samples, int(shape[0]))
        dtype = getattr(leaf, "dtype", None)
        if len(shape) == 2 and dtype is not None and jnp.issubdtype(dtype, jnp.integer):
            tokens = max(tokens, int(shape[0]) * int(shape[1]))
    return tokens or samples


def _is_model_like(obj) -> bool:
    # flax linen modules (stateless) pass through prepare()
    return hasattr(obj, "apply") and hasattr(obj, "init")


class Accelerator:
    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: Optional[str] = None,
        gradient_accumulation_steps: int = 1,
        cpu: bool = False,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        deepspeed_plugin: Optional[ZeroPlugin] = None,
        fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
        megatron_lm_plugin: Optional[ModelParallelPlugin] = None,
        mesh: Union[None, MeshConfig, Dict[str, int], jax.sharding.Mesh] = None,
        rng_types: Optional[List[Union[str, RNGType]]] = None,
        log_with: Optional[Union[str, List[str]]] = None,
        project_dir: Optional[str] = None,
        project_config: Optional[ProjectConfiguration] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        step_scheduler_with_optimizer: bool = True,
        kwargs_handlers: Optional[List[Any]] = None,
        compilation_config: Optional[CompilationConfig] = None,
        dynamo_backend: Optional[str] = None,  # accepted for API parity; XLA always compiles
        metrics_port: Optional[int] = None,  # debug server port; 0 = ephemeral, None = env/off
    ):
        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        # kwargs handlers (reference accelerator.py:338-375)
        from .utils.dataclasses import FP8RecipeKwargs

        self.scaler_handler: Optional[GradScalerKwargs] = None
        self.collective_handler: Optional[CollectiveKwargs] = None
        self.init_handler: Optional[InitProcessGroupKwargs] = None
        self.fp8_recipe_handler: Optional[FP8RecipeKwargs] = None
        for handler in kwargs_handlers or []:
            if isinstance(handler, GradScalerKwargs):
                self.scaler_handler = handler
            elif isinstance(handler, CollectiveKwargs):
                self.collective_handler = handler
            elif isinstance(handler, InitProcessGroupKwargs):
                self.init_handler = handler
            elif isinstance(handler, FP8RecipeKwargs):
                self.fp8_recipe_handler = handler
        if self.fp8_recipe_handler is None and mixed_precision == "fp8":
            self.fp8_recipe_handler = FP8RecipeKwargs()
        if self.collective_handler is None and any(
            os.environ.get(k)
            for k in ("ACCELERATE_GRAD_REDUCE_DTYPE", "ACCELERATE_COMM_HOOK",
                      "ACCELERATE_POWERSGD_RANK")
        ):
            # launcher-serialized comm tuning (questionnaire comm_config
            # block); an explicitly passed handler took the branch above
            self.collective_handler = CollectiveKwargs.from_env()

        if deepspeed_plugin is None and os.environ.get("ACCELERATE_DEEPSPEED_CONFIG_FILE"):
            # launcher --deepspeed_config_file: DeepSpeed-JSON migration shim
            deepspeed_plugin = ZeroPlugin.from_deepspeed_config(
                os.environ["ACCELERATE_DEEPSPEED_CONFIG_FILE"]
            )
        if deepspeed_plugin is None and parse_flag_from_env("ACCELERATE_USE_DEEPSPEED"):
            deepspeed_plugin = ZeroPlugin()
        if (
            mixed_precision is None
            and not os.environ.get("ACCELERATE_MIXED_PRECISION")
            and deepspeed_plugin is not None
            and getattr(deepspeed_plugin, "inferred_mixed_precision", None)
        ):
            # the DS JSON's fp16/bf16 section stands in for --mixed_precision —
            # but an explicit value (ctor arg or the launcher's env) wins
            mixed_precision = deepspeed_plugin.inferred_mixed_precision
        if fsdp_plugin is None and parse_flag_from_env("ACCELERATE_USE_FSDP"):
            fsdp_plugin = FullyShardedDataParallelPlugin()
        if megatron_lm_plugin is None and parse_flag_from_env("ACCELERATE_USE_MEGATRON_LM"):
            megatron_lm_plugin = ModelParallelPlugin()

        if gradient_accumulation_plugin is None:
            if (
                gradient_accumulation_steps == 1
                and deepspeed_plugin is not None
                and deepspeed_plugin.gradient_accumulation_steps
            ):
                # DS-JSON migration: the config file's value stands in when the
                # user passes none (reference fills "auto" the other way round)
                gradient_accumulation_steps = deepspeed_plugin.gradient_accumulation_steps
            ga_steps = int(os.environ.get("ACCELERATE_GRADIENT_ACCUMULATION_STEPS", gradient_accumulation_steps))
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=ga_steps)
        elif gradient_accumulation_steps != 1:
            raise ValueError("Pass either gradient_accumulation_steps or gradient_accumulation_plugin, not both")

        init_kwargs = self.init_handler.to_kwargs() if self.init_handler else {}
        init_kwargs.pop("backend", None)
        init_kwargs.pop("init_method", None)
        self.state = AcceleratorState(
            mixed_precision=mixed_precision,
            cpu=cpu,
            fsdp_plugin=fsdp_plugin,
            zero_plugin=deepspeed_plugin,
            model_parallel_plugin=megatron_lm_plugin,
            mesh_config=mesh if isinstance(mesh, MeshConfig) else None,
            _from_accelerator=True,
            **init_kwargs,
        )
        if mesh is not None and not isinstance(mesh, MeshConfig):
            self.state.partial_state.set_mesh(mesh)
        elif mesh is None:
            self._default_mesh()

        self.gradient_state = GradientState(gradient_accumulation_plugin=gradient_accumulation_plugin)
        self.device_placement = device_placement
        self.dataloader_config = dataloader_config or DataLoaderConfiguration(split_batches=split_batches)
        if split_batches:
            self.dataloader_config.split_batches = True
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.compilation_config = compilation_config or CompilationConfig.from_env()
        # FSDP activation_checkpointing / ModelParallel recompute_activations
        # lower onto the one remat mechanism (jax.checkpoint over the loss).
        wants_remat = (
            (fsdp_plugin is not None and fsdp_plugin.activation_checkpointing)
            or (megatron_lm_plugin is not None and megatron_lm_plugin.recompute_activations)
        )
        if wants_remat and self.compilation_config.remat_policy == "none":
            self.compilation_config.remat_policy = "full"
        self.rng_types = rng_types or ["generator"]

        self.log_with = [log_with] if isinstance(log_with, str) else (log_with or [])
        self.trackers: List[Any] = []

        self.step = 0  # python-side micro-step counter (GradientState parity)
        self.flag_tensor: Optional[int] = None
        self._models: List[Any] = []
        self._optimizers: List[AcceleratedOptimizer] = []
        self._schedulers: List[AcceleratedScheduler] = []
        self._dataloaders: List[Any] = []
        self._custom_objects: List[Any] = []
        self._save_model_state_pre_hooks: Dict[Any, Callable] = {}
        self._load_model_state_pre_hooks: Dict[Any, Callable] = {}
        self._jit_cache: Dict[Any, Callable] = {}
        self._chunk_info = None  # set by create_train_state under offload_optimizer
        self._offload_master = False
        # Most recent TrainState this accelerator created or stepped — the handle
        # AcceleratedOptimizer.state_dict()/load_state_dict() round-trips through.
        # _latest_state_by_tx disambiguates multiple optimizers: states are also
        # keyed by the identity of their optax transformation.
        self._latest_state: Optional[TrainState] = None
        self._latest_state_by_tx: Dict[int, TrainState] = {}

        # Unified telemetry (telemetry/): the process registry + span tracer
        # every built-in surface records into.  See docs/usage/observability.md.
        self.telemetry = _get_telemetry_registry()
        self.tracer = _get_tracer()
        # Flight recorder + XLA cost accounting + opt-in debug endpoint.
        # The recorder's heartbeat comes from the instrumented train step;
        # the cost table is filled lazily (analyze_costs / a /metrics scrape)
        # so the hot path never waits on a second compile.
        self.flight_recorder = get_flight_recorder()
        self.cost_table = CostTable(self.telemetry)
        self.device_peaks = detect_device_peaks()
        self.debug_server = start_debug_server(
            metrics_port, registry=self.telemetry, recorder=self.flight_recorder
        )
        if self.debug_server is not None:
            self.debug_server.add_collector(self.analyze_costs)

    def _track_state(self, state: TrainState) -> TrainState:
        self._latest_state = state
        if getattr(state, "tx", None) is not None:
            self._latest_state_by_tx[id(state.tx)] = state
        return state

    def analyze_costs(self) -> Dict[str, Any]:
        """Run XLA ``cost_analysis``/``memory_analysis`` over every captured
        executable (train/eval steps compiled by this accelerator) and
        publish the ``train/model_flops`` / ``train/hbm_peak_bytes`` gauges.

        Best-effort and idempotent; the first call re-lowers (and compiles)
        each executable from its recorded abstract signature, so call it off
        the step loop — benches do, and the debug server runs it as a scrape
        collector.  ``train/step_mfu`` updates on the next instrumented step
        once FLOPs are known.
        """
        snap = self.cost_table.analyze_all()
        for name, entry in snap.items():
            if name.startswith("train_step/"):
                if entry.get("flops"):
                    self.telemetry.gauge(
                        "train/model_flops",
                        help="XLA-estimated FLOPs per train step",
                    ).set(entry["flops"])
                if entry.get("hbm_peak_bytes"):
                    self.telemetry.gauge(
                        "train/hbm_peak_bytes",
                        help="train step executable HBM peak (arg+out+temp-alias)",
                    ).set(entry["hbm_peak_bytes"])
        return snap

    # --------------------------------------------------------------- topology
    def _default_mesh(self):
        """Derive the mesh from env (launcher) or plugins: fsdp/tp/pp/sp/ep axes, rest dp."""
        ps = self.state.partial_state
        n = ps.num_devices
        # `accelerate-tpu launch --mesh` serializes the layout to ACCELERATE_MESH
        # (commands/launch.py prepare_launch_env), the mesh analog of the
        # reference's ACCELERATE_*/FSDP_* env IPC (utils/launch.py:152-273).
        env_mesh = os.environ.get("ACCELERATE_MESH")
        if env_mesh:
            from .utils.dataclasses import parse_mesh_spec

            axes = parse_mesh_spec(env_mesh)
            # An explicit mesh must still carry the axes the active plugins
            # shard over — otherwise FSDP/TP would silently degrade to
            # replication (mesh_axis_size returns 1 for missing axes).
            required = []
            fsdp_plugin = self.effective_fsdp_plugin
            if fsdp_plugin is not None and fsdp_plugin.shards_opt_state:
                required.append("fsdp")
            mp = self.state.model_parallel_plugin
            if mp is not None:
                for axis, degree in (
                    ("tp", mp.tp_degree), ("pp", mp.pp_degree),
                    ("sp", mp.sp_degree), ("ep", mp.expert_parallel_degree),
                ):
                    if degree > 1:
                        required.append(axis)
            missing = [a for a in required if a not in axes]
            if missing:
                raise ValueError(
                    f"ACCELERATE_MESH={env_mesh!r} lacks axes {missing} required by the "
                    "active FSDP/ZeRO/model-parallel plugins. Add them to --mesh "
                    f"(e.g. --mesh {','.join(f'{a}=...' for a in missing)},{env_mesh}) "
                    "or drop the plugin flags."
                )
            dcn_spec = os.environ.get("ACCELERATE_DCN_MESH")
            ps.set_mesh(
                MeshConfig(
                    axes=axes,
                    dcn_axes=parse_mesh_spec(dcn_spec) if dcn_spec else {},
                )
            )
            return
        mp = self.state.model_parallel_plugin
        axes: Dict[str, int] = {}
        if mp is not None:
            if mp.pp_degree > 1:
                axes["pp"] = mp.pp_degree
            if mp.sp_degree > 1:
                axes["sp"] = mp.sp_degree
            if mp.tp_degree > 1:
                axes["tp"] = mp.tp_degree
            if mp.expert_parallel_degree > 1:
                axes["ep"] = mp.expert_parallel_degree
        fsdp_plugin = self.effective_fsdp_plugin
        model_par = math.prod(axes.values()) if axes else 1
        if n % model_par != 0:
            raise ValueError(f"Model-parallel degrees {axes} do not divide {n} devices")
        rest = n // model_par
        if fsdp_plugin is not None and fsdp_plugin.shards_opt_state:
            if fsdp_plugin.hybrid and ps.num_processes > 1:
                # FULL_SHARD inside each host (ICI), DP across hosts (DCN).
                axes = {"dp": ps.num_processes, "fsdp": rest // ps.num_processes, **axes}
                mesh = mesh_lib.build_mesh(axes, dcn_axes={"dp": ps.num_processes})
                ps.set_mesh(mesh)
                return
            fsdp_size = fsdp_plugin.fsdp_axis_size if fsdp_plugin.fsdp_axis_size > 0 else rest
            axes = {"dp": rest // fsdp_size, "fsdp": fsdp_size, **axes}
        else:
            axes = {"dp": rest, **axes}
        ps.set_mesh({k: v for k, v in axes.items()})

    @property
    def effective_fsdp_plugin(self) -> Optional[FullyShardedDataParallelPlugin]:
        """ZeRO lowers onto the FSDP sharding mechanism (one substrate, SURVEY §7.7)."""
        if self.state.fsdp_plugin is not None:
            return self.state.fsdp_plugin
        if self.state.zero_plugin is not None:
            return self.state.zero_plugin.to_fsdp_plugin()
        return None

    # ------------------------------------------------------------- properties
    @property
    def distributed_type(self) -> DistributedType:
        return self.state.distributed_type

    @property
    def num_processes(self) -> int:
        return self.state.num_processes

    @property
    def process_index(self) -> int:
        return self.state.process_index

    @property
    def local_process_index(self) -> int:
        return self.state.local_process_index

    @property
    def device(self):
        return self.state.device

    @property
    def mesh(self):
        return self.state.mesh

    @property
    def is_main_process(self) -> bool:
        return self.state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.state.is_last_process

    @property
    def mixed_precision(self) -> str:
        return self.state.mixed_precision

    @property
    def policy(self) -> PrecisionPolicy:
        return self.state.policy

    @property
    def use_distributed(self) -> bool:
        return self.state.use_distributed

    @property
    def _use_loss_scaling(self) -> bool:
        """fp16 dynamic loss scaling, honoring GradScalerKwargs(enabled=False)."""
        return self.policy.use_loss_scaling and (
            self.scaler_handler.enabled if self.scaler_handler else True
        )

    @property
    def sync_gradients(self) -> bool:
        return self.gradient_state.sync_gradients

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value: int):
        self.gradient_state.plugin_kwargs.update({"num_steps": value})

    @property
    def split_batches(self) -> bool:
        return self.dataloader_config.split_batches

    @property
    def dispatch_batches(self):
        return self.dataloader_config.dispatch_batches

    @property
    def even_batches(self) -> bool:
        return self.dataloader_config.even_batches

    @property
    def use_seedable_sampler(self) -> bool:
        return self.dataloader_config.use_seedable_sampler

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    # ---------------------------------------------------------- process ctl
    def wait_for_everyone(self):
        self.state.partial_state.wait_for_everyone()

    def print(self, *args, **kwargs):
        self.state.partial_state.print(*args, **kwargs)

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return self.state.partial_state.split_between_processes(inputs, apply_padding=apply_padding)

    def on_main_process(self, function):
        return self.state.partial_state.on_main_process(function)

    def on_local_main_process(self, function):
        return self.state.partial_state.on_local_main_process(function)

    def on_process(self, function=None, process_index=None):
        return self.state.partial_state.on_process(function, process_index=process_index)

    def on_last_process(self, function):
        return self.state.partial_state.on_last_process(function)

    @contextlib.contextmanager
    def main_process_first(self):
        with self.state.partial_state.main_process_first():
            yield

    @contextlib.contextmanager
    def local_main_process_first(self):
        with self.state.partial_state.local_main_process_first():
            yield

    # ----------------------------------------------------------------- prepare
    def prepare(self, *args, device_placement: Optional[List[bool]] = None):
        """Shard/wrap objects for distributed TPU execution (reference ``accelerator.py:1191``).

        Accepts any mix of dataloaders, optax transformations, LR schedules,
        :class:`TrainState` and flax modules; returns them in the same order.
        """
        result = []
        for obj in args:
            result.append(self._prepare_one(obj))
        return result[0] if len(result) == 1 else tuple(result)

    def _prepare_one(self, obj):
        if _is_dataloader_like(obj):
            prepared = self.prepare_data_loader(obj)
            self._dataloaders.append(prepared)
            return prepared
        if _is_optimizer_like(obj):
            prepared = AcceleratedOptimizer(obj, _accelerator=self)
            self._optimizers.append(prepared)
            return prepared
        if isinstance(obj, TrainState):
            return self._shard_train_state(obj)
        if isinstance(obj, AcceleratedScheduler):
            self._schedulers.append(obj)
            return obj
        if callable(obj) and not _is_model_like(obj):
            # bare optax schedule fn
            sched = AcceleratedScheduler(
                obj,
                step_multiplier=self.num_processes if self.step_scheduler_with_optimizer else 1,
                split_batches=self.split_batches,
            )
            self._schedulers.append(sched)
            return sched
        if _is_model_like(obj):
            obj = self._maybe_apply_fp8(obj)
            self._models.append(obj)
            return obj
        return obj

    def _maybe_apply_fp8(self, model):
        """Under ``mixed_precision="fp8"`` rebuild the model with fp8 matmuls.

        The TE analog (reference ``accelerator.py:1378-1392`` swaps Linear for
        ``te.Linear``): here models that expose a config with ``use_fp8`` get it
        flipped so their Dense layers use :func:`ops.fp8.fp8_dot_general`.
        """
        if self.mixed_precision != "fp8":
            return model
        cfg = getattr(model, "config", None)
        import dataclasses as _dc

        if cfg is not None and _dc.is_dataclass(cfg) and hasattr(cfg, "use_fp8"):
            if getattr(cfg, "quantization", None) is not None:
                import warnings

                warnings.warn(
                    "mixed_precision='fp8': model is int-quantized (weights already "
                    "dequantize into the matmul); leaving it unchanged.",
                    stacklevel=3,
                )
                return model
            recipe = self.fp8_recipe_handler
            replacements = {"use_fp8": True}
            if hasattr(cfg, "fp8_margin"):
                replacements["fp8_margin"] = int(getattr(recipe, "margin", 0) or 0)
            if hasattr(cfg, "fp8_format"):
                replacements["fp8_format"] = str(getattr(recipe, "fp8_format", "HYBRID"))
            return type(model)(_dc.replace(cfg, **replacements))
        import warnings

        warnings.warn(
            f"mixed_precision='fp8': {type(model).__name__} has no fp8-capable config "
            "(a dataclass with a use_fp8 field); its matmuls stay in bf16. Inject "
            "accelerate_tpu.ops.fp8.fp8_dot_general into the model's Dense layers "
            "to opt in.",
            stacklevel=3,
        )
        return model

    def prepare_data_loader(self, data_loader, device_placement: Optional[bool] = None):
        if isinstance(data_loader, (DataLoaderShard, DataLoaderDispatcher)):
            return data_loader
        cfg = self.dataloader_config
        return prepare_data_loader(
            data_loader,
            device=self.device,
            split_batches=cfg.split_batches,
            put_on_device=self.device_placement if device_placement is None else device_placement,
            rng_types=self.rng_types if self.num_processes > 1 else None,
            dispatch_batches=cfg.dispatch_batches,
            even_batches=cfg.even_batches,
            use_seedable_sampler=cfg.use_seedable_sampler,
            non_blocking=cfg.non_blocking,
            prefetch_size=cfg.prefetch_size,
            mesh=self.mesh,
        )

    # ------------------------------------------------------------ train state
    def create_train_state(
        self,
        *,
        params,
        tx: Union[optax.GradientTransformation, AcceleratedOptimizer],
        apply_fn: Optional[Callable] = None,
        rng: Optional[jax.Array] = None,
        seed: Optional[int] = None,
    ) -> TrainState:
        """Create a mesh-sharded :class:`TrainState` (params + optimizer state).

        Placement follows the active plugins: FULL_SHARD shards params & opt state
        over the ``fsdp`` axis, SHARD_GRAD_OP only opt state, etc.  Uses abstract
        init + ``out_shardings`` so full state is never materialized on one device.
        """
        if isinstance(tx, AcceleratedOptimizer):
            tx = tx.optimizer
        if rng is None and seed is not None:
            rng = jax.random.PRNGKey(seed)
        params = self.policy.cast_to_param(params)

        # Host-offloaded optimizer: rebuild tx as chained per-chunk masked
        # transforms so sync-step updates stream the moments through HBM in
        # bounded chunks (utils/chunked_update.py; the whole-state round-trip
        # OOMs exactly in the bigger-than-HBM case the offload targets).
        self._chunk_info = None
        self._offload_master = False
        use_master = False
        fsdp_plugin = self.effective_fsdp_plugin
        if fsdp_plugin is not None and fsdp_plugin.offload_optimizer_nvme_path and (
            not fsdp_plugin.offload_optimizer
            or fsdp_plugin.offload_update_chunk_mb == 0
        ):
            # the disk tier only exists inside the chunked update — silently
            # keeping the state in HBM would defeat the request at exactly the
            # bigger-than-HBM scale it targets
            raise ValueError(
                "offload_optimizer_nvme_path requires offload_optimizer=True and "
                "a non-zero offload_update_chunk_mb: the nvme tier streams the "
                "optimizer state through the chunked update "
                "(utils/chunked_update.py)."
            )
        if (
            fsdp_plugin is not None
            and fsdp_plugin.offload_optimizer
            and fsdp_plugin.offload_update_chunk_mb != 0
        ):
            from .utils.chunked_update import (
                auto_chunk_bytes,
                build_chunked_tx,
                with_master_weights,
            )

            use_master = fsdp_plugin.offload_master_weights
            if use_master is None:
                use_master = self.policy.compute_dtype != jnp.float32
            if use_master:
                # ZeRO-Offload weight split: device holds compute-dtype working
                # weights; the fp32 masters live inside the (host-offloaded,
                # chunked) optimizer state.  Kills both the fp32 param residency
                # and the cast copy in HBM.  tx.init sees the FULL-precision
                # params (masters must seed from fp32, not a bf16 round-trip);
                # the working copy is downcast after creation in init_fn.
                tx = with_master_weights(tx, master_dtype=self.policy.param_dtype)
            self._offload_master = bool(use_master)

            overlap = max(int(fsdp_plugin.offload_update_overlap), 1)
            if fsdp_plugin.offload_update_chunk_mb < -1:
                raise ValueError(
                    f"offload_update_chunk_mb={fsdp_plugin.offload_update_chunk_mb}: "
                    "use a positive size in MB, 0 to disable chunking, or -1 for "
                    "adaptive sizing from free HBM."
                )
            if fsdp_plugin.offload_update_chunk_mb == -1:
                # adaptive: fill the HBM headroom left by the per-device
                # resident set (working params + grads [+ accum buffer], each
                # sharded over fsdp) across the in-flight chunk window
                working_b = jnp.dtype(
                    self.policy.compute_dtype if use_master else self.policy.param_dtype
                ).itemsize
                grad_b = jnp.dtype(
                    self.policy.compute_dtype if use_master else jnp.float32
                ).itemsize
                accum_b = grad_b if self.gradient_accumulation_steps > 1 else 0
                chunk_bytes = auto_chunk_bytes(
                    params,
                    working_bytes_per_element=working_b,
                    grad_bytes_per_element=grad_b,
                    accum_buffer_bytes_per_element=accum_b,
                    shard_degree=mesh_lib.mesh_axis_size(self.mesh, "fsdp"),
                    overlap=overlap,
                )
                logger.info(
                    f"offload_update_chunk_mb=auto resolved to {chunk_bytes >> 20} MB "
                    f"(overlap={overlap})"
                )
            else:
                chunk_bytes = fsdp_plugin.offload_update_chunk_mb * 2**20

            tx, info = build_chunked_tx(tx, params, chunk_bytes)
            nvme_path = fsdp_plugin.offload_optimizer_nvme_path
            if info is None and nvme_path:
                from .utils.chunked_update import _BYTES_PER_ELEMENT

                state_mb = (
                    sum(
                        int(math.prod(getattr(l, "shape", ()) or (1,)))
                        for l in jax.tree_util.tree_leaves(params)
                    )
                    * _BYTES_PER_ELEMENT
                ) >> 20
                raise ValueError(
                    "offload_optimizer_device='nvme' streams the optimizer state "
                    "through bounded chunks, but offload_update_chunk_mb resolves "
                    f"to a single chunk for this model (~{state_mb} MB of state). "
                    f"Set offload_update_chunk_mb below {max(state_mb // 2, 1)} to "
                    "engage the disk tier."
                )
            if info is not None:
                info["master"] = bool(use_master)
                info["params_treedef"] = jax.tree_util.tree_structure(params)
                info["overlap"] = overlap
                if nvme_path:
                    from .utils.chunked_update import DiskChunkStore

                    info["disk_store"] = DiskChunkStore(nvme_path)
                self._chunk_info = info

        grad_accum_dtype = None
        if self.collective_handler and self.collective_handler.grad_reduce_dtype:
            from .utils.dataclasses import TENSOR_DTYPES

            grad_accum_dtype = TENSOR_DTYPES[self.collective_handler.grad_reduce_dtype]
        if use_master and grad_accum_dtype is None:
            grad_accum_dtype = self.policy.compute_dtype  # buffer matches the wire
        powersgd = self._powersgd_config()
        compute_dtype = self.policy.compute_dtype

        def init_fn(p):
            ts = TrainState.create(
                apply_fn=apply_fn,
                params=p,
                tx=tx,
                gradient_accumulation_steps=self.gradient_accumulation_steps,
                use_loss_scaling=self._use_loss_scaling,
                init_loss_scale=(self.scaler_handler.init_scale if self.scaler_handler else 2.0**16),
                loss_scale_kwargs=(
                    {
                        "growth_factor": self.scaler_handler.growth_factor,
                        "backoff_factor": self.scaler_handler.backoff_factor,
                        "growth_interval": self.scaler_handler.growth_interval,
                    }
                    if self.scaler_handler
                    else None
                ),
                rng=rng,
                grad_accum_dtype=grad_accum_dtype,
            )
            if use_master:
                # downcast the working copy AFTER tx.init seeded fp32 masters
                ts = ts.replace(
                    params=jax.tree_util.tree_map(
                        lambda x: x.astype(compute_dtype), ts.params
                    )
                )
            if powersgd is not None:
                from .parallel.compression import powersgd_init

                ts = ts.replace(
                    comm_state=powersgd_init(
                        p,
                        rank=powersgd["rank"],
                        min_compression_size=powersgd["min_size"],
                        key=jax.random.PRNGKey(0),
                        replicas=mesh_lib.mesh_axis_size(self.mesh, "dp"),
                    )
                )
            return ts

        abstract = jax.eval_shape(init_fn, params)
        shardings = self._train_state_shardings(abstract)
        if self._chunk_info is not None:
            return self._track_state(
                self._create_chunked_offload_state(init_fn, params, abstract, shardings)
            )
        return self._track_state(
            self._place_with_offload(init_fn, params, shardings, clear_after=True)
        )

    def _create_chunked_offload_state(self, init_fn, params, abstract, shardings):
        """Creation path for chunked host-offloaded states: one small program
        per optimizer chunk instead of one state-sized program.

        A single init program would hold the fp32 operand, the sliced view,
        and every master/moment as device temps before they reach host memory
        — state-sized HBM, exactly what cannot fit.  Here the non-optimizer
        fields build in one small program, then each chunk's masked-init runs
        with only its own leaves: masters seed from the ORIGINAL fp32 params
        (the chunk programs receive them, not the downcast working copy) and
        stream straight to their host placement.
        """
        from jax.tree_util import tree_flatten, tree_unflatten

        info = self._chunk_info
        disk_store = info.get("disk_store")

        def base_fn(p):
            from jax.memory import Space

            # host-resident source params (init_params_on_host) stream in;
            # the unused opt_state computation is dead code XLA eliminates
            p = jax.device_put(p, Space.Device)
            return init_fn(p).replace(opt_state=())

        base_shardings = self._train_state_shardings(jax.eval_shape(base_fn, params))
        base = self._place_with_offload(base_fn, params, base_shardings, clear_after=True)

        opt_abstract = abstract.opt_state
        opt_shardings = shardings.opt_state
        p_leaves, _ = tree_flatten(params)
        meta = info["meta"]
        n_view = info["n_view_leaves"]
        view_treedef = info["view_treedef"]

        opt_states = []
        for i, (group, masked) in enumerate(zip(info["groups"], info["masked"])):
            orig_ids = sorted({meta[v][0] for v in group})
            orig_pos = {j: k for k, j in enumerate(orig_ids)}

            def chunk_init(chunk_leaves, group=group, masked=masked, orig_pos=orig_pos):
                from jax.memory import Space

                from .utils.chunked_update import fill_view

                # compute happens in device space; host-resident source leaves
                # (init_params_on_host) stream in here (no-op for device args)
                chunk_leaves = jax.device_put(chunk_leaves, Space.Device)
                full_v = fill_view(group, meta, orig_pos, chunk_leaves, n_view)
                return masked.init(tree_unflatten(view_treedef, full_v))

            chunk_leaves = [p_leaves[j] for j in orig_ids]
            jitted_init = jax.jit(chunk_init, out_shardings=opt_shardings[i])
            placed = jitted_init(chunk_leaves)
            if disk_store is not None:
                # nvme tier: persist the freshly initialized chunk to disk and
                # keep only the mmap views in the train state (device_get
                # inside write_chunk doubles as the serialization barrier)
                placed = disk_store.write_chunk(i, placed)
            else:
                # serialize chunk inits: their stream buffers must not coexist
                jax.tree_util.tree_map(
                    lambda x: x.block_until_ready() if isinstance(x, jax.Array) else x,
                    placed,
                )
            # evict just this init program's executable (its HBM plan is
            # chunk-sized but there are many chunks; see _place_with_offload)
            jitted_init.clear_cache()
            opt_states.append(placed)
        return base.replace(opt_state=tuple(opt_states))

    def _train_state_shardings(self, abstract_state):
        plugin = self.effective_fsdp_plugin
        tp_parallel = mesh_lib.mesh_axis_size(self.mesh, "tp") > 1
        if tp_parallel:
            from .parallel.tensor_parallel import make_tp_sharding_fn

            param_rule = make_tp_sharding_fn(self.mesh, plugin)
            opt_rule = make_tp_sharding_fn(self.mesh, plugin, for_opt_state=True)
        else:
            shape_param_rule = make_param_sharding_fn(self.mesh, plugin)
            shape_opt_rule = make_opt_sharding_fn(self.mesh, plugin)
            param_rule = lambda path, x: shape_param_rule(x)
            opt_rule = lambda path, x: shape_opt_rule(x)
        if mesh_lib.mesh_axis_size(self.mesh, "pp") > 1:
            # scan-stacked layer params shard their depth axis over pp so each
            # pipeline stage owns its layer slice at rest (no per-step reshard)
            from .parallel.tensor_parallel import wrap_with_pp_rule

            param_rule = wrap_with_pp_rule(param_rule, self.mesh)
            opt_rule = wrap_with_pp_rule(opt_rule, self.mesh)
        replicated = NamedSharding(self.mesh, PartitionSpec())

        ep_size = mesh_lib.mesh_axis_size(self.mesh, "ep")
        if ep_size > 1:
            # Stacked-expert leaves ([num_experts, ...], module name "experts")
            # shard their leading dim over ep; the dispatch/combine einsums then
            # lower to all-to-alls under GSPMD (parallel/moe.py design).
            from .parallel.sharding import expert_partition_spec
            from .parallel.tensor_parallel import path_to_str

            fsdp_size = mesh_lib.mesh_axis_size(self.mesh, "fsdp")
            min_size = plugin.min_weight_size if plugin is not None else 2**12

            def _expert_wrap(base, shards_fsdp: bool):
                # fsdp composition honors the strategy's shards flag, exactly
                # like the base shape rules do
                eff_fsdp = fsdp_size if shards_fsdp else 1

                def wrapped(path, x):
                    base_sharding = base(path, x)
                    if "experts" in path_to_str(path).split("/"):
                        spec = expert_partition_spec(
                            getattr(x, "shape", ()), ep_size, eff_fsdp, min_size
                        )
                        # keep the base rule's memory kind (host offload applies
                        # to expert leaves like any other param/opt leaf)
                        kind = getattr(base_sharding, "memory_kind", None)
                        if kind is not None and kind != "device":
                            return NamedSharding(self.mesh, spec, memory_kind=kind)
                        return NamedSharding(self.mesh, spec)
                    return base_sharding

                return wrapped

            param_rule = _expert_wrap(
                param_rule, plugin is not None and plugin.shards_params
            )
            opt_rule = _expert_wrap(
                opt_rule, plugin is not None and plugin.shards_opt_state
            )

        # ZeRO-1 vs ZeRO-2: stage 1 keeps the grad buffer replicated like the
        # params (all-reduce comm pattern); stage 2+ shards it over fsdp so XLA
        # reduce-scatters instead (FullyShardedDataParallelPlugin.shards_grads).
        grad_rule = opt_rule if (plugin is None or plugin.shards_grads) else param_rule

        def rule(path, x):
            root = path[0]
            name = getattr(root, "name", getattr(root, "key", None))
            if name == "params":
                return param_rule(path, x)
            if name == "opt_state":
                return opt_rule(path, x)
            if name == "grad_accum":
                # grads are touched every micro-step: keep them in HBM even when
                # the optimizer state is host-offloaded
                return _strip_memory_kind(grad_rule(path, x))
            if name == "comm_state":
                # PowerSGD state: error feedback is per-replica (leading axis
                # over dp); warm-start q is replicated (parallel/compression.py)
                last = path[-1]
                key_name = getattr(last, "key", getattr(last, "name", None))
                if key_name == "error" and mesh_lib.mesh_axis_size(self.mesh, "dp") > 1:
                    return NamedSharding(self.mesh, PartitionSpec("dp"))
                return replicated
            return replicated

        return jax.tree_util.tree_map_with_path(rule, abstract_state)

    def _pin_state_placement(self, state: TrainState) -> TrainState:
        """Constrain a traced state to the placement :meth:`create_train_state`
        gives it.  Left free, XLA's sharding propagation returns leaves the
        policy keeps replicated (norm scales under ``min_weight_size``) sharded
        over ``fsdp``: the state then comes back from its first step placed
        differently from how it went in, and the second step compiles again."""
        shardings = self._train_state_shardings(jax.eval_shape(lambda s: s, state))
        return jax.lax.with_sharding_constraint(state, shardings)

    def _shard_train_state(self, state: TrainState) -> TrainState:
        abstract = jax.eval_shape(lambda s: s, state)
        shardings = self._train_state_shardings(abstract)
        return self._track_state(self._place_with_offload(lambda s: s, state, shardings))

    def _place_with_offload(self, init_fn, operand, shardings, clear_after: bool = False):
        """jit directly into the target shardings, host memory kinds included.

        Emitting pinned-host outputs straight from the init program keeps the
        creation-time HBM peak at the *device-resident* leaves only — the
        two-phase fallback (device first, then device_put to host) transiently
        materializes the whole state in HBM, which is exactly what cannot fit
        in the bigger-than-HBM case the offload targets (1.5B Adam: ~21 GB).
        """
        has_host = any(
            getattr(s, "memory_kind", None) == "pinned_host"
            for s in jax.tree_util.tree_leaves(
                shardings, is_leaf=lambda x: isinstance(x, NamedSharding)
            )
        )
        if has_host:
            try:
                jitted = jax.jit(init_fn, out_shardings=shardings)
                placed = jitted(operand)
                if clear_after:
                    # Loaded executables keep their HBM allocation plans
                    # reserved (init programs are state-sized); for a
                    # bigger-than-HBM state those reservations crowd out the
                    # train step's compile.  The eviction is scoped to THIS
                    # init program's cache (jitted.clear_cache()) — a global
                    # jax.clear_caches() would silently invalidate any steps
                    # the user compiled before creating a second state.
                    jax.tree_util.tree_map(
                        lambda x: x.block_until_ready() if isinstance(x, jax.Array) else x,
                        placed,
                    )
                    jitted.clear_cache()
                return placed
            except (ValueError, NotImplementedError, jax.errors.JaxRuntimeError) as e:
                # older runtimes: trace-time rejection (ValueError /
                # NotImplementedError) or an XLA compile-time RET_CHECK on
                # host-placement annotations (JaxRuntimeError)
                logger.warning_once(
                    f"direct host-memory placement unsupported ({e}); falling back "
                    "to two-phase creation — the full state transiently occupies HBM."
                )
        device_shardings = jax.tree_util.tree_map(_strip_memory_kind, shardings)
        placed = jax.jit(init_fn, out_shardings=device_shardings)(operand)
        if has_host:
            placed = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s) if isinstance(x, jax.Array) else x,
                placed,
                shardings,
            )
        return placed

    def _powersgd_config(self) -> Optional[Dict[str, int]]:
        """Validated PowerSGD settings, or None when the hook is off.

        The hook runs the backward per-replica under a partial-auto
        ``shard_map``: only ``dp`` is a manual axis (reference
        ``DDPCommunicationHookType.POWER_SGD`` analog), while an ``fsdp``
        axis — the HYBRID_SHARD multi-slice topology the hook exists for —
        stays auto, so GSPMD keeps handling the in-replica parameter
        sharding.  Model-parallel axes (tp/pp/sp/ep) remain rejected: their
        rules restructure the computation itself, not just placement.
        """
        handler = self.collective_handler
        if handler is None or handler.comm_hook in (None, "none"):
            return None
        if handler.comm_hook != "powersgd":
            raise ValueError(
                f"Unknown CollectiveKwargs.comm_hook {handler.comm_hook!r}; "
                "supported: 'none', 'powersgd'."
            )
        offending = [
            a for a in self.mesh.axis_names
            if a not in ("dp", "fsdp") and mesh_lib.mesh_axis_size(self.mesh, a) > 1
        ]
        if offending:
            raise ValueError(
                "comm_hook='powersgd' compresses the dp gradient reduction and "
                f"composes with dp/fsdp meshes only; this mesh also shards over "
                f"{offending}. Drop the hook or the model-parallel axes "
                "(PowerSGD targets replicated-DP over slow networks)."
            )
        if "dp" not in self.mesh.axis_names:
            raise ValueError(
                "comm_hook='powersgd' compresses the dp gradient reduction but "
                "this mesh has no dp axis; add one (e.g. mesh={'dp': n_slices, "
                "'fsdp': -1}) or drop the hook."
            )
        if self._use_loss_scaling:
            raise ValueError(
                "comm_hook='powersgd' is bf16/fp32-only: dynamic loss scaling "
                "re-scales gradients across steps, which breaks the error-feedback "
                "carry (stale-scale residuals)."
            )
        return {"rank": int(handler.powersgd_rank), "min_size": int(handler.comm_hook_min_size)}

    # ------------------------------------------------------------- step build
    def _offload_flags(self, warn: bool = False):
        """(offload_params, offload_opt) per the active plugin and backend support.

        ``offload_opt`` means *pinned-host* residency; the nvme tier keeps the
        state on disk instead (chunk programs see plain device arguments fed
        from mmaps), so it reports False here and works on any backend.
        """
        plugin = self.effective_fsdp_plugin
        from .parallel.sharding import supports_host_offload

        offloading_ok = supports_host_offload(self.mesh)
        on_disk = plugin is not None and bool(plugin.offload_optimizer_nvme_path)
        offload_opt = (
            plugin is not None and plugin.offload_optimizer and offloading_ok and not on_disk
        )
        offload_params = plugin is not None and plugin.cpu_offload and offloading_ok
        if (
            warn
            and plugin is not None
            and ((plugin.offload_optimizer and not on_disk) or plugin.cpu_offload)
            and not offloading_ok
        ):
            import warnings

            warnings.warn(
                "Host-memory offload requires the TPU runtime; keeping state in device "
                "memory on this backend.",
                stacklevel=3,
            )
        return offload_params, offload_opt

    def _maybe_remat(self, wrapped_loss: Callable) -> Callable:
        """Apply ``CompilationConfig.remat_policy`` (activation checkpointing).

        One mechanism serves FSDP ``activation_checkpointing``, ModelParallel
        ``recompute_activations`` (both lower to remat_policy="full" at init)
        and the explicit policy dial: the loss computation is wrapped in
        ``jax.checkpoint`` so the backward pass recomputes instead of saving
        intermediates XLA would otherwise keep in HBM.
        """
        name = self.compilation_config.remat_policy
        if name in (None, "none"):
            return wrapped_loss
        policies = {
            "full": None,  # save nothing, recompute everything
            "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
            "dots_saveable": jax.checkpoint_policies.dots_saveable,
            "dots_with_no_batch_dims_saveable": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "everything_saveable": jax.checkpoint_policies.everything_saveable,
            # save the model's checkpoint_name-tagged projection outputs and
            # recompute the rest (models/transformer.py tags q/k/v/o/gate/down
            # as "proj_out"; up_proj is tagged "proj_wide" and deliberately
            # recomputed — see _REMAT_POLICIES there; custom models can tag
            # their own)
            "proj_saveable": jax.checkpoint_policies.save_only_these_names("proj_out"),
        }
        if name not in policies:
            raise ValueError(
                f"Unknown remat_policy {name!r}; expected one of {['none', *policies]}"
            )
        return jax.checkpoint(wrapped_loss, policy=policies[name], prevent_cse=False)

    def _wrap_loss_fn(self, loss_fn: Callable, has_aux: bool):
        """Normalize loss_fn(params, batch[, rng]) and apply the precision policy."""
        try:
            n_args = len(inspect.signature(loss_fn).parameters)
        except (TypeError, ValueError):
            n_args = 2
        policy = self.policy

        def wrapped(params, batch, rng):
            p = policy.cast_to_compute(params)
            if n_args >= 3:
                out = loss_fn(p, batch, rng)
            else:
                out = loss_fn(p, batch)
            if has_aux:
                loss, aux = out
            else:
                loss, aux = out, ()
            return loss.astype(jnp.float32), aux

        return wrapped

    def _constrain_batch(self, batch):
        spec = mesh_lib.data_partition_spec(self.mesh)

        def constrain(x):
            if hasattr(x, "ndim") and x.ndim >= 1:
                return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))
            return x

        return jax.tree_util.tree_map(constrain, batch)

    def compile_train_step(
        self,
        loss_fn: Callable,
        *,
        has_aux: bool = False,
        max_grad_norm: Optional[float] = None,
        max_grad_value: Optional[float] = None,
        donate: bool = True,
        compile_budget: Optional[int] = 4,
    ) -> Callable:
        """Compile the full training step: fwd+bwd+accumulate+clip+update.

        ``loss_fn(params, batch[, rng]) -> loss`` (or ``(loss, aux)`` with
        ``has_aux``).  Returns ``step(state, batch) -> (state, metrics)``.

        The step is telemetry-instrumented (``train/step_time_s`` histogram,
        ``train/tokens_per_s`` + deferred ``train/grad_norm`` gauges) and its
        compiled program sits behind a :class:`RecompileWatchdog`: more than
        ``compile_budget`` distinct ``(shape, dtype)`` call signatures — a
        varying batch shape silently retracing — logs a visible warning.
        ``compile_budget=None`` counts without warning.

        Gradient accumulation is compiled in: for ``num_steps`` N, the optimizer
        applies on every N-th call (and on the final batch of an epoch, mirroring
        ``GradientState.sync_with_dataloader``); other calls only add to the
        gradient buffer — semantics of reference ``accumulate()``/``no_sync``
        (``accelerator.py:912-1069``) without the Python-side no_sync dance.
        """
        if max_grad_norm is None and self.state.zero_plugin is not None:
            # DS-JSON migration: the config file's gradient_clipping stands in
            # when the caller passes none
            max_grad_norm = self.state.zero_plugin.gradient_clipping
        pp_size = mesh_lib.mesh_axis_size(self.mesh, "pp")
        if pp_size > 1 and not getattr(loss_fn, "_pp_aware", False):
            raise ValueError(
                f"The mesh has a pp axis of size {pp_size} but this loss_fn has no "
                "pipeline schedule: the pp devices would silently replicate compute. "
                "Build the loss with accelerate_tpu.parallel.pipeline_lm_loss_fn(model) "
                "(or mark a custom loss that microbatch-schedules over the pp axis "
                "with `loss_fn._pp_aware = True`), or drop pp_degree from "
                "ModelParallelPlugin."
            )
        sp_size = mesh_lib.mesh_axis_size(self.mesh, "sp")
        if sp_size > 1 and not getattr(loss_fn, "_sp_aware", False):
            raise ValueError(
                f"The mesh has an sp axis of size {sp_size} but this loss_fn does not "
                "shard the sequence: those devices would silently replicate compute. "
                "Use a ring-attention model (TransformerConfig(attention_impl='ring') "
                "with lm_loss_fn — parallel/ring_attention.py), mark a custom "
                "sequence-sharded loss with `loss_fn._sp_aware = True`, or drop "
                "sp_degree from ModelParallelPlugin."
            )
        wrapped_loss = self._wrap_loss_fn(loss_fn, has_aux)
        if getattr(loss_fn, "_pipeline_schedule", None) == "1f1b":
            # the 1f1b loss computes gradients inside its own forward
            # (custom_vjp); jax.checkpoint around it would re-run the whole
            # interleaved schedule — and its O(pp) activation stash already IS
            # the memory policy
            if self.compilation_config.remat_policy not in (None, "none"):
                logger.warning_once(
                    "remat_policy is ignored for schedule='1f1b' pipeline losses: "
                    "the interleaved schedule bounds activation memory itself, and "
                    "checkpointing a custom_vjp would re-run it."
                )
        else:
            wrapped_loss = self._maybe_remat(wrapped_loss)
        accum = self.gradient_accumulation_steps
        policy = self.policy
        fp16 = self._use_loss_scaling

        # Chunked offloaded updates (create_train_state built a chained-masked
        # tx): the in-graph apply is disabled and sync steps run one bounded
        # jitted program per chunk instead (utils/chunked_update.py).
        chunk_info = getattr(self, "_chunk_info", None)
        chunked = chunk_info is not None
        # Gradient carry dtype (the DDP fp16/bf16 compression-hook analog):
        # grads are cast to this dtype right after the backward pass, halving
        # the accumulation buffer and any cross-step traffic under bf16.  Note
        # the in-step cross-replica reduction itself rides the *compute* dtype
        # (XLA reduce-scatters the bf16 dot-transpose partials under a bf16
        # policy before this cast); norm/clip math stays fp32, and the
        # in-graph optimizer apply upcasts the carry (master mode upcasts
        # inside the chunk update against fp32 masters instead).
        reduce_dtype = jnp.float32
        master_active = bool(getattr(self, "_offload_master", False))
        if master_active:
            # ZeRO-Offload wire format: grads/avg ride in the compute dtype
            # (the fp32 upcast happens inside the master update) — half the
            # grad buffer and stream traffic.  Applies with or without
            # chunking: create_train_state sized grad_accum to match.
            reduce_dtype = policy.compute_dtype
        explicit_wire = bool(
            self.collective_handler and self.collective_handler.grad_reduce_dtype
        )
        if explicit_wire:
            # With accumulation this sets the buffer dtype; without, it still
            # sets the dtype the gradient TREE materializes in between the
            # backward and the optimizer apply — at 1B params the fp32 default
            # is a 4 GB live set during clipping, halved under bf16.  Norm and
            # clip math stay fp32 (global_norm upcasts per-leaf, fused).
            from .utils.dataclasses import TENSOR_DTYPES

            reduce_dtype = TENSOR_DTYPES[self.collective_handler.grad_reduce_dtype]

        # Chunk applies manage their own donation (make_chunk_apply excludes
        # host-resident args itself), so capture the user's intent BEFORE the
        # offload override: the wrapper replaces state.params with the chunk
        # outputs, so donating the device-resident inputs is safe and saves a
        # params-sized transient per chunk on exactly the bigger-than-HBM
        # configs this path exists for.
        user_donate = donate
        offload_params, offload_opt = self._offload_flags(warn=True)
        if offload_opt or offload_params:
            donate = False  # donation of host-resident buffers is rejected by XLA

        if chunked:
            # the wrapper re-wraps the INPUT param buffers into the next state
            # (params never round-trip the grad program); donation would free them
            donate = False

        powersgd = self._powersgd_config()
        mesh = self.mesh
        dp_present = mesh_lib.mesh_axis_size(mesh, "dp") > 1

        def _powersgd_grads(params, batch, sub, comm_state):
            """Per-replica backward + compressed mean over dp (parallel/compression.py).

            comm_state entries carry the error buffer with a leading replica
            axis sharded over dp; each shard_map block sees its own slice.
            The shard_map is PARTIAL-AUTO (``axis_names={"dp"}``): an fsdp
            axis stays auto, so inside each dp block GSPMD keeps the params,
            the backward and the compression factors fsdp-sharded — the
            HYBRID_SHARD composition (in-slice fsdp, compressed dp across the
            slow network).
            """
            from .parallel.compression import compressed_pmean

            p_leaves, p_def = jax.tree_util.tree_flatten(params)
            entries = p_def.flatten_up_to(comm_state)

            def entry_specs():
                def one(e):
                    if e is None:
                        return None
                    err = PartitionSpec("dp") if dp_present else PartitionSpec()
                    return {"q": PartitionSpec(), "error": err}
                return jax.tree_util.tree_unflatten(p_def, [one(e) for e in entries])

            def run(params, batch, sub, comm_state):
                if sub is not None:
                    # distinct dropout per replica (the SPMD path's global mask
                    # sharded over dp has per-example randomness; match it)
                    sub = jax.random.fold_in(sub, jax.lax.axis_index("dp"))
                local_entries = [
                    e if e is None else {"q": e["q"], "error": e["error"][0] if dp_present else e["error"]}
                    for e in p_def.flatten_up_to(comm_state)
                ]
                local_state = jax.tree_util.tree_unflatten(p_def, local_entries)

                def loss_and_aux(p):
                    loss, aux = wrapped_loss(p, batch, sub)
                    return loss, (loss, aux)

                grads, (loss, aux) = jax.grad(loss_and_aux, has_aux=True)(params)
                grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
                ghat, new_local = compressed_pmean(grads, local_state, "dp")
                ghat = jax.tree_util.tree_map(lambda g: g.astype(reduce_dtype), ghat)
                new_entries = [
                    e if e is None else {"q": e["q"], "error": e["error"][None] if dp_present else e["error"]}
                    for e in p_def.flatten_up_to(new_local)
                ]
                new_comm = jax.tree_util.tree_unflatten(p_def, new_entries)
                loss = jax.lax.pmean(loss, "dp")
                aux = jax.tree_util.tree_map(lambda a: jax.lax.pmean(a, "dp"), aux)
                return ghat, loss, aux, new_comm

            # mirror _constrain_batch: only leaves with a batch dim shard over
            # dp; scalars/rank-0 leaves replicate
            data_spec = jax.tree_util.tree_map(
                lambda x: PartitionSpec("dp") if getattr(x, "ndim", 0) >= 1 else PartitionSpec(),
                batch,
            )
            rng_spec = None if sub is None else PartitionSpec()
            return mesh_lib.shard_map(
                run,
                mesh=mesh,
                axis_names={"dp"},
                in_specs=(PartitionSpec(), data_spec, rng_spec, entry_specs()),
                out_specs=(PartitionSpec(), PartitionSpec(), PartitionSpec(), entry_specs()),
                check_vma=False,
            )(params, batch, sub, comm_state)

        def _step(state: TrainState, batch, force_sync, sync_mode=None):
            """``sync_mode``: None = runtime sync decision (the standard single
            program); True/False = chunked mode's statically specialized sync /
            micro programs — the sync program emits ``avg`` (aliased into the
            donated accumulation buffer) and no ``grad_accum``, the micro
            program the reverse, saving a params-sized buffer each."""
            from jax.memory import Space

            # Host-offloaded params stream to HBM for the step and back after
            # (ZeRO-offload; reference DeepSpeedPlugin.offload_*_device).  The
            # optimizer state is only touched inside the apply branch below, so
            # its round-trip happens exclusively on sync steps.
            if offload_params:
                state = state.replace(params=jax.device_put(state.params, Space.Device))
            batch = self._constrain_batch(batch)
            if state.rng is not None:
                new_rng, sub = jax.random.split(state.rng)
            else:
                new_rng, sub = None, None

            scale = state.loss_scale.scale if fp16 else jnp.float32(1.0)

            new_comm = state.comm_state
            if powersgd is not None:
                grads, loss, aux, new_comm = _powersgd_grads(
                    state.params, batch, sub, state.comm_state
                )
            else:
                def scaled_loss(p):
                    loss, aux = wrapped_loss(p, batch, sub)
                    return loss * scale, (loss, aux)

                grads, (loss, aux) = jax.grad(scaled_loss, has_aux=True)(state.params)
                grads = jax.tree_util.tree_map(
                    lambda g: (g.astype(jnp.float32) / scale).astype(reduce_dtype), grads
                )

            count = state.micro_step + 1
            if accum > 1:
                acc = jax.tree_util.tree_map(lambda a, g: a + g, state.grad_accum, grads)
                if sync_mode is None:
                    do_sync = jnp.logical_or(force_sync, count >= accum)
                else:
                    do_sync = jnp.asarray(bool(sync_mode))
            else:
                acc = grads
                do_sync = jnp.asarray(True)

            # Norm + clip without materializing a second full-precision grad
            # tree: the norm reduces the buffer per-leaf in fp32 (fused, no
            # buffer), and the 1/count average folds into one elementwise
            # scale with the clip factor.  norm(acc)/count == norm(avg), so
            # the reported grad_norm and the clip math are unchanged.  This
            # halves the step's transient footprint — decisive when the
            # buffer is params-sized and HBM is the constraint (zero3 bench).
            inv_count = 1.0 / count.astype(jnp.float32)
            gnorm = global_norm(acc) * inv_count
            scale_factor = inv_count
            if max_grad_norm is not None:
                clip = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-6))
                scale_factor = scale_factor * clip
            # Offloaded-master updates upcast against fp32 masters, so their
            # wire rides reduce_dtype; an EXPLICIT grad_reduce_dtype keeps the
            # whole carry in the wire dtype (the optimizer apply upcasts
            # per-leaf against its fp32 state).  Otherwise the plain in-graph
            # apply keeps the documented fp32 avg.
            avg_dtype = (
                reduce_dtype if (chunked or master_active or explicit_wire) else jnp.float32
            )
            avg = jax.tree_util.tree_map(
                lambda g: (g.astype(jnp.float32) * scale_factor).astype(avg_dtype), acc
            )
            if max_grad_value is not None:
                avg = jax.tree_util.tree_map(
                    lambda g: jnp.clip(g, -max_grad_value, max_grad_value), avg
                )
            finite = tree_finite(avg) if fp16 else jnp.asarray(True)

            def do_apply(operand):
                st, g = operand
                if offload_opt:
                    st = st.replace(opt_state=jax.device_put(st.opt_state, Space.Device))
                new = st.apply_gradients(g)
                if offload_opt:
                    new = new.replace(opt_state=jax.device_put(new.opt_state, Space.Host))
                return new

            def skip_apply(operand):
                st, _ = operand
                return st

            applied = jnp.logical_and(do_sync, finite)
            # bookkeeping: reset buffers on sync (applied or overflow-skipped)
            new_accum = None
            if accum > 1:
                zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
                new_accum = jax.tree_util.tree_map(
                    lambda z, a: jnp.where(do_sync, z, a), zeros, acc
                )
            new_micro = jnp.where(do_sync, 0, count)
            new_scale = None
            if fp16:
                new_scale = jax.lax.cond(
                    do_sync,
                    lambda ls: ls.update(finite),
                    lambda ls: ls,
                    state.loss_scale,
                )

            metrics = {
                "loss": loss,
                "grad_norm": gnorm,
                "applied": applied,
                "overflow": jnp.logical_and(do_sync, jnp.logical_not(finite)),
            }
            if has_aux:
                metrics["aux"] = aux

            if chunked:
                # Slim outputs: params and (host-resident) opt state are NOT
                # program outputs — an un-donated pass-through output would be
                # a params-sized HBM copy, which is exactly the headroom the
                # chunked offload path exists to free.  The wrapper re-wraps
                # the input buffers with these small fields.  The grad wire
                # rides reduce_dtype (XLA fuses the fp32 clip math into the
                # cast, so no fp32 tree materializes).
                small = {
                    "micro_step": new_micro,
                    "rng": new_rng,
                    # the specialized sync program drops the (all-zeros) buffer
                    # so `avg` can alias the donated accumulation input; the
                    # wrapper refills zeros afterwards
                    "grad_accum": None if sync_mode is True else new_accum,
                    "loss_scale": new_scale,
                    "comm_state": new_comm,
                }
                if sync_mode is False:
                    return small, metrics
                return small, metrics, avg

            new_state = jax.lax.cond(applied, do_apply, skip_apply, (state, avg))
            if accum > 1:
                new_state = new_state.replace(grad_accum=new_accum)
            new_state = new_state.replace(
                micro_step=new_micro, rng=new_rng, comm_state=new_comm
            )
            if fp16:
                new_state = new_state.replace(loss_scale=new_scale)

            if offload_params:
                new_state = new_state.replace(params=jax.device_put(new_state.params, Space.Host))
            elif not (offload_opt or chunked):
                new_state = self._pin_state_placement(new_state)

            return new_state, metrics

        if chunked and accum > 1:
            # Statically specialized micro/sync programs with the accumulation
            # buffer as its own donated argument: XLA aliases it into the
            # same-shaped new_accum (micro) or avg (sync) output, saving a
            # params-sized buffer each — the margin on bigger-than-HBM configs.
            def _split(sync_flag):
                def fn(state_rest, accum_buf, batch):
                    return _step(
                        state_rest.replace(grad_accum=accum_buf), batch,
                        jnp.asarray(sync_flag), sync_mode=sync_flag,
                    )
                return jax.jit(fn, donate_argnums=(1,))

            prog_micro, prog_sync = _split(False), _split(True)

            def jitted(state, batch, synced):
                rest = state.replace(grad_accum=None)
                prog = prog_sync if synced else prog_micro
                out = prog(rest, state.grad_accum, batch)
                return out if synced else (*out, None)
        elif chunked:
            _jit_once = jax.jit(_step, donate_argnums=())

            def jitted(state, batch, synced):
                return _jit_once(state, batch, jnp.asarray(True))
        else:
            jitted = jax.jit(_step, donate_argnums=(0,) if donate else ())

        # Recompile watchdog over the compiled program: every distinct
        # (shape, dtype) call signature is a (re)trace; past the budget the
        # silent-retrace failure mode becomes a logged warning + gauge.
        jitted = RecompileWatchdog(
            jitted,
            name=f"train_step/{getattr(loss_fn, '__name__', 'loss')}",
            budget=compile_budget,
            registry=self.telemetry,
            span="train/dispatch",
        )

        # python mirror of the chunked path's micro-step counter (see above)
        _micro_mirror: Dict[str, Any] = {"ref": None, "micro": 0}

        @functools.wraps(loss_fn)
        def step(state, batch):
            gs = self.gradient_state
            force = bool(
                (gs.sync_with_dataloader and gs.end_of_dataloader) or gs.sync_each_batch
            )
            if chunked:
                # the layout was captured at compile time; a state from a
                # different create_train_state call has a different treedef
                if jax.tree_util.tree_structure(state.params) != chunk_info["params_treedef"]:
                    raise ValueError(
                        "This compiled step's chunked-offload layout does not match "
                        "the given state's param tree. compile_train_step binds to "
                        "the most recent create_train_state — recompile the step "
                        "after creating each offloaded train state."
                    )
                # Sync-ness derives from the state's micro-step counter, but a
                # D2H read every call would serialize the whole pipeline (async
                # dispatch lost for the full training loop, not just sync
                # steps).  A python mirror tracks the counter for states THIS
                # step emitted (identity-checked via weakref); the device value
                # is read only on re-alignment — first call, checkpoint
                # restore, or a state from elsewhere.
                if accum > 1:
                    known = _micro_mirror.get("ref")
                    if known is not None and known() is state:
                        micro = _micro_mirror["micro"]
                    else:
                        micro = int(jax.device_get(state.micro_step))
                    synced = force or (micro + 1 >= accum)
                else:
                    synced = True
                small, metrics, avg = jitted(state, batch, synced)
                new_state = state.replace(
                    micro_step=small["micro_step"],
                    rng=small["rng"],
                    comm_state=small["comm_state"],
                )
                if small["grad_accum"] is not None:
                    new_state = new_state.replace(grad_accum=small["grad_accum"])
                if small["loss_scale"] is not None:
                    new_state = new_state.replace(loss_scale=small["loss_scale"])
                self.step = 0 if synced else self.step + 1
                if synced:
                    # fp16 finiteness folds into the in-graph applied flag
                    if bool(jax.device_get(metrics["applied"])):
                        new_state = self._apply_chunked(
                            new_state, avg, chunk_info,
                            opt_on_host=offload_opt, params_on_host=offload_params,
                            donate=user_donate,
                        )
                    if accum > 1:
                        # the sync program dropped the accumulation buffer so
                        # avg could alias it; refill zeros (after the chunk
                        # applies, when avg's peak has passed)
                        zkey = ("accum_zeros", id(chunk_info))
                        zfn = self._jit_cache.get(zkey)
                        if zfn is None:
                            # donate avg: the zeros alias its (now dead) buffer
                            # instead of allocating a third params-sized tensor
                            zfn = self._jit_cache[zkey] = jax.jit(
                                lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
                                donate_argnums=(0,),
                            )
                        new_state = new_state.replace(grad_accum=zfn(avg))
                if accum > 1:
                    _micro_mirror["micro"] = 0 if synced else micro + 1
                    _micro_mirror["ref"] = weakref.ref(new_state)
                self._track_state(new_state)
                gs._set_sync_gradients(synced)
                return new_state, metrics

            if getattr(self, "_chunk_info", None) is not None:
                raise ValueError(
                    "An offload-chunked train state exists but this step was "
                    "compiled before create_train_state: the in-graph apply "
                    "would round-trip the whole host-resident optimizer state "
                    "through HBM. Call create_train_state first, then "
                    "compile_train_step."
                )
            new_state, metrics = jitted(state, batch, force)
            # python-side GradientState mirror (reference _do_sync, accelerator.py:1001-1008);
            # a forced sync resets the counter so it stays aligned with micro_step.
            self.step += 1
            synced = force or (self.step % max(accum, 1) == 0)
            if synced:
                self.step = 0
            self._track_state(new_state)
            gs._set_sync_gradients(synced)
            return new_state, metrics

        # Telemetry wrapper: a disabled registry short-circuits to the raw
        # step (one boolean check); enabled it costs two perf_counter reads,
        # a histogram bisect, and gauge stores.  grad_norm/loss gauges hold
        # the live device values — the D2H happens at snapshot time, never
        # in-loop, so async dispatch is preserved.
        registry = self.telemetry
        tracer = self.tracer
        recorder = self.flight_recorder
        cost_table = self.cost_table
        peak_flops = self.device_peaks.flops_per_s
        cost_key = f"train_step/{getattr(loss_fn, '__name__', 'loss')}"
        step_hist = registry.histogram("train/step_time_s", help="train step wall time (s)")
        steps_total = registry.counter("train/steps_total", help="train step calls")
        tokens_total = registry.counter("train/tokens_total", help="tokens (or samples) stepped")
        tps_gauge = registry.gauge("train/tokens_per_s", help="last-step token throughput")
        gnorm_gauge = registry.gauge("train/grad_norm", help="last-step gradient norm (deferred)")
        loss_gauge = registry.gauge("train/loss", help="last-step loss (deferred)")
        mfu_gauge = registry.gauge(
            "train/step_mfu", help="measured FLOPs/s over chip peak, clamped to (0, 1]"
        )
        flops_gauge = registry.gauge(
            "train/model_flops", help="XLA-estimated FLOPs per train step"
        )
        hbm_gauge = registry.gauge(
            "train/hbm_peak_bytes", help="train step executable HBM peak (arg+out+temp-alias)"
        )

        @functools.wraps(step)
        def instrumented(state, batch):
            if not _telemetry_metrics.enabled():
                return step(state, batch)
            if not cost_table.captured(cost_key):
                # First call: record only the abstract signature (no buffers)
                # so analyze_costs() can re-lower off the hot path. The
                # sync-flag value is shape-irrelevant. Python-dispatch paths
                # (accumulation splitter, chunked offload) yield graceful
                # None downstream — jitted has no .lower there.
                cost_table.capture(cost_key, jitted, (state, batch, False))
                try:
                    shapes = sorted(
                        {
                            str(tuple(leaf.shape))
                            for leaf in jax.tree_util.tree_leaves(batch)
                            if hasattr(leaf, "shape")
                        }
                    )
                except Exception:
                    shapes = None
                recorder.record("train/capture", name=cost_key, batch_shapes=shapes)
            # the span covers the whole wrapper: its self time (less
            # train/dispatch) is this repo's Python per step, the watchdog's
            # signature pass, the gauges and the heartbeat included
            with tracer.span("train/step"):
                t0 = time.perf_counter()
                new_state, metrics = step(state, batch)
                dt = time.perf_counter() - t0
                step_hist.observe(dt)
                steps_total.inc()
                ntok = _batch_token_count(batch)
                if ntok:
                    tokens_total.inc(ntok)
                    tps_gauge.set(ntok / dt if dt > 0 else 0.0)
                loss = None
                if isinstance(metrics, dict):
                    if metrics.get("grad_norm") is not None:
                        gnorm_gauge.set(metrics["grad_norm"])
                    if metrics.get("loss") is not None:
                        loss = metrics["loss"]
                        loss_gauge.set(loss)
                # Cost-derived gauges: dict lookups only; None until someone ran
                # analyze_costs() (bench, scrape collector, flight dump).
                flops = cost_table.flops(cost_key)
                if flops:
                    flops_gauge.set(flops)
                    if dt > 0:
                        mfu_gauge.set(min(1.0, flops / dt / peak_flops))
                hbm = cost_table.hbm_peak_bytes(cost_key)
                if hbm:
                    hbm_gauge.set(hbm)
                # Progress heartbeat: feeds the stall detector and /healthz; the
                # loss stays a live device value until a dump coerces it.
                recorder.heartbeat(
                    "train/step", step=steps_total.value, dt_s=dt, tokens=ntok, loss=loss
                )
            return new_state, metrics

        instrumented._jitted = jitted
        instrumented._watchdog = jitted
        return instrumented

    def _apply_chunked(
        self, state: TrainState, avg, info, opt_on_host: bool, params_on_host: bool,
        donate: bool = True,
    ) -> TrainState:
        """Optimizer update in bounded HBM chunks (utils/chunked_update.py).

        Each chunk's moments stream host→HBM→host inside its own jitted
        program, keeping peak HBM at O(chunk) instead of the whole optimizer
        state.  The compiled chunk fns are cached on ``info`` itself (one
        chunk layout per create_train_state call — a shared key would reuse
        another state's treedef).
        """
        from .utils.chunked_update import make_chunk_apply

        disk = info.get("disk_store")
        key = ("fns", opt_on_host, params_on_host, donate)
        fns = info.get(key)
        if fns is None:
            fns = info[key] = [
                make_chunk_apply(
                    group, masked, info,
                    opt_on_host=opt_on_host, params_on_host=params_on_host,
                    donate=donate, opt_on_disk=disk is not None,
                )
                for group, masked in zip(info["groups"], info["masked"])
            ]
        p_leaves, p_def = jax.tree_util.tree_flatten(state.params)
        g_leaves = jax.tree_util.tree_flatten(avg)[0]
        opt_states = list(state.opt_state)
        new_p = list(p_leaves)
        # Bounded in-flight window: the chunk programs are mutually independent
        # (data deps between chunks sharing a sliced leaf are tracked by the
        # arrays themselves), so unbounded async dispatch would let ALL their
        # stream buffers coexist in HBM — the O(opt state) peak this path
        # exists to avoid.  The window is `overlap` wide (default 1,
        # serialized — measured faster than the 2-deep double-buffer on the
        # bench rig, see ZeroPlugin.offload_update_overlap); overlap=2
        # overlaps chunk N's host write-back with chunk N+1's host read at
        # peak = overlap * chunk transients.
        overlap = max(int(info.get("overlap", 1)), 1)

        def _drain(entry):
            i, outputs = entry
            if disk is not None:
                # nvme tier: persist the updated subtree (device_get inside
                # write_chunk doubles as the completion barrier) and swap the
                # mmap views back into the state
                opt_states[i] = disk.write_chunk(i, opt_states[i])
                return
            # A chunk output can be donated to a LATER chunk before we block on
            # it (a sliced leaf spanning two chunks): skip deleted buffers —
            # the consuming program's own completion handle covers them.
            for x in outputs:
                if isinstance(x, jax.Array) and not x.is_deleted():
                    x.block_until_ready()
                    return

        inflight: List[Any] = []
        for i, (fn, orig_ids) in enumerate(fns):
            if len(inflight) >= overlap:
                _drain(inflight.pop(0))
            chunk_p = [new_p[j] for j in orig_ids]
            chunk_g = [g_leaves[j] for j in orig_ids]
            new_chunk_p, opt_states[i] = fn(chunk_p, chunk_g, opt_states[i])
            # completion handles: prefer the new opt-state leaves (never fed to
            # a later chunk in this loop), fall back to the param outputs (an
            # empty-state tx like sgd has no opt arrays)
            inflight.append(
                (i, jax.tree_util.tree_leaves(opt_states[i]) + list(new_chunk_p))
            )
            for pos, j in enumerate(orig_ids):
                new_p[j] = new_chunk_p[pos]
        while inflight:
            _drain(inflight.pop(0))
        return state.replace(
            params=jax.tree_util.tree_unflatten(p_def, new_p),
            opt_state=tuple(opt_states),
            step=state.step + 1,
        )

    def compile_eval_step(
        self, eval_fn: Callable, *, donate: bool = False,
        compile_budget: Optional[int] = 4,
    ) -> Callable:
        """Compile an eval/predict step: ``eval_fn(params, batch[, rng])`` with policy cast.

        Instrumented like the train step: ``eval/step_time_s`` histogram and a
        recompile watchdog with the same ``compile_budget`` semantics.
        """
        wrapped = self._wrap_loss_fn(eval_fn, has_aux=False)
        offload_params, _ = self._offload_flags()

        def _step(state_or_params, batch):
            params = state_or_params.params if isinstance(state_or_params, TrainState) else state_or_params
            if offload_params:
                from jax.memory import Space

                params = jax.device_put(params, Space.Device)
            batch = self._constrain_batch(batch)
            out, _ = wrapped(params, batch, None)
            return self.policy.cast_to_output(out)

        jitted = RecompileWatchdog(
            jax.jit(_step, donate_argnums=()),
            name=f"eval_step/{getattr(eval_fn, '__name__', 'eval')}",
            budget=compile_budget,
            registry=self.telemetry,
        )
        registry = self.telemetry
        tracer = self.tracer
        cost_table = self.cost_table
        cost_key = f"eval_step/{getattr(eval_fn, '__name__', 'eval')}"
        eval_hist = registry.histogram("eval/step_time_s", help="eval step wall time (s)")

        @functools.wraps(eval_fn)
        def instrumented(state_or_params, batch):
            if not _telemetry_metrics.enabled():
                return jitted(state_or_params, batch)
            if not cost_table.captured(cost_key):
                cost_table.capture(cost_key, jitted, (state_or_params, batch))
            t0 = time.perf_counter()
            with tracer.span("eval/step"):
                out = jitted(state_or_params, batch)
            eval_hist.observe(time.perf_counter() - t0)
            return out

        instrumented._jitted = jitted
        return instrumented

    # ----------------------------------------------------- imperative mirror
    @contextlib.contextmanager
    def accumulate(self, *models):
        """Reference ``accumulate()`` context (``accelerator.py:1027``)."""
        self._do_sync()
        yield

    def _do_sync(self):
        gs = self.gradient_state
        if gs.sync_with_dataloader and gs.end_of_dataloader:
            self.step = 0
            gs._set_sync_gradients(True)
        else:
            self.step += 1
            gs._set_sync_gradients((self.step % self.gradient_accumulation_steps) == 0)
        if gs.sync_each_batch:
            gs._set_sync_gradients(True)

    @contextlib.contextmanager
    def no_sync(self, model=None):
        """Reference ``no_sync`` (``accelerator.py:1056-1068``): skip grad sync."""
        old = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(old)

    def compute_gradients(self, loss_fn: Callable, state: TrainState, batch, has_aux: bool = False):
        """Jitted value-and-grad (the ``backward()`` analog).

        Returns ``(grads, metrics)``; grads are fp32 and unscaled.
        """
        key = ("grad", loss_fn, has_aux)
        if key not in self._jit_cache:
            wrapped = self._wrap_loss_fn(loss_fn, has_aux)
            offload_params, _ = self._offload_flags()

            def _grad(state, batch):
                if offload_params:
                    from jax.memory import Space

                    state = state.replace(params=jax.device_put(state.params, Space.Device))
                if state.rng is not None:
                    _, sub = jax.random.split(state.rng)
                else:
                    sub = None
                scale = state.loss_scale.scale if state.loss_scale is not None else jnp.float32(1.0)

                def scaled(p):
                    loss, aux = wrapped(p, batch, sub)
                    return loss * scale, (loss, aux)

                grads, (loss, aux) = jax.grad(scaled, has_aux=True)(state.params)
                grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32) / scale, grads)
                return grads, {"loss": loss, "aux": aux}

            self._jit_cache[key] = jax.jit(_grad)
        return self._jit_cache[key](state, batch)

    def backward(self, *args, **kwargs):
        """Unsupported verbatim: JAX has no imperative autograd tape.

        Use :meth:`compute_gradients` + :meth:`apply_gradients` for the reference
        loop shape, or :meth:`compile_train_step` for the fused fast path.
        """
        raise RuntimeError(
            "accelerator.backward(loss) has no meaning on the TPU-native stack: gradients are "
            "computed functionally. Use `grads, m = accelerator.compute_gradients(loss_fn, state, batch)` "
            "then `state = accelerator.apply_gradients(state, grads)`, or the fused "
            "`accelerator.compile_train_step(loss_fn)`."
        )

    def apply_gradients(self, state: TrainState, grads, max_grad_norm: Optional[float] = None):
        """Apply (or accumulate) gradients per ``GradientState.sync_gradients``."""
        offload_params, offload_opt = self._offload_flags()
        offloading = offload_params or offload_opt
        if not self.sync_gradients:
            key = "accumulate_grads"
            if key not in self._jit_cache:
                def _acc(state, grads):
                    # advance the rng even on non-sync micro-steps so dropout masks differ
                    new_rng = jax.random.split(state.rng)[0] if state.rng is not None else None
                    if state.grad_accum is not None:
                        acc = jax.tree_util.tree_map(lambda a, g: a + g, state.grad_accum, grads)
                        return state.replace(grad_accum=acc, micro_step=state.micro_step + 1, rng=new_rng)
                    return state.replace(micro_step=state.micro_step + 1, rng=new_rng)

                self._jit_cache[key] = jax.jit(_acc, donate_argnums=() if offloading else (0,))
            return self._track_state(self._jit_cache[key](state, grads))
        key = ("apply_grads", max_grad_norm)
        if key not in self._jit_cache:
            def _apply(state, grads):
                if offloading:
                    # Stream host-offloaded leaves to HBM for the update and back
                    # (same round-trip the compiled step does on sync steps).
                    from jax.memory import Space

                    if offload_params:
                        state = state.replace(params=jax.device_put(state.params, Space.Device))
                    if offload_opt:
                        state = state.replace(opt_state=jax.device_put(state.opt_state, Space.Device))
                count = state.micro_step + 1
                if state.grad_accum is not None:
                    grads = jax.tree_util.tree_map(lambda a, g: a + g, state.grad_accum, grads)
                grads = jax.tree_util.tree_map(lambda g: g / count.astype(jnp.float32), grads)
                if max_grad_norm is not None:
                    gnorm = global_norm(grads)
                    clip = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-6))
                    grads = jax.tree_util.tree_map(lambda g: g * clip, grads)
                finite = tree_finite(grads) if state.loss_scale is not None else jnp.asarray(True)
                new = jax.lax.cond(
                    finite, lambda op: op[0].apply_gradients(op[1]), lambda op: op[0], (state, grads)
                )
                if state.grad_accum is not None:
                    new = new.replace(
                        grad_accum=jax.tree_util.tree_map(jnp.zeros_like, state.grad_accum)
                    )
                if state.loss_scale is not None:
                    new = new.replace(loss_scale=state.loss_scale.update(finite))
                if state.rng is not None:
                    new = new.replace(rng=jax.random.split(state.rng)[0])
                if offloading:
                    from jax.memory import Space

                    if offload_params:
                        new = new.replace(params=jax.device_put(new.params, Space.Host))
                    if offload_opt:
                        new = new.replace(opt_state=jax.device_put(new.opt_state, Space.Host))
                return new.replace(micro_step=jnp.zeros((), jnp.int32))

            self._jit_cache[key] = jax.jit(_apply, donate_argnums=() if offloading else (0,))
        return self._track_state(self._jit_cache[key](state, grads))

    def clip_grad_norm_(self, grads, max_norm: float, norm_type: float = 2.0):
        """Clip a gradient pytree by global norm (reference ``accelerator.py:2242-2289``)."""
        if norm_type != 2.0:
            raise NotImplementedError("Only L2 global-norm clipping is supported on TPU")
        key = ("clip_norm", float(max_norm))
        if key not in self._jit_cache:
            def _clip(grads):
                gnorm = global_norm(grads)
                factor = jnp.minimum(1.0, max_norm / (gnorm + 1e-6))
                return jax.tree_util.tree_map(lambda g: g * factor, grads), gnorm

            self._jit_cache[key] = jax.jit(_clip)
        return self._jit_cache[key](grads)

    def clip_grad_value_(self, grads, clip_value: float):
        key = ("clip_value", float(clip_value))
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(
                lambda g: jax.tree_util.tree_map(lambda x: jnp.clip(x, -clip_value, clip_value), g)
            )
        return self._jit_cache[key](grads)

    # ------------------------------------------------------------ collectives
    def gather(self, tensor):
        return ops.gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather + drop end-of-epoch duplicate samples (reference ``accelerator.py:2352-2417``)."""
        all_tensors = all(ops.is_tensor(leaf) for leaf in jax.tree_util.tree_leaves(input_data))
        if not all_tensors or use_gather_object:
            data = ops.gather_object(input_data)
        else:
            data = ops.gather(input_data)
        if self.gradient_state.end_of_dataloader and self.gradient_state.remainder > 0:
            def _adjust(tensor):
                return tensor[: self.gradient_state.remainder]

            if all_tensors and not use_gather_object:
                data = ops.recursively_apply(_adjust, data)
            else:
                try:
                    data = data[: self.gradient_state.remainder]
                except TypeError:
                    # Gathered python objects that don't support slicing (e.g. a
                    # dict) can't be truncated; return them whole rather than
                    # fail the metrics path.  Any other error is a real bug and
                    # propagates.
                    logger.warning_once(
                        "gather_for_metrics could not truncate duplicate end-of-epoch "
                        "samples on a non-sliceable object; returning data unmodified."
                    )
        return data

    def reduce(self, tensor, reduction: str = "sum", scale: float = 1.0):
        return ops.reduce(tensor, reduction=reduction, scale=scale)

    def pad_across_processes(self, tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
        return ops.pad_across_processes(tensor, dim=dim, pad_index=pad_index, pad_first=pad_first)

    # ------------------------------------------------------------- utilities
    @contextlib.contextmanager
    def autocast(self, autocast_handler=None):
        """Parity context: precision is a functional policy here (no-op scope).

        The reference patches forward with an autocast ctx (``accelerator.py:3323``);
        on this stack every compiled fn already applies ``PrecisionPolicy``.
        """
        yield

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches: Optional[bool] = None):
        """Parity context (reference ``accelerator.py:1072-1157``).

        Uneven inputs cannot reach compiled SPMD steps: ``even_batches`` index math
        guarantees equal batch counts per process, so this is a no-op scope.
        """
        yield

    def unwrap_model(self, model, keep_fp32_wrapper: bool = True):
        from .utils.other import extract_model_from_parallel

        return extract_model_from_parallel(model, keep_fp32_wrapper)

    def free_memory(self, *objects):
        """Release compiled/jitted caches and live buffers (reference ``accelerator.py:3158``)."""
        self._jit_cache.clear()
        self._latest_state = None
        self._latest_state_by_tx.clear()
        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        gc.collect()
        jax.clear_caches()
        return objects

    def clear(self, *objects):
        return self.free_memory(*objects)

    def set_trigger(self):
        """Flag this process for a cross-process breakpoint (reference ``accelerator.py:2148``)."""
        self.flag_tensor = 1

    def check_trigger(self) -> bool:
        """True if any process called ``set_trigger`` (reference ``accelerator.py:2190``)."""
        flags = ops.gather_object([self.flag_tensor or 0])
        triggered = any(bool(f) for f in flags)
        if triggered:
            self.flag_tensor = 0
        return triggered

    def get_state_dict(self, state_or_params, unwrap: bool = True):
        """Full host copy of parameters (reference ``accelerator.py:3217-3284``)."""
        params = state_or_params.params if isinstance(state_or_params, TrainState) else state_or_params
        return jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x)), params)

    def register_for_checkpointing(self, *objects):
        """Register custom stateful objects for save_state/load_state (reference ``:3286``)."""
        invalid = [o for o in objects if not (hasattr(o, "state_dict") and hasattr(o, "load_state_dict"))]
        if invalid:
            raise ValueError(
                f"All objects must have state_dict/load_state_dict methods; got {invalid}"
            )
        self._custom_objects.extend(objects)

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        return skip_first_batches(dataloader, num_batches=num_batches)

    # ------------------------------------------------------------ checkpoints
    def save_state(self, output_dir: Optional[str] = None, state: Optional[TrainState] = None, **save_kwargs):
        from .checkpointing import save_accelerator_state

        return save_accelerator_state(self, output_dir, state, **save_kwargs)

    def load_state(self, input_dir: Optional[str] = None, state: Optional[TrainState] = None, **load_kwargs):
        from .checkpointing import load_accelerator_state

        return load_accelerator_state(self, input_dir, state, **load_kwargs)

    def save_model(
        self,
        state_or_params,
        save_directory: str,
        max_shard_size: Union[int, str] = "10GB",
        safe_serialization: bool = True,
        save_dtype=None,
    ):
        from .checkpointing import save_model

        if (
            save_dtype is None
            and self.state.zero_plugin is not None
            and self.state.zero_plugin.zero3_save_16bit_model
        ):
            save_dtype = jnp.bfloat16
        return save_model(
            self, state_or_params, save_directory, max_shard_size=max_shard_size,
            safe_serialization=safe_serialization, save_dtype=save_dtype,
        )

    def register_save_state_pre_hook(self, hook: Callable):
        handle = object()
        self._save_model_state_pre_hooks[handle] = hook
        return handle

    def register_load_state_pre_hook(self, hook: Callable):
        handle = object()
        self._load_model_state_pre_hooks[handle] = hook
        return handle

    # --------------------------------------------------------------- tracking
    def init_trackers(self, project_name: str, config: Optional[dict] = None, init_kwargs: dict = {}):
        from .tracking import filter_trackers

        self.trackers = filter_trackers(self.log_with, self.logging_dir, project_name, config, init_kwargs)

    @property
    def logging_dir(self):
        return self.project_configuration.logging_dir

    def log(self, values: dict, step: Optional[int] = None, log_kwargs: dict = {}):
        for tracker in self.trackers:
            tracker.log(values, step=step, **log_kwargs.get(tracker.name, {}))

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"{name} is not an available tracker stored inside the Accelerator")

    def end_training(self):
        for tracker in self.trackers:
            tracker.finish()

    # ---------------------------------------------------------------- profile
    @contextlib.contextmanager
    def profile(self, log_dir: Optional[str] = None):
        """First-class profiler capture (exceeds reference; SURVEY §5.1).

        Wraps ``jax.profiler`` trace capture; view with TensorBoard or Perfetto.
        While the capture is live, telemetry spans (``tracer.span`` /
        ``telemetry.span``) also enter ``jax.profiler.TraceAnnotation`` so the
        host-side phase names line up against the device timeline.
        """
        log_dir = log_dir or os.path.join(self.project_dir or ".", "profile")
        jax.profiler.start_trace(log_dir)
        set_device_trace_active(True)
        try:
            with self.tracer.span("profile", log_dir=log_dir):
                yield
        finally:
            set_device_trace_active(False)
            jax.profiler.stop_trace()
