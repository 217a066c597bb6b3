"""Utilities: config dataclasses, pytree operations, seeding, availability probes."""

from .dataclasses import (
    AutocastKwargs,
    CollectiveKwargs,
    CompilationConfig,
    DataLoaderConfiguration,
    DistributedType,
    FP8RecipeKwargs,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    GradScalerKwargs,
    InitProcessGroupKwargs,
    KwargsHandler,
    MeshConfig,
    ModelParallelPlugin,
    PrecisionPolicy,
    PrecisionType,
    ProjectConfiguration,
    RNGType,
    ShardingStrategy,
    StateDictType,
    ZeroPlugin,
    parse_choice_from_env,
    parse_flag_from_env,
    str_to_bool,
)
from .imports import (
    is_datasets_available,
    is_pallas_available,
    is_safetensors_available,
    is_tensorboard_available,
    is_torch_available,
    is_tpu_available,
    is_tpu_platform,
    is_transformers_available,
    is_wandb_available,
)
from .ds_compat import optax_from_ds_config
from .operations import (
    ConvertOutputsToFp32,
    DistributedOperationException,
    broadcast,
    broadcast_object_list,
    concatenate,
    convert_outputs_to_fp32,
    convert_to_fp32,
    find_batch_size,
    find_device,
    gather,
    gather_object,
    honor_type,
    listify,
    pad_across_processes,
    pad_input_tensors,
    recursively_apply,
    reduce,
    send_to_device,
    slice_tensors,
)
from .modeling import (
    compute_module_sizes,
    flatten_tree,
    get_balanced_memory,
    get_max_layer_size,
    infer_auto_device_map,
    top_level_modules,
    unflatten_tree,
)
from .offload import (
    OffloadedWeightsLoader,
    PrefixedDataset,
    load_offloaded_weight,
    offload_state_dict,
    offload_weight,
)
from .memory import (
    clear_device_cache,
    find_executable_batch_size,
    release_memory,
    should_reduce_batch_size,
)
from .other import (
    check_os_kernel,
    clear_environment,
    convert_bytes,
    extract_model_from_parallel,
    is_port_in_use,
    merge_dicts,
    patch_environment,
    save,
)
from .random import make_rng_key, set_seed, synchronize_rng_state, synchronize_rng_states
from .tqdm import tqdm
