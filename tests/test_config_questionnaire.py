"""Questionnaire completeness: the interactive config alone must reproduce a
FULL plugin surface with no launch flags (reference
``get_cluster_input``, ``commands/config/cluster.py:49-520``).

Flow under test: scripted answers -> get_cluster_input() -> YAML round-trip ->
prepare_launch_env() -> plugin ``__post_init__`` env rehydration — all four
config layers, asserting field-for-field equality at the end.
"""

import builtins

import pytest

from accelerate_tpu.commands.config.cluster import get_cluster_input
from accelerate_tpu.commands.config.config_args import ClusterConfig
from accelerate_tpu.commands.launch import prepare_launch_env
from accelerate_tpu.utils.dataclasses import (
    CollectiveKwargs,
    CompilationConfig,
    FullyShardedDataParallelPlugin,
    ModelParallelPlugin,
    ShardingStrategy,
    StateDictType,
    ZeroPlugin,
)

ENV_KEYS = [
    "ACCELERATE_MIXED_PRECISION", "ACCELERATE_DEBUG_MODE",
    "ACCELERATE_GRADIENT_ACCUMULATION_STEPS", "ACCELERATE_MESH",
    "ACCELERATE_USE_FSDP", "FSDP_SHARDING_STRATEGY", "FSDP_OFFLOAD_PARAMS",
    "FSDP_MIN_NUM_PARAMS", "FSDP_STATE_DICT_TYPE", "FSDP_ACTIVATION_CHECKPOINTING",
    "FSDP_OFFLOAD_OPTIMIZER", "FSDP_OFFLOAD_UPDATE_CHUNK_MB",
    "FSDP_OFFLOAD_UPDATE_OVERLAP", "FSDP_NVME_PATH", "FSDP_OFFLOAD_MASTER_WEIGHTS",
    "ACCELERATE_USE_DEEPSPEED", "ACCELERATE_DEEPSPEED_ZERO_STAGE",
    "ACCELERATE_DEEPSPEED_OFFLOAD_OPTIMIZER_DEVICE",
    "ACCELERATE_DEEPSPEED_OFFLOAD_PARAM_DEVICE", "ACCELERATE_DEEPSPEED_NVME_PATH",
    "ACCELERATE_DEEPSPEED_GRADIENT_CLIPPING",
    "ACCELERATE_DEEPSPEED_ZERO3_SAVE_16BIT_MODEL",
    "ACCELERATE_DEEPSPEED_OFFLOAD_UPDATE_CHUNK_MB",
    "ACCELERATE_DEEPSPEED_OFFLOAD_UPDATE_OVERLAP",
    "ACCELERATE_USE_MEGATRON_LM", "MEGATRON_LM_TP_DEGREE", "MEGATRON_LM_PP_DEGREE",
    "MEGATRON_LM_SP_DEGREE", "MEGATRON_LM_EP_DEGREE",
    "MEGATRON_LM_NUM_MICRO_BATCHES", "MEGATRON_LM_RECOMPUTE_ACTIVATIONS",
    "ACCELERATE_GRAD_REDUCE_DTYPE", "ACCELERATE_COMM_HOOK",
    "ACCELERATE_POWERSGD_RANK", "ACCELERATE_REMAT_POLICY", "ACCELERATE_SCAN_LAYERS",
]


def _answer_script(monkeypatch, answers):
    it = iter(answers)

    def fake_input(prompt=""):
        try:
            return next(it)
        except StopIteration:
            return ""  # accept defaults for anything beyond the script

    monkeypatch.setattr(builtins, "input", fake_input)
    # pin the input() fallback path: under `pytest -s` on a real terminal the
    # choices questions would take the arrow-key menu branch (raw keypress
    # reads) and ignore the scripted answers entirely
    import sys as _sys

    monkeypatch.setattr(_sys.stdin, "isatty", lambda: False, raising=False)


def _roundtrip(config: ClusterConfig, tmp_path) -> ClusterConfig:
    path = str(tmp_path / "config.yaml")
    config.to_yaml_file(path)
    return ClusterConfig.from_yaml_file(path)


def _apply_env(monkeypatch, env):
    for k in ENV_KEYS:
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        if k in ENV_KEYS:
            monkeypatch.setenv(k, v)


class TestZeroFlow:
    def test_full_zero_plugin_without_flags(self, monkeypatch, tmp_path):
        _answer_script(monkeypatch, [
            "1",            # machines
            "no",           # cpu only
            "bf16",         # mixed precision
            "no",           # debug
            "4",            # grad accum
            "dp=2,fsdp=4",  # mesh
            "no",           # fsdp?
            "yes",          # zero?
            "no",           # from DS json?
            "3",            # stage
            "nvme",         # offload optimizer
            "cpu",          # offload param
            "/mnt/nvme0",   # nvme path
            "-1",           # chunk mb (adaptive)
            "2",            # overlap
            "1.0",          # grad clipping
            "yes",          # zero3 save 16bit
            "yes",          # model parallel?
            "2", "2", "1", "1",  # tp, pp, sp, ep
            "no",           # recompute activations
            "12",           # num micro batches (pp > 1)
            "yes",          # comm tuning?
            "bf16",         # wire dtype
            "powersgd",     # hook
            "2",            # rank
            "yes",          # compile tuning?
            "proj_saveable",  # remat policy
            "yes",          # scan layers
        ])
        cfg = get_cluster_input()
        cfg = _roundtrip(cfg, tmp_path)

        assert cfg.mixed_precision == "bf16"
        assert cfg.gradient_accumulation_steps == 4
        assert cfg.mesh == {"dp": 2, "fsdp": 4}
        assert cfg.zero_config == {
            "zero_stage": 3, "offload_optimizer_device": "nvme",
            "offload_param_device": "cpu", "nvme_path": "/mnt/nvme0",
            "offload_update_chunk_mb": -1, "offload_update_overlap": 2,
            "gradient_clipping": 1.0, "zero3_save_16bit_model": True,
        }
        assert cfg.model_parallel_config == {
            "tp_degree": 2, "pp_degree": 2, "sp_degree": 1, "ep_degree": 1,
            "recompute_activations": False, "num_micro_batches": 12,
        }
        assert cfg.comm_config == {
            "grad_reduce_dtype": "bf16", "comm_hook": "powersgd", "powersgd_rank": 2,
        }
        assert cfg.compilation_config == {"remat_policy": "proj_saveable", "scan_layers": True}

        env = prepare_launch_env(cfg)
        _apply_env(monkeypatch, env)

        zp = ZeroPlugin()
        assert zp.zero_stage == 3
        assert zp.offload_optimizer_device == "nvme"
        assert zp.offload_param_device == "cpu"
        assert zp.nvme_path == "/mnt/nvme0"
        assert zp.gradient_clipping == 1.0
        assert zp.zero3_save_16bit_model is True
        assert zp.offload_update_chunk_mb == -1
        assert zp.offload_update_overlap == 2

        mp = ModelParallelPlugin()
        assert (mp.tp_degree, mp.pp_degree, mp.sp_degree) == (2, 2, 1)
        assert mp.expert_parallel_degree == 1
        assert mp.num_micro_batches == 12
        assert mp.recompute_activations is False

        ck = CollectiveKwargs.from_env()
        assert ck.grad_reduce_dtype == "bf16"
        assert ck.comm_hook == "powersgd"
        assert ck.powersgd_rank == 2

        cc = CompilationConfig.from_env()
        assert cc.remat_policy == "proj_saveable"
        assert cc.scan_layers is True


class TestFsdpFlow:
    def test_full_fsdp_plugin_without_flags(self, monkeypatch, tmp_path):
        _answer_script(monkeypatch, [
            "1",                 # machines
            "no",                # cpu only
            "bf16",              # mixed precision
            "no",                # debug
            "1",                 # grad accum
            "fsdp=8",            # mesh
            "yes",               # fsdp?
            "HYBRID_SHARD",      # strategy
            "yes",               # offload params
            "4096",              # min num params
            "FULL_STATE_DICT",   # state dict type
            "yes",               # activation checkpointing
            "yes",               # offload optimizer
            "yes",               # master weights
            "1024",              # chunk mb
            "1",                 # overlap
            "yes",               # nvme tier
            "/mnt/nvme1",        # nvme path
            "no",                # model parallel?
            "no",                # comm tuning?
            "no",                # compile tuning?
        ])
        cfg = _roundtrip(get_cluster_input(), tmp_path)
        env = prepare_launch_env(cfg)
        _apply_env(monkeypatch, env)

        fp = FullyShardedDataParallelPlugin()
        assert fp.sharding_strategy == ShardingStrategy.HYBRID_SHARD
        assert fp.cpu_offload is True
        assert fp.min_weight_size == 4096
        assert fp.state_dict_type == StateDictType.FULL_STATE_DICT
        assert fp.activation_checkpointing is True
        assert fp.offload_optimizer is True
        assert fp.offload_master_weights is True
        assert fp.offload_update_chunk_mb == 1024
        assert fp.offload_update_overlap == 1
        assert fp.offload_optimizer_nvme_path == "/mnt/nvme1"

    def test_deepspeed_json_shortcut(self, monkeypatch, tmp_path):
        _answer_script(monkeypatch, [
            "1", "no", "bf16", "no", "1", "",   # topology
            "no",                                # fsdp?
            "yes",                               # zero?
            "yes",                               # from DS json
            "/cfg/ds.json",                      # path
            "no", "no", "no",                    # mp / comm / compile
        ])
        cfg = _roundtrip(get_cluster_input(), tmp_path)
        assert cfg.zero_config == {"deepspeed_config_file": "/cfg/ds.json"}
        env = prepare_launch_env(cfg)
        assert env["ACCELERATE_DEEPSPEED_CONFIG_FILE"] == "/cfg/ds.json"
        assert "ACCELERATE_USE_DEEPSPEED" not in env  # the JSON is authoritative
