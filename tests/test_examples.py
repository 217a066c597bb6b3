"""Smoke-run every example script (reference tests/test_examples.py runs each
by_feature script; here each runs as a subprocess on the 8-device CPU mesh)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def run_example(script, *args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(flags + ["--xla_force_host_platform_device_count=8"])
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, f"{script} failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


def test_nlp_example():
    out = run_example("nlp_example.py", "--num_epochs", "1")
    assert "epoch 0" in out


def test_nlp_example_fsdp_bf16():
    out = run_example("nlp_example.py", "--num_epochs", "1", "--fsdp", "--mixed_precision", "bf16")
    assert "epoch 0" in out


def test_cv_example():
    out = run_example("cv_example.py", "--num_epochs", "1", "--batch_size", "32")
    assert "epoch 0" in out


def test_complete_nlp_example_checkpoint_and_resume(tmp_path):
    out = run_example(
        "complete_nlp_example.py", "--num_epochs", "1",
        "--checkpointing_steps", "epoch", "--with_tracking",
        "--project_dir", str(tmp_path),
    )
    assert "epoch 0" in out
    assert (tmp_path / "epoch_0").is_dir()
    out = run_example(
        "complete_nlp_example.py", "--num_epochs", "2",
        "--resume_from_checkpoint", str(tmp_path / "epoch_0"),
        "--project_dir", str(tmp_path),
    )
    assert "Resuming" in out and "epoch 1" in out and "epoch 0:" not in out


def test_feature_gradient_accumulation():
    out = run_example("by_feature/gradient_accumulation.py", "--num_epochs", "1")
    assert "optimizer_steps" in out


def test_feature_checkpointing(tmp_path):
    out = run_example("by_feature/checkpointing.py", "--project_dir", str(tmp_path))
    assert "resumed epoch 1" in out


def test_feature_tracking(tmp_path):
    out = run_example("by_feature/tracking.py", "--project_dir", str(tmp_path), "--num_epochs", "1")
    assert "metric records" in out


def test_feature_memory():
    out = run_example("by_feature/memory.py")
    assert "Executable batch size found: 16" in out


def test_feature_local_sgd():
    out = run_example("by_feature/local_sgd.py", "--num_epochs", "1")
    assert "optimizer step" in out


def test_feature_early_stopping():
    out = run_example("by_feature/early_stopping.py", "--num_epochs", "8")
    assert "early stop" in out or "without triggering" in out


def test_feature_fp8():
    out = run_example("by_feature/fp8.py", "--steps", "15")
    assert "fp8 training" in out


def test_feature_fsdp():
    out = run_example("by_feature/fsdp.py", "--zero_stage", "3", "--steps", "10")
    # ZeRO-3 must actually shard the params (not just name an fsdp mesh axis)
    spec_line = next(line for line in out.splitlines() if "param spec" in line)
    assert "fsdp" in spec_line, spec_line


def test_feature_big_model_inference():
    out = run_example("by_feature/big_model_inference.py")
    assert "pooled-HBM sharded" in out
    out = run_example("by_feature/big_model_inference.py", "--stream")
    assert "host-streamed" in out


def test_feature_finetune_hf_checkpoint():
    out = run_example("by_feature/finetune_hf_checkpoint.py", "--steps", "12")
    assert "finetune_hf_checkpoint: OK" in out


def test_feature_streaming_hooks():
    out = run_example("by_feature/streaming_hooks.py")
    assert "streaming_hooks example: OK" in out
    assert "pinned-cache hits: 4" in out


def test_feature_profiler(tmp_path):
    out = run_example("by_feature/profiler.py", "--project_dir", str(tmp_path))
    assert "profile captured" in out


def test_feature_multi_process_metrics():
    out = run_example("by_feature/multi_process_metrics.py", "--num_epochs", "1")
    assert "no duplicates counted" in out


def test_feature_model_parallelism():
    out = run_example("by_feature/model_parallelism.py", "--tp_degree", "2", "--steps", "10")
    assert "column-parallel" in out and "tp" in out


def test_feature_automatic_gradient_accumulation():
    out = run_example("by_feature/automatic_gradient_accumulation.py")
    # started at 64, simulated OOM drops to 32, accumulation doubles to keep
    # the effective batch at 64
    assert "batch_size=32 x accum=2" in out
    assert "[64, 32]" in out


def test_feature_cross_validation():
    out = run_example("by_feature/cross_validation.py", "--num_folds", "2")
    assert "ensemble of 2 folds" in out


def test_feature_schedule_free():
    out = run_example("by_feature/schedule_free.py", "--num_epochs", "1")
    assert "eval_acc(schedule-free params)" in out


def test_inference_hf_checkpoint_generate():
    out = run_example("inference/hf_checkpoint_generate.py", "--max_new_tokens", "4")
    assert "hf_checkpoint_generate: OK" in out


def test_inference_distributed_generate():
    out = run_example("inference/distributed_generate.py")
    assert "8 continuations generated" in out


def test_inference_pipeline_generate():
    out = run_example("inference/pipeline_generate.py")
    assert "pipeline over 2 stage(s)" in out


def test_bench_smoke_tasks():
    """The zero3/fsdp BASELINE bench configs run end to end (tiny geometry)."""
    import json

    for extra in (("--task", "zero3"), ("--task", "fsdp"),
                  ("--task", "zero3", "--offload-device", "nvme"),
                  ("--task", "cv"), ("--task", "longseq")):
        env_out = run_example(os.path.join("..", "bench.py"), *extra, "--smoke")
        row = json.loads([l for l in env_out.splitlines() if l.startswith("{")][-1])
        assert row["value"] > 0, (extra, row)


def test_feature_ddp_comm_hook():
    out = run_example("by_feature/ddp_comm_hook.py", "--num_epochs", "1")
    assert "wire compression" in out
